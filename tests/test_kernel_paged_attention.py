"""Parity sweeps for the ISSUE-7 paged-attention kernel families.

``paged_flash_decode`` (bf16 + int8) and ``bgpp_paged_attend`` (fused
two-phase plane-scan / top-k / int8 attend) must agree with their pure-jnp
``ref.py`` oracles BIT-for-bit in interpret mode — the dispatch wrappers
jit both paths, and under jit the kernel body and the oracle lower to the
same reduction orders (the eager paths can drift by one f32 ulp in fused
softmax chains, which is why every assertion here goes through the public
jitted wrappers).

Swept: page-boundary position spans, deliberately shuffled (non-identity)
page tables / phys maps so logical->physical translation is actually
exercised, cache fills below / at / above the bgpp keep budget, and GQA
ratios including Hq == Hk.  ``paged_flash_decode`` reads a stacked cache
whose layers hold different contents, so a wrong layer index fails.  A second class checks the kernel family
against the ENGINE's legacy jnp attend on real caches (the contract
``serving.kernel_decode`` relies on when routing the serve_step), and a
third pins the actionable build-time validation errors.
"""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import MCBPOptions
from repro.kernels.bgpp_paged_attend import bgpp_paged_attend
from repro.kernels.paged_flash_decode import paged_flash_decode
from repro.kernels.paged_flash_decode.kernel import (
    slot_page_rows, slot_table,
)
from repro.serving import engine, kernel_decode, kv_cache as kvc

jax.config.update("jax_platform_name", "cpu")

D = 16  # head_dim — a multiple of 8 so bgpp planes pack bytewise
PAGE = 8


def _plan(S, rounds=4, keep=0.25):
    """The serving plan's arithmetic (kv_cache.bgpp_decode_plan) without a
    config object — synthetic sweeps pick keep ratios per test."""
    k_max = max(1, min(S, math.ceil(keep * S)))
    survivors = (S,) + tuple(max(k_max, S >> r) for r in range(1, rounds))
    return rounds, k_max, survivors


LAYERS = 3  # stacked operands: a middle and a last layer to pick


def _dense_pools(rng, n_tok, Hk, fmt, layers=LAYERS):
    """Token-row stacks ``(L, n_tok, Hk*D)``, every layer drawn anew, with
    int8 scales as token-major pools ``(L, n_tok, Hk)``."""
    kf = jnp.asarray(rng.normal(size=(layers, n_tok, Hk, D)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(layers, n_tok, Hk, D)), jnp.float32)
    rows = lambda x: x.reshape(layers, n_tok, Hk * D)
    if fmt == "bf16":
        return (rows(kf).astype(jnp.bfloat16), rows(vf).astype(jnp.bfloat16),
                {})
    k_q, ks = kvc.quantize_kv(kf)
    v_q, vs = kvc.quantize_kv(vf)
    return rows(k_q), rows(v_q), {"k_scale": ks, "v_scale": vs}


def _slot_scales(scales, B, S):
    """Token-major scale pools ``(L, B*S, Hk)`` as the slot stacks keep
    them: heads-major per-slot rows ``(L, B, Hk, S)``."""
    return {n: jnp.swapaxes(a.reshape(a.shape[0], B, S, -1), 2, 3)
            for n, a in scales.items()}


def _bgpp_pools(rng, n_tok, Hk):
    kf = jnp.asarray(rng.normal(size=(n_tok, Hk, D)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(n_tok, Hk, D)), jnp.float32)
    k_q, k_scale = kvc.quantize_kv(kf)
    v_q, v_scale = kvc.quantize_kv(vf)
    planes, sign = kvc.k_to_bitplanes(k_q)
    return planes, sign, k_scale, v_q, v_scale


class TestPagedFlashDecodeParity:
    # pos 7 ends page 0, pos 8 starts page 1: both page-boundary sides
    @pytest.mark.parametrize("fmt", ["bf16", "int8"])
    @pytest.mark.parametrize("g", [1, 3])
    @pytest.mark.parametrize("pos_val", [0, 7, 8, 13])
    def test_interpret_matches_ref_on_shuffled_pages(self, fmt, g, pos_val):
        B, Hk, S = 2, 2, 16
        pp = S // PAGE
        rng = np.random.default_rng(
            1000 * (fmt == "int8") + 100 * g + pos_val
        )
        n_pages = B * pp + 2  # spare pages: garbage rows behind the table
        k, v, scales = _dense_pools(rng, n_pages * PAGE, Hk, fmt)
        # non-identity table: a kernel that forgot to translate pages
        # reads the wrong tokens (and possibly the spare garbage pages)
        table = jnp.asarray(
            rng.permutation(n_pages)[: B * pp].reshape(B, pp).astype(np.int32)
        )
        q = jnp.asarray(rng.normal(size=(B, Hk, g, D)), jnp.float32)
        pos = jnp.asarray([pos_val, max(0, pos_val - 1)], jnp.int32)

        out_i = paged_flash_decode(
            q, k, v, table, pos, 1, page_size=PAGE, mode="interpret",
            **scales
        )
        out_r = paged_flash_decode(
            q, k, v, table, pos, 1, page_size=PAGE, mode="ref", **scales
        )
        assert out_i.shape == (B, Hk, g, D)
        assert np.array_equal(np.asarray(out_i), np.asarray(out_r)), (
            f"{fmt} g={g} pos={pos_val}: interpret kernel diverges from the "
            f"jnp oracle (max |d| "
            f"{np.max(np.abs(np.asarray(out_i, np.float32) - np.asarray(out_r, np.float32)))})"
        )

    def test_unmapped_pages_never_contribute(self):
        """Lanes behind -1 page-table entries clamp to row 0 and are
        position-masked: poisoning the pool's unreached rows with huge
        values must not move the output."""
        B, Hk, g, S = 1, 2, 2, 16
        rng = np.random.default_rng(7)
        k, v, _ = _dense_pools(rng, 4 * PAGE, Hk, "bf16")
        table = jnp.asarray([[1, -1]], jnp.int32)  # page 1 live, page 2 unmapped
        q = jnp.asarray(rng.normal(size=(B, Hk, g, D)), jnp.float32)
        pos = jnp.asarray([PAGE - 1], jnp.int32)  # only page 1's lanes valid
        base = paged_flash_decode(
            q, k, v, table, pos, 1, page_size=PAGE, mode="interpret"
        )
        k_p = k.at[:, 2 * PAGE:].set(jnp.asarray(1e4, k.dtype))
        v_p = v.at[:, 2 * PAGE:].set(jnp.asarray(1e4, v.dtype))
        poisoned = paged_flash_decode(
            q, k_p, v_p, table, pos, 1, page_size=PAGE, mode="interpret"
        )
        assert np.array_equal(np.asarray(base), np.asarray(poisoned))

    @pytest.mark.parametrize("fmt", ["bf16", "int8"])
    @pytest.mark.parametrize("layout", ["paged", "slot"])
    @pytest.mark.parametrize("layer", [1, LAYERS - 1])
    def test_reads_the_named_layer(self, fmt, layout, layer):
        """Layer ``layer`` of a stack whose layers differ attends exactly
        as that layer alone does, and no other layer's output matches it.
        The slot form reads the ``(L, B*S, Hk*D)`` merge of a slot stack in
        sub-pages (S/P = 4) with its scales as heads-major per-slot rows."""
        B, Hk, g, S = 2, 2, 3, 16
        rng = np.random.default_rng(10 * layer + (fmt == "int8"))
        if layout == "slot":
            k, v, scales = _dense_pools(rng, B * S, Hk, fmt)
            scales = _slot_scales(scales, B, S)
            page, table = S // 4, slot_table(B, S, S // 4)
        else:
            page, pp = PAGE, S // PAGE
            k, v, scales = _dense_pools(rng, (B * pp + 2) * PAGE, Hk, fmt)
            table = jnp.asarray(rng.permutation(B * pp + 2)[:B * pp]
                                .reshape(B, pp).astype(np.int32))
        q = jnp.asarray(rng.normal(size=(B, Hk, g, D)), jnp.float32)
        pos = jnp.asarray([S - 1, S // 2], jnp.int32)

        def run(li, mode="interpret", only=False):
            pick = (lambda a: a[li:li + 1]) if only else (lambda a: a)
            return np.asarray(paged_flash_decode(
                q, pick(k), pick(v), table, pos, 0 if only else li,
                page_size=page, mode=mode,
                **{n: pick(a) for n, a in scales.items()},
            ))

        out = run(layer)
        assert np.array_equal(out, run(layer, mode="ref"))
        assert np.array_equal(out, run(layer, only=True))
        for other in range(LAYERS):
            if other != layer:
                assert not np.allclose(out, run(other)), (
                    f"layer {other} reads like layer {layer}")

    @pytest.mark.parametrize("fmt", ["bf16", "int8"])
    def test_slot_sub_pages_match_one_page(self, fmt):
        """A slot stack read in S/P sub-pages attends exactly as it does
        read in one S-row page per slot."""
        B, Hk, g, S = 2, 2, 3, 32
        rng = np.random.default_rng(5 + (fmt == "int8"))
        k, v, scales = _dense_pools(rng, B * S, Hk, fmt)
        scales = _slot_scales(scales, B, S)
        q = jnp.asarray(rng.normal(size=(B, Hk, g, D)), jnp.float32)
        pos = jnp.asarray([S - 3, 6], jnp.int32)
        outs = [np.asarray(paged_flash_decode(
            q, k, v, slot_table(B, S, P), pos, 2, page_size=P,
            mode="interpret", **scales)) for P in (S, S // 2, S // 4)]
        assert all(np.array_equal(outs[0], o) for o in outs[1:])


@pytest.mark.parametrize("S,width,itemsize,pages", [
    (2048, 8 * 128, 1, 2),  # phi4-chat-int8: 20 slots x 2048, 8 kv heads
    (4096, 8 * 128, 1, 4),  # a deepseek7b-longdoc-1x4 device: 8 of 32 heads
    (2048, 8 * 128, 2, 4),  # bf16 rows of the same widths
    (30, 2 * 16, 1, 1),  # ragged: no tile-multiple divisor, one page
    (65536, 2 * 16, 1, 2),  # narrow rows: a long slot still splits
])
def test_slot_page_rows_split_by_shape(S, width, itemsize, pages):
    """A slot's sub-page is the largest tile-multiple divisor of S within
    the block budget, whole S when none is."""
    P = slot_page_rows(S, width, itemsize)
    assert S % P == 0 and S // P == pages, P
    assert P == S or P % (32 // itemsize) == 0


class TestBgppPagedAttendParity:
    # keep=0.5 at S=16 -> k_max=8: fills below / at / above the budget
    @pytest.mark.parametrize("g", [1, 2, 3])
    @pytest.mark.parametrize("s_ctx", [3, 8, 13, 16])
    def test_interpret_matches_ref_on_shuffled_phys(self, g, s_ctx):
        B, Hk, S = 2, 2, 16
        rng = np.random.default_rng(100 * g + s_ctx)
        pp = S // PAGE
        n_pages = B * pp + 2  # spare pages the shuffled table skips
        planes, sign, ks, v, vs = _bgpp_pools(rng, n_pages * PAGE, Hk)
        # non-identity table: a kernel that forgot to translate pages reads
        # the wrong tokens (and possibly the spare garbage pages)
        table = jnp.asarray(
            rng.permutation(n_pages)[: B * pp].reshape(B, pp).astype(np.int32)
        )
        q = jnp.asarray(rng.normal(size=(B, Hk, g, D)), jnp.float32)
        pos = jnp.asarray([s_ctx - 1, max(0, s_ctx - 2)], jnp.int32)
        rounds, k_max, survivors = _plan(S, rounds=4, keep=0.5)

        args = (q, planes, sign, ks, v, vs, table, pos)
        kw = dict(page_size=PAGE, rounds=rounds, k_max=k_max,
                  survivors=survivors)
        out_i = bgpp_paged_attend(*args, mode="interpret", **kw)
        out_r = bgpp_paged_attend(*args, mode="ref", **kw)
        assert out_i.shape == (B, Hk, g, D)
        assert np.array_equal(np.asarray(out_i), np.asarray(out_r)), (
            f"g={g} s_ctx={s_ctx}: fused bgpp kernel diverges from the jnp "
            f"oracle (max |d| {np.max(np.abs(np.asarray(out_i - out_r)))})"
        )

    @pytest.mark.parametrize("keep", [0.25, 1.0])
    def test_plan_sweep(self, keep):
        """rounds/keep variations (k_max = S at keep=1.0 degenerates the
        top-k to 'everything survives') stay oracle-exact."""
        B, Hk, g, S = 1, 2, 3, 32
        rng = np.random.default_rng(int(keep * 100))
        planes, sign, ks, v, vs = _bgpp_pools(rng, B * S, Hk)
        table = jnp.arange(B * S // PAGE, dtype=jnp.int32).reshape(B, -1)
        q = jnp.asarray(rng.normal(size=(B, Hk, g, D)), jnp.float32)
        pos = jnp.asarray([S - 2], jnp.int32)
        rounds, k_max, survivors = _plan(S, rounds=4, keep=keep)
        args = (q, planes, sign, ks, v, vs, table, pos)
        kw = dict(page_size=PAGE, rounds=rounds, k_max=k_max,
                  survivors=survivors)
        out_i = bgpp_paged_attend(*args, mode="interpret", **kw)
        out_r = bgpp_paged_attend(*args, mode="ref", **kw)
        assert np.array_equal(np.asarray(out_i), np.asarray(out_r))


class TestSurvivorSelection:
    """The compiled BGPP kernel selects by lane masks (two bisections); it
    must pick exactly the set ``lax.top_k`` picks, lowest lane first among
    ties, with dropped lanes never chosen."""

    @pytest.mark.parametrize("seed,k", [(0, 1), (1, 5), (2, 16), (3, 31)])
    def test_mask_matches_top_k(self, seed, k):
        from repro.kernels.bgpp_paged_attend import kernel as bk

        rng = np.random.default_rng(seed)
        rows, S = 3, 48
        # few distinct values: ties at the k-th key are the common case
        key = rng.integers(-4, 4, (rows, S)).astype(np.int32) * 1024
        key[:, rng.permutation(S)[:6]] = bk._INVALID
        key[:, rng.permutation(S)[:4]] = bk._DROPPED
        lane = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (rows, S))
        got = np.asarray(bk._select(jnp.asarray(key), k, lane))
        _, idx = jax.lax.top_k(jnp.asarray(key), k)
        want = np.zeros((rows, S), bool)
        np.put_along_axis(want, np.asarray(idx), True, axis=1)
        assert np.array_equal(got, want)
        assert not got[key == bk._DROPPED].any()


# -------------------------------------------------------------------------
# engine-path parity on REAL caches (the kernel_decode routing contract)
# -------------------------------------------------------------------------

B_ENG, S_MAX = 2, 32
KEEP = 0.25


def _cfg():
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    return dataclasses.replace(
        cfg, mcbp=MCBPOptions(bgpp_rounds=4, bgpp_keep_ratio=KEEP)
    )


def _filled(cfg, fmt, s_ctx, seed, s_max=S_MAX):
    """Same random K/V in a shuffled-table paged cache and a slot cache."""
    rng = np.random.default_rng(seed)
    lp = kvc.layout_for(cfg, B_ENG, s_max, kv_format=fmt, layout="paged",
                        page_size=PAGE)
    ls = kvc.layout_for(cfg, B_ENG, s_max, kv_format=fmt)
    paged = kvc.init_cache_arrays(cfg, lp)
    slot = kvc.init_cache_arrays(cfg, ls)
    tbl = np.full((B_ENG, lp.pages_per_slot), -1, np.int32)
    perm = rng.permutation(lp.num_pages)
    npg = -(-s_ctx // PAGE)
    for b in range(B_ENG):
        tbl[b, :npg] = perm[b * lp.pages_per_slot:b * lp.pages_per_slot + npg]
    paged["page_table"] = jnp.asarray(tbl)
    Hk, Dh = cfg.num_kv_heads, cfg.head_dim
    k = jnp.asarray(rng.normal(size=(B_ENG, s_ctx, Hk, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B_ENG, s_ctx, Hk, Dh)), jnp.float32)
    for b in range(B_ENG):
        paged["global"] = kvc.write_prefill(
            paged["global"], 0, k[b:b + 1], v[b:b + 1], slot=b,
            page_table=paged["page_table"], page_size=PAGE, max_seq=s_max,
        )
        slot["global"] = kvc.write_prefill(
            slot["global"], 0, k[b:b + 1], v[b:b + 1], slot=b,
        )
    q = jnp.asarray(
        rng.normal(size=(B_ENG, cfg.num_heads, Dh)), jnp.float32
    )
    return lp, ls, paged, slot, q


class TestEnginePathParity:
    @pytest.mark.parametrize("s_ctx", [5, 13, 30])
    def test_bgpp_kernel_matches_engine_two_phase(self, s_ctx):
        cfg = _cfg()
        lp, _, paged, _, q = _filled(cfg, "bgpp", s_ctx, seed=s_ctx)
        phys = kvc.phys_table(paged["page_table"], PAGE, S_MAX)
        valid = jnp.arange(S_MAX)[None, :] < s_ctx
        eng = jax.jit(
            lambda q_, st, ph: engine._bgpp_paged_decode_attend(
                q_, st, 0, ph, valid, cfg
            )
        )(q, paged["global"], phys)

        pos = jnp.full((B_ENG,), s_ctx - 1, jnp.int32)
        ker = kernel_decode.decode_attend(
            q, paged["global"], 0, pos, cfg, lp, _no_mesh_rules(),
            "interpret", page_table=paged["page_table"],
        )
        assert ker is not None
        assert np.array_equal(np.asarray(eng), np.asarray(ker)), (
            f"s_ctx={s_ctx}: kernel-routed bgpp attend diverges from the "
            f"engine's two-phase path "
            f"(max |d| {np.max(np.abs(np.asarray(eng - ker)))})"
        )

    @pytest.mark.parametrize("fmt", ["bf16", "int8"])
    def test_dense_kernel_matches_engine_paged_entry(self, fmt):
        cfg, s_ctx = _cfg(), 13
        lp, _, paged, _, q = _filled(cfg, fmt, s_ctx, seed=3)
        phys = kvc.phys_table(paged["page_table"], PAGE, S_MAX)
        valid = jnp.arange(S_MAX)[None, :] < s_ctx
        eng = jax.jit(
            lambda q_, st, ph: engine._decode_attend(
                q_, kvc.paged_entry(st, 0, ph, heads=cfg.num_kv_heads),
                valid, cfg, fmt
            )
        )(q, paged["global"], phys)

        pos = jnp.full((B_ENG,), s_ctx - 1, jnp.int32)
        ker = kernel_decode.decode_attend(
            q, paged["global"], 0, pos, cfg, lp, _no_mesh_rules(),
            "interpret", page_table=paged["page_table"],
        )
        assert ker is not None
        # agreement to f32 rounding, not bitwise: the engine's einsums run
        # fused over the gathered (B, Hk, g, 1, S) view, the kernel's dots
        # per (slot, head) cell, and XLA may accumulate the two in different
        # orders (1.8e-7 observed on CPU for O(1) outputs; a compiled
        # Mosaic kernel differs from XLA in the same way)
        np.testing.assert_allclose(
            np.asarray(ker), np.asarray(eng), rtol=1e-6, atol=1e-6,
            err_msg=f"{fmt}: kernel-routed paged attend diverges from the "
                    f"engine's paged_entry path",
        )

    @pytest.mark.parametrize("fmt", ["bgpp", "int8"])
    def test_ragged_max_seq_matches_engine(self, fmt):
        """max_seq=30 with page_size=8: the tail page is only partially
        addressable.  serve_llm derives max_seq=prompt+steps+slack, which
        is rarely a page multiple — the kernel path must accept it and
        stay bit-identical (the flash kernel masks the page-tail lanes
        past pos; the bgpp phys map is row-level, no page walking)."""
        cfg, s_max, s_ctx = _cfg(), 30, 21
        lp, _, paged, _, q = _filled(cfg, fmt, s_ctx, seed=7, s_max=s_max)
        phys = kvc.phys_table(paged["page_table"], PAGE, s_max)
        valid = jnp.arange(s_max)[None, :] < s_ctx
        if fmt == "bgpp":
            eng = jax.jit(
                lambda q_, st, ph: engine._bgpp_paged_decode_attend(
                    q_, st, 0, ph, valid, cfg
                )
            )(q, paged["global"], phys)
        else:
            eng = jax.jit(
                lambda q_, st, ph: engine._decode_attend(
                    q_, kvc.paged_entry(st, 0, ph, heads=cfg.num_kv_heads),
                valid, cfg, fmt
                )
            )(q, paged["global"], phys)
        pos = jnp.full((B_ENG,), s_ctx - 1, jnp.int32)
        ker = kernel_decode.decode_attend(
            q, paged["global"], 0, pos, cfg, lp, _no_mesh_rules(),
            "interpret", page_table=paged["page_table"],
        )
        assert ker is not None
        assert np.array_equal(np.asarray(eng), np.asarray(ker)), (
            f"{fmt}: ragged max_seq={s_max} kernel attend diverges from "
            f"the engine "
            f"(max |d| {np.max(np.abs(np.asarray(eng - ker)))})"
        )

    @pytest.mark.parametrize("fmt", ["bf16", "int8", "bgpp"])
    def test_slot_pool_views_match_paged(self, fmt):
        """The slot layout's pool-ified stacks (transposes + identity maps)
        feed the SAME kernel as the paged layout — identical cache contents
        must produce identical outputs across layouts."""
        cfg, s_ctx = _cfg(), 13
        lp, ls, paged, slot, q = _filled(cfg, fmt, s_ctx, seed=11)
        phys = kvc.phys_table(paged["page_table"], PAGE, S_MAX)
        pos = jnp.full((B_ENG,), s_ctx - 1, jnp.int32)
        rules = _no_mesh_rules()
        out_p = kernel_decode.decode_attend(
            q, paged["global"], 0, pos, cfg, lp, rules, "interpret",
            page_table=paged["page_table"],
        )
        out_s = kernel_decode.decode_attend(
            q, slot["global"], 0, pos, cfg, ls, rules, "interpret",
        )
        assert out_p is not None and out_s is not None
        assert np.array_equal(np.asarray(out_p), np.asarray(out_s)), (
            f"{fmt}: slot pool-ification diverges from the paged pools "
            f"(max |d| {np.max(np.abs(np.asarray(out_p - out_s)))})"
        )


def _no_mesh_rules():
    """Minimal stand-in for ShardingRules off-mesh: decode_attend only
    reads ``.mesh`` (None -> unsharded local call)."""
    return type("R", (), {"mesh": None})()


# -------------------------------------------------------------------------
# build-time validation: actionable errors, not Pallas lowering failures
# -------------------------------------------------------------------------


class TestValidationErrors:
    def _bgpp_args(self):
        rng = np.random.default_rng(0)
        B, Hk, g, S = 1, 2, 2, 16
        planes, sign, ks, v, vs = _bgpp_pools(rng, B * S, Hk)
        table = jnp.arange(B * S // PAGE, dtype=jnp.int32).reshape(B, -1)
        q = jnp.zeros((B, Hk, g, D), jnp.float32)
        pos = jnp.zeros((B,), jnp.int32)
        return q, planes, sign, ks, v, vs, table, pos

    def test_flash_rejects_ungrouped_query(self):
        rng = np.random.default_rng(0)
        k, v, _ = _dense_pools(rng, 16, 2, "bf16")
        with pytest.raises(ValueError, match="grouped \\(B, Hk, g, D\\)"):
            paged_flash_decode(
                jnp.zeros((1, 4, D)), k, v,
                jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32), 0,
                page_size=PAGE,
            )

    def test_flash_rejects_head_shard_mismatch(self):
        rng = np.random.default_rng(0)
        k, v, _ = _dense_pools(rng, 16, 2, "bf16")
        with pytest.raises(ValueError, match="device-local head shard"):
            paged_flash_decode(
                jnp.zeros((1, 4, 1, D)), k, v,
                jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32), 0,
                page_size=PAGE,
            )

    def test_flash_rejects_ragged_pages(self):
        rng = np.random.default_rng(0)
        k, v, _ = _dense_pools(rng, 20, 2, "bf16")  # 20 rows, page 8
        with pytest.raises(ValueError, match="whole number of pages"):
            paged_flash_decode(
                jnp.zeros((1, 2, 1, D)), k, v,
                jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32), 0,
                page_size=PAGE,
            )

    def test_flash_rejects_lone_scale(self):
        rng = np.random.default_rng(0)
        k, v, scales = _dense_pools(rng, 16, 2, "int8")
        with pytest.raises(ValueError, match="BOTH k_scale and v_scale"):
            paged_flash_decode(
                jnp.zeros((1, 2, 1, D)), k, v,
                jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32), 0,
                page_size=PAGE, k_scale=scales["k_scale"],
            )

    def test_bgpp_rejects_bad_survivor_plan(self):
        q, planes, sign, ks, v, vs, table, pos = self._bgpp_args()
        with pytest.raises(ValueError, match="survivor widths"):
            bgpp_paged_attend(q, planes, sign, ks, v, vs, table, pos,
                              page_size=PAGE, rounds=2, k_max=4, survivors=(16, 8, 8))
        with pytest.raises(ValueError, match="survivors\\[0\\]"):
            bgpp_paged_attend(q, planes, sign, ks, v, vs, table, pos,
                              page_size=PAGE, rounds=2, k_max=4, survivors=(8, 8))
        with pytest.raises(ValueError, match="non-increasing"):
            bgpp_paged_attend(q, planes, sign, ks, v, vs, table, pos,
                              page_size=PAGE, rounds=2, k_max=4, survivors=(16, 17))
        with pytest.raises(ValueError, match="k_max"):
            bgpp_paged_attend(q, planes, sign, ks, v, vs, table, pos,
                              page_size=PAGE, rounds=2, k_max=12, survivors=(16, 8))

    def test_bgpp_rejects_unpacked_planes(self):
        q, planes, sign, ks, v, vs, table, pos = self._bgpp_args()
        with pytest.raises(ValueError, match="packed magnitude planes"):
            bgpp_paged_attend(q, planes[:3], sign, ks, v, vs, table, pos,
                              page_size=PAGE, rounds=2, k_max=4, survivors=(16, 8))

    def test_kernel_decode_validate_gqa(self):
        cfg = dataclasses.replace(_cfg(), num_heads=7)
        lp = kvc.layout_for(_cfg(), B_ENG, S_MAX, kv_format="bgpp",
                            layout="paged", page_size=PAGE)
        with pytest.raises(ValueError, match="GQA group size"):
            kernel_decode.validate(cfg, lp)

    def test_kernel_decode_validate_accepts_ragged_max_seq(self):
        # max_seq need not be page-aligned (serve_llm derives it from
        # prompt+steps+slack); correctness is pinned end-to-end by
        # TestEnginePathParity.test_ragged_max_seq_matches_engine
        cfg = _cfg()
        lp = kvc.layout_for(cfg, B_ENG, S_MAX, kv_format="bgpp",
                            layout="paged", page_size=PAGE)
        kernel_decode.validate(cfg, dataclasses.replace(lp, max_seq=30))
