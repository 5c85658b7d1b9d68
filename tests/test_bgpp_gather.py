"""Top-k gather equivalence for the two-phase BGPP paged decode.

The tentpole contract of the access-reduced path: phase 1 predicts the
top-k candidate set from bit-slice planes alone, phase 2 gathers ONLY the
surviving tokens' full-precision rows through the page table — and the
resulting logits are BIT-identical to the full-entry BGPP attend (the slot
layout's path, and ``paged_entry``'s full-row gather view).  Checked for
cache fills below / at / above the keep budget ``K = ceil(keep_ratio · S)``
and across a page boundary, on a deliberately shuffled (non-identity) page
table so logical->physical translation is actually exercised.

Also pins the kv-read accounting that rides the same plan: paged bgpp
decode reads bit-planes plus at most ``K`` full-precision rows per
(slot, layer) — the ISSUE-5 acceptance assert.
"""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import MCBPOptions
from repro.serving import engine, kv_cache as kvc

jax.config.update("jax_platform_name", "cpu")

B, S_MAX, PAGE = 2, 32, 8
KEEP = 0.25  # K = ceil(0.25 * 32) = 8 keys kept at full precision


def _cfg():
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    return dataclasses.replace(
        cfg, mcbp=MCBPOptions(bgpp_rounds=4, bgpp_keep_ratio=KEEP)
    )


def _filled_caches(cfg, s_ctx, seed):
    """Write the SAME random K/V into a paged store (shuffled page table)
    and a slot store, returning (paged cache, slot cache, q, valid)."""
    rng = np.random.default_rng(seed)
    lp = kvc.layout_for(cfg, B, S_MAX, kv_format="bgpp", layout="paged",
                        page_size=PAGE)
    ls = kvc.layout_for(cfg, B, S_MAX, kv_format="bgpp")
    paged = kvc.init_cache_arrays(cfg, lp)
    slot = kvc.init_cache_arrays(cfg, ls)

    # non-identity mapping: slot rows land on permuted physical pages, so
    # a gather that forgot to translate would read the wrong tokens
    tbl = np.full((B, lp.pages_per_slot), -1, np.int32)
    perm = rng.permutation(lp.num_pages)
    npg = -(-s_ctx // PAGE)
    for b in range(B):
        tbl[b, :npg] = perm[b * lp.pages_per_slot:b * lp.pages_per_slot + npg]
    paged["page_table"] = jnp.asarray(tbl)

    Hk, Dh, Hq = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    k = jnp.asarray(rng.normal(size=(B, s_ctx, Hk, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, s_ctx, Hk, Dh)), jnp.float32)
    for b in range(B):
        paged["global"] = kvc.write_prefill(
            paged["global"], 0, k[b:b + 1], v[b:b + 1], slot=b,
            page_table=paged["page_table"], page_size=PAGE, max_seq=S_MAX,
        )
        slot["global"] = kvc.write_prefill(
            slot["global"], 0, k[b:b + 1], v[b:b + 1], slot=b,
        )
    q = jnp.asarray(rng.normal(size=(B, Hq, Dh)), jnp.float32)
    valid = jnp.arange(S_MAX)[None, :] < s_ctx
    return paged, slot, q, valid


class TestTopkGatherEquivalence:
    # K = 8: fills straddle the keep budget, and 13/30 span page boundaries
    @pytest.mark.parametrize("s_ctx", [5, 8, 13, 30])
    def test_two_phase_matches_full_entry(self, s_ctx):
        cfg = _cfg()
        paged, slot, q, valid = _filled_caches(cfg, s_ctx, seed=s_ctx)
        phys = kvc.phys_table(paged["page_table"], PAGE, S_MAX)

        two_phase = np.asarray(engine._bgpp_paged_decode_attend(
            q, paged["global"], 0, phys, valid, cfg
        ))
        # full-entry reference #1: the whole paged row gathered back into
        # the heads-major view (the pre-two-phase paged path)
        full_view = kvc.paged_entry(paged["global"], 0, phys)
        full_paged = np.asarray(engine._bgpp_decode_attend(
            q, full_view, valid, cfg
        ))
        # full-entry reference #2: the slot layout's dense row
        entry_slot = {n: slot["global"][n][0] for n in slot["global"]}
        full_slot = np.asarray(engine._bgpp_decode_attend(
            q, entry_slot, valid, cfg
        ))

        assert np.array_equal(two_phase, full_paged), (
            f"s_ctx={s_ctx}: two-phase attend diverges from the full "
            f"paged-entry BGPP path "
            f"(max |d| {np.max(np.abs(two_phase - full_paged))})"
        )
        assert np.array_equal(two_phase, full_slot), (
            f"s_ctx={s_ctx}: two-phase attend diverges from the slot "
            f"layout (max |d| {np.max(np.abs(two_phase - full_slot))})"
        )

    def test_compacted_buffer_is_keep_ratio_sized(self):
        """Phase 2's gather is fixed-shape: exactly K = ceil(keep·S) token
        rows per (slot, head), never the full row."""
        cfg = _cfg()
        paged, _, q, valid = _filled_caches(cfg, 13, seed=0)
        phys = kvc.phys_table(paged["page_table"], PAGE, S_MAX)
        qf = engine._bgpp_quant_query(q, cfg)
        idx, idx_valid = engine._bgpp_topk_indices(
            qf,
            kvc.paged_plane(paged["global"], 0, kvc.NBITS - 1, phys),
            kvc.paged_sign(paged["global"], 0, phys),
            lambda p, i: kvc.paged_plane_rows(
                paged["global"], 0, p, kvc.paged_rows_at(phys, i)
            ),
            valid, cfg,
        )
        k_max = math.ceil(KEEP * S_MAX)
        assert idx.shape == (B, cfg.num_kv_heads, k_max)
        gathered = kvc.paged_topk_entry(
            paged["global"], 0, kvc.paged_rows_at(phys, idx)
        )
        Hk, Dh = cfg.num_kv_heads, cfg.head_dim
        assert gathered["k_planes"].shape == (kvc.NBITS, B, Hk, k_max, Dh // 8)
        assert gathered["v"].shape == (B, Hk, k_max, Dh)
        assert gathered["k_scale"].shape == (B, Hk, k_max)
        # with 13 valid tokens and K=8, every candidate lane is real
        assert bool(np.all(np.asarray(idx_valid)))


def _iter_avals(jaxpr):
    """Every intermediate aval in a jaxpr, recursing into sub-jaxprs
    (pjit/scan/cond bodies) — duck-typed so it tracks JAX versions."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None:
                yield aval
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(sub, "jaxpr", None)  # ClosedJaxpr
                if inner is not None and hasattr(inner, "eqns"):
                    yield from _iter_avals(inner)
                elif hasattr(sub, "eqns"):  # raw Jaxpr
                    yield from _iter_avals(sub)


class TestServeStepAccessStructure:
    def test_paged_bgpp_serve_step_never_materializes_full_rows(self):
        """Couple the kv-read counter's claim to the ACTUAL decode graph:
        trace the real ``serve_step`` for a paged bgpp layout and assert
        no intermediate carries a full-width int8 KV row ``(B, S, Hk, Dh)``
        (either axis order).  If the engine ever regressed to the
        ``paged_entry`` full-row gather, such a tensor must appear — shown
        by the positive control, which traces the full-entry reference and
        requires the detector to fire.  (Bit-plane tensors are uint8 and
        the compacted phase-2 buffers are K-wide, so the two-phase graph
        is clean by construction.)"""
        from repro.models import model_zoo

        cfg = _cfg()
        params, _ = model_zoo.init(jax.random.key(0), cfg)
        lp = kvc.layout_for(cfg, B, S_MAX, kv_format="bgpp", layout="paged",
                            page_size=PAGE)
        cache = kvc.init_cache_arrays(cfg, lp)
        cache["page_table"] = kvc.identity_page_table(lp)
        step = engine.make_serve_step(cfg, lp)
        closed = jax.make_jaxpr(step)(
            params, cache, jnp.zeros((B, 1), jnp.int32)
        )
        Hk, Dh = cfg.num_kv_heads, cfg.head_dim
        forbidden = {(B, S_MAX, Hk, Dh), (B, Hk, S_MAX, Dh)}

        def full_row_avals(jaxpr):
            return [
                a for a in _iter_avals(jaxpr)
                if getattr(a, "dtype", None) == jnp.int8
                and tuple(getattr(a, "shape", ())) in forbidden
            ]

        assert not full_row_avals(closed.jaxpr), (
            "paged bgpp serve_step materialized a full-width int8 KV row —"
            " the two-phase gather regressed to a full-entry gather"
        )

        # positive control: the detector must fire on the full-entry path
        phys = kvc.phys_table(cache["page_table"], PAGE, S_MAX)
        valid = jnp.ones((B, S_MAX), bool)
        q = jnp.zeros((B, cfg.num_heads, Dh), jnp.float32)
        ref = jax.make_jaxpr(
            lambda q_, store, phys_: engine._bgpp_decode_attend(
                q_, kvc.paged_entry(store, 0, phys_), valid, cfg
            )
        )(q, cache["global"], phys)
        assert full_row_avals(ref.jaxpr), (
            "detector lost sensitivity: the full-entry reference no longer"
            " shows a full-width int8 row"
        )

    def test_kernel_dispatch_serve_step_never_materializes_full_rows(
        self, monkeypatch
    ):
        """The same structural claim on the KERNEL-routed decode graph:
        with ``decode_kernel=interpret`` the global attend becomes a
        ``pallas_call`` over the token-major pools, and the traced step —
        including every sub-jaxpr the interpreter carries — must still
        never hold a full-width int8 KV row.  The kernel stages a slot's
        pages (packed planes and int8 V as ``(S, Hk*...)`` rows) inside
        its own VMEM, so a heads-split full-row aval appearing here means
        the dispatch path regressed to an XLA dense-entry gather."""
        from repro.models import model_zoo
        from repro.serving import kernel_decode

        monkeypatch.setenv(kernel_decode.ENV_VAR, "interpret")
        cfg = _cfg()
        params, _ = model_zoo.init(jax.random.key(0), cfg)
        lp = kvc.layout_for(cfg, B, S_MAX, kv_format="bgpp", layout="paged",
                            page_size=PAGE)
        cache = kvc.init_cache_arrays(cfg, lp)
        cache["page_table"] = kvc.identity_page_table(lp)
        step = engine.make_serve_step(cfg, lp)
        closed = jax.make_jaxpr(step)(
            params, cache, jnp.zeros((B, 1), jnp.int32)
        )
        Hk, Dh = cfg.num_kv_heads, cfg.head_dim
        forbidden = {(B, S_MAX, Hk, Dh), (B, Hk, S_MAX, Dh)}
        hits = [
            a for a in _iter_avals(closed.jaxpr)
            if getattr(a, "dtype", None) == jnp.int8
            and tuple(getattr(a, "shape", ())) in forbidden
        ]
        assert not hits, (
            "kernel-dispatch paged bgpp serve_step materialized a "
            "full-width int8 KV row — the fused kernel path regressed to "
            "a full-entry gather"
        )


class TestKvReadAccounting:
    def test_bgpp_reads_planes_plus_at_most_keep_full_rows(self):
        """The ISSUE-5 acceptance bound, via the counter the scheduler
        threads to stats(): full-precision rows per (slot, layer) never
        exceed ceil(keep_ratio * S), and everything else is plane-sized."""
        cfg = _cfg()
        lp = kvc.layout_for(cfg, B, S_MAX, kv_format="bgpp", layout="paged",
                            page_size=PAGE)
        r = kvc.decode_read_bytes(lp, cfg)
        assert r["bgpp"]["full_rows_per_slot"] == math.ceil(KEEP * S_MAX)
        assert r["bgpp"]["full_rows_per_slot"] <= math.ceil(
            cfg.mcbp.bgpp_keep_ratio * S_MAX
        )
        # the global-stack read decomposes exactly into sign + planes +
        # top-k full rows — nothing else is fetched
        parts = (r["bgpp"]["sign_bytes"] + r["bgpp"]["plane_bytes"]
                 + r["bgpp"]["topk_full_bytes"])
        assert parts == pytest.approx(r["global"])
        assert r["total"] < r["bf16_equiv"]

    def test_format_ordering_and_slot_paged_agree(self):
        cfg = _cfg()
        totals = {}
        for fmt in ("bf16", "int8", "bgpp"):
            ls = kvc.layout_for(cfg, B, S_MAX, kv_format=fmt)
            lp = kvc.layout_for(cfg, B, S_MAX, kv_format=fmt, layout="paged",
                                page_size=PAGE)
            # the layout changes where rows live, not how many bytes one
            # decode step must fetch
            assert kvc.decode_read_bytes(ls, cfg) == kvc.decode_read_bytes(lp, cfg)
            totals[fmt] = kvc.decode_read_bytes(ls, cfg)["total"]
        assert totals["bgpp"] < totals["int8"] < totals["bf16"]

    def test_chunk_read_is_full_precision(self):
        """Prefill has nothing to skip: the chunk attend reads the whole
        row at full precision for every format."""
        cfg = _cfg()
        for fmt in ("bf16", "int8", "bgpp"):
            layout = kvc.layout_for(cfg, B, S_MAX, kv_format=fmt)
            c = kvc.chunk_read_bytes(layout, cfg)
            assert c["total"] == pytest.approx(
                len(layout.global_layers) * S_MAX
                * kvc._token_row_bytes(cfg, fmt)
            )
