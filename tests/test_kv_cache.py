"""KV-cache container invariants: slot eviction must scrub EVERY store
leaf of the slot row — k/v bodies, int8 scales, BGPP bit/sign planes, ring
``abs_pos`` — without touching live neighbors.  Paged layouts: writes
through the page table must land on exactly the pool rows the gather view
reads back (value-identical to the slot layout), and ``reset_slot`` must
leave the shared pool and the page table alone (the allocator owns them)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.serving import kv_cache as kvc

jax.config.update("jax_platform_name", "cpu")

EXPECTED_LEAVES = {
    "bf16": {"k", "v"},
    "int8": {"k", "v", "k_scale", "v_scale"},
    "bgpp": {"k_planes", "k_sign", "k_scale", "v", "v_scale"},
}


def _filled_cache(cfg, layout):
    """Every leaf nonzero so a missed reset is visible."""
    cache = kvc.init_cache_arrays(cfg, layout)
    return jax.tree.map(lambda a: jnp.full_like(a, 3), cache)


@pytest.mark.parametrize("fmt", ["bf16", "int8", "bgpp"])
def test_reset_slot_clears_every_leaf(fmt):
    # gemma3 has both a sliding-window ring stack and a global stack, so
    # every store family of the format is exercised
    cfg = get_config("gemma3-4b", smoke=True)
    layout = kvc.layout_for(cfg, 3, 32, kv_format=fmt)
    assert layout.local_layers and layout.global_layers
    cache = _filled_cache(cfg, layout)

    # the allocation actually contains the leaves this test claims to cover
    assert set(cache["global"].keys()) == EXPECTED_LEAVES[fmt]
    local_fmt = "int8" if fmt == "bgpp" else fmt
    assert set(cache["local"].keys()) == EXPECTED_LEAVES[local_fmt] | {"abs_pos"}

    slot = 1
    cache = kvc.reset_slot(cache, layout, slot)

    for stack in ("global", "local"):
        for name, arr in cache[stack].items():
            a = np.asarray(arr)
            bdim = kvc._batch_dim(stack, name)
            row = np.take(a, slot, axis=bdim)
            fill = -1 if name == "abs_pos" else 0
            assert np.all(row == fill), f"{stack}/{name}: slot row not cleared"
            for other in (0, 2):  # live neighbors untouched (still 3)
                keep = np.take(a, other, axis=bdim)
                assert np.all(keep == 3), f"{stack}/{name}: slot {other} touched"
    assert int(np.asarray(cache["pos"])[slot]) == 0
    assert np.all(np.asarray(cache["pos"])[[0, 2]] == 3)


@pytest.mark.parametrize("fmt", ["bf16", "int8", "bgpp"])
def test_paged_writes_match_slot_layout(fmt):
    """Every write path (decode token, padded chunk, contiguous slot and
    whole-batch prefill) must produce a gather view value-identical to the
    dense row the slot layout stores."""
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    B, S, ps = 2, 32, 8
    Hk, Dh = cfg.num_kv_heads, cfg.head_dim
    ls = kvc.layout_for(cfg, B, S, kv_format=fmt)
    lp = kvc.layout_for(cfg, B, S, kv_format=fmt, layout="paged", page_size=ps)
    dense = kvc.init_cache_arrays(cfg, ls)["global"]
    paged = kvc.init_cache_arrays(cfg, lp)["global"]
    pt = kvc.identity_page_table(lp)
    pkw = dict(page_table=pt, page_size=ps, max_seq=S)
    rng = np.random.default_rng(0)

    def rnd(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    k1, v1 = rnd((B, 1, Hk, Dh)), rnd((B, 1, Hk, Dh))
    dense = kvc.write_token(dense, 0, k1, v1, jnp.asarray([3, 17]))
    paged = kvc.write_token(paged, 0, k1, v1, jnp.asarray([3, 17]), **pkw)

    kc, vc = rnd((1, 6, Hk, Dh)), rnd((1, 6, Hk, Dh))
    dense = kvc.write_prefill(dense, 0, kc, vc, slot=1, offset=5, length=4)
    paged = kvc.write_prefill(paged, 0, kc, vc, slot=1, offset=5, length=4,
                              **pkw)

    kp, vp = rnd((1, 12, Hk, Dh)), rnd((1, 12, Hk, Dh))
    dense = kvc.write_prefill(dense, 1, kp, vp, slot=0)
    paged = kvc.write_prefill(paged, 1, kp, vp, slot=0, **pkw)

    kb, vb = rnd((B, 9, Hk, Dh)), rnd((B, 9, Hk, Dh))
    dense = kvc.write_prefill(dense, 2, kb, vb)
    paged = kvc.write_prefill(paged, 2, kb, vb, **pkw)

    phys = kvc.phys_table(pt, ps, S)
    for gi in range(3):
        view = kvc.paged_entry(paged, gi, phys, heads=Hk)
        entry = kvc.slot_entry(dense, gi, heads=Hk)
        for n in dense:
            # dense layer entry and paged gather view share one shape:
            # (B, Hk, S, ...) — and must share every value
            assert np.array_equal(np.asarray(entry[n]),
                                  np.asarray(view[n])), (fmt, gi, n)


def test_paged_unmapped_pages_drop_writes():
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    lp = kvc.layout_for(cfg, 2, 32, kv_format="bf16", layout="paged",
                        page_size=8)
    store = kvc.init_cache_arrays(cfg, lp)["global"]
    pt = jnp.full((2, 4), -1, jnp.int32).at[0, 0].set(2)  # one mapped page
    k = jnp.ones((2, 1, cfg.num_kv_heads, cfg.head_dim), jnp.float32)
    # slot 0 writes pos 3 (mapped -> page 2 row 3); slot 1 pos 9 (unmapped)
    store = kvc.write_token(store, 0, k, k, jnp.asarray([3, 9]),
                            page_table=pt, page_size=8, max_seq=32)
    body = np.asarray(store["k"][0])
    assert np.all(body[2 * 8 + 3] == 1)
    assert np.count_nonzero(body) == body[2 * 8 + 3].size, \
        "write through an unmapped page leaked into the pool"


def test_paged_reset_slot_leaves_pool_and_table_alone():
    cfg = get_config("gemma3-4b", smoke=True)
    layout = kvc.layout_for(cfg, 3, 32, kv_format="int8", layout="paged",
                            page_size=8)
    assert layout.local_layers and layout.global_layers
    cache = _filled_cache(cfg, layout)
    cache["page_table"] = kvc.identity_page_table(layout)
    cache = kvc.reset_slot(cache, layout, 1)
    for n, a in cache["global"].items():
        assert np.all(np.asarray(a) == 3), f"pool leaf {n} touched"
    assert np.array_equal(np.asarray(cache["page_table"]),
                          np.asarray(kvc.identity_page_table(layout)))
    # slot-major state still resets: local ring row + pos
    for n, a in cache["local"].items():
        row = np.take(np.asarray(a), 1, axis=kvc._batch_dim("local", n))
        assert np.all(row == (-1 if n == "abs_pos" else 0)), f"local/{n}"
    assert int(np.asarray(cache["pos"])[1]) == 0


def test_reset_slot_covers_mamba_and_cross():
    cfg = get_config("whisper-medium", smoke=True)
    layout = kvc.layout_for(cfg, 2, 16, kv_format="int8")
    cache = _filled_cache(cfg, layout)
    cache = kvc.reset_slot(cache, layout, 0)
    for name in ("cross_k", "cross_v"):
        a = np.asarray(cache[name])
        assert np.all(a[:, 0] == 0) and np.all(a[:, 1] == 3)

    cfg = get_config("mamba2-1.3b", smoke=True)
    layout = kvc.layout_for(cfg, 2, 16)
    cache = _filled_cache(cfg, layout)
    cache = kvc.reset_slot(cache, layout, 1)
    for name in ("h", "conv"):
        a = np.asarray(cache["mamba"][name])
        assert np.all(a[:, 1] == 0) and np.all(a[:, 0] == 3)
