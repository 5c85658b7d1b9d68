"""Property tests for the paged-KV page allocator and pool hygiene.

The three laws the paged layout's safety rests on:

  * alloc/free/refcount round-trips never double-free or leak — after any
    op sequence every page is exactly one of {free, mapped}, refcounts
    equal table reachability, and the free list is duplicate-free
    (``PageAllocator.check``);
  * freed pages are re-zeroed across EVERY store leaf — k/v bodies, int8
    scales, bgpp bit/sign planes — before they can be remapped;
  * no physical page is ever reachable from two slots whose requests do
    not share the page-aligned token prefix covering it (prefix reuse is
    the only legal sharing channel).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.serving import kv_cache as kvc
from repro.serving.paging import PageAllocator
from repro.serving.request import Request
from repro.serving.scheduler import Scheduler

jax.config.update("jax_platform_name", "cpu")


def _layout(batch=3, max_seq=32, fmt="int8", page_size=8, num_pages=None):
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    return cfg, kvc.layout_for(cfg, batch, max_seq, kv_format=fmt,
                               layout="paged", page_size=page_size,
                               num_pages=num_pages)


# --------------------------------------------------------------------------
# allocator bookkeeping
# --------------------------------------------------------------------------


def test_alloc_release_round_trip_never_leaks(rng):
    _, layout = _layout()
    pager = PageAllocator(layout)
    for _ in range(200):
        slot = int(rng.integers(0, layout.batch))
        if rng.random() < 0.6:
            hi = int(rng.integers(1, layout.max_seq + 1))
            lo = int(rng.integers(0, hi))
            pager.ensure_range(slot, lo, hi)
        else:
            pager.release_slot(slot)
        pager.check()
    for slot in range(layout.batch):
        pager.release_slot(slot)
        # releasing an already-empty slot is a no-op, not a double free
        pager.release_slot(slot)
    pager.check()
    assert pager.pages_in_use == 0


def test_refcount_sharing_round_trip():
    _, layout = _layout()
    pager = PageAllocator(layout)
    prompt = np.arange(24, dtype=np.int32)
    pager.ensure_range(0, 0, 24)
    pager.register_prefix(0, prompt, upto=24)
    n, ids = pager.lookup_prefix(np.concatenate([prompt, [99]]).astype(np.int32))
    assert n == 24 and len(ids) == 3
    pager.adopt_prefix(1, ids)
    pager.check()
    assert all(pager.refcount[p] == 2 for p in ids)
    # releasing the donor keeps the sharer's pages alive (refcount 2 -> 1)
    assert pager.release_slot(0) == []
    pager.check()
    assert all(pager.refcount[p] == 1 for p in ids)
    # releasing the last holder frees them
    freed = pager.release_slot(1)
    assert sorted(freed) == sorted(ids)
    pager.check()
    assert pager.pages_in_use == 0


def test_lookup_caps_reuse_below_full_prompt():
    # the last prompt token must still run through prefill to produce the
    # first-token logits, so an exact whole-prompt match reuses one page
    # less than the match
    _, layout = _layout()
    pager = PageAllocator(layout)
    prompt = np.arange(16, dtype=np.int32)
    pager.ensure_range(0, 0, 16)
    pager.register_prefix(0, prompt, upto=16)
    n, _ = pager.lookup_prefix(prompt)
    assert n == 8  # one of the two matched pages


def test_stale_prefix_entries_never_resurrect_freed_pages():
    _, layout = _layout()
    pager = PageAllocator(layout)
    prompt = np.arange(16, dtype=np.int32)
    pager.ensure_range(0, 0, 16)
    pager.register_prefix(0, prompt, upto=16)
    pager.release_slot(0)  # frees the pages; generations move on
    longer = np.concatenate([prompt, prompt]).astype(np.int32)
    assert pager.lookup_prefix(longer) == (0, ())
    # ... even if another slot re-acquires the same physical pages
    pager.ensure_range(1, 0, 16)
    assert pager.lookup_prefix(longer) == (0, ())
    pager.check()


def test_pool_exhaustion_is_loud():
    _, layout = _layout(batch=2, max_seq=32, num_pages=2)
    pager = PageAllocator(layout)
    pager.ensure_range(0, 0, 16)
    with pytest.raises(RuntimeError, match="exhausted"):
        pager.ensure_range(1, 0, 16)


# --------------------------------------------------------------------------
# rewind: speculative-decode rollback (pos frontier moves backwards)
# --------------------------------------------------------------------------


def test_rewind_deregisters_prefix_entries_past_keep():
    """Regression (spec-decode satellite): rolling a slot back across a
    page boundary must deregister every sha1 prefix-index entry covering
    now-invalid pages.  Before the fix a rewound slot's stale 16/24-token
    entries would still hit for a later prompt and adopt pages whose tail
    tokens were never (re)written."""
    _, layout = _layout()
    pager = PageAllocator(layout)
    prompt = np.arange(24, dtype=np.int32)
    pager.ensure_range(0, 0, 24)
    pager.register_prefix(0, prompt, upto=24)
    probe = np.concatenate([prompt, [99]]).astype(np.int32)
    n, ids = pager.lookup_prefix(probe)
    assert n == 24 and len(ids) == 3
    # rewind to keep 10 tokens: page 2 (tokens 16..24) frees outright,
    # page 1 (8..16) is the partially-kept frontier — the 16- and
    # 24-token boundary digests it carries must BOTH dereg, while the
    # wholly-kept page-0 boundary survives
    freed = pager.rewind_slot(0, 10)
    pager.check()
    assert freed == [int(ids[2])]
    assert int(pager.table[0, 2]) == -1
    n, hit = pager.lookup_prefix(probe)
    assert n == 8 and list(hit) == [int(ids[0])], (
        f"rewound prefix entries must miss: matched {n} tokens")


def test_rewind_page_aligned_keeps_covered_boundaries():
    _, layout = _layout()
    pager = PageAllocator(layout)
    prompt = np.arange(24, dtype=np.int32)
    pager.ensure_range(0, 0, 24)
    pager.register_prefix(0, prompt, upto=24)
    probe = np.concatenate([prompt, [99]]).astype(np.int32)
    # keep == a page boundary: pages 0/1 stay fully written, so their
    # 8- and 16-token boundaries remain legal adoption targets
    freed = pager.rewind_slot(0, 16)
    pager.check()
    assert len(freed) == 1
    n, hit = pager.lookup_prefix(probe)
    assert n == 16 and len(hit) == 2


def test_rewind_bumps_generation_against_resurrection():
    """A page freed by rewind must be unresurrectable: even if another
    slot re-acquires the same physical page, pre-rewind index entries
    (had any survived) die at the generation check, and re-registering
    after the rewind starts from the rewound frontier."""
    _, layout = _layout()
    pager = PageAllocator(layout)
    prompt = np.arange(16, dtype=np.int32)
    pager.ensure_range(0, 0, 16)
    pager.register_prefix(0, prompt, upto=16)
    freed = pager.rewind_slot(0, 0)  # rewind everything away
    pager.check()
    assert len(freed) == 2 and pager.pages_in_use == 0
    probe = np.concatenate([prompt, [99]]).astype(np.int32)
    assert pager.lookup_prefix(probe) == (0, ())
    pager.ensure_range(1, 0, 16)  # same physical pages, new generation
    assert pager.lookup_prefix(probe) == (0, ())
    pager.check()
    # the rewound slot itself re-registers from scratch
    pager.ensure_range(0, 0, 16)
    pager.register_prefix(0, prompt, upto=16)
    n, _ = pager.lookup_prefix(probe)
    assert n == 16


def test_rewind_refuses_to_corrupt_shared_frontier():
    """The frontier page can never legally be shared (adopted pages cover
    at most prompt_len - 1 < keep tokens), so a shared-frontier rewind is
    allocator corruption and must be loud."""
    _, layout = _layout()
    pager = PageAllocator(layout)
    prompt = np.arange(24, dtype=np.int32)
    pager.ensure_range(0, 0, 24)
    pager.register_prefix(0, prompt, upto=24)
    _, ids = pager.lookup_prefix(np.concatenate([prompt, [99]])
                                 .astype(np.int32))
    pager.adopt_prefix(1, ids)
    with pytest.raises(AssertionError, match="shared page"):
        pager.rewind_slot(0, 12)  # page 1 shared AND partially kept
    # page-aligned rewinds around the shared region stay legal
    pager.rewind_slot(0, 24)
    pager.check()


# --------------------------------------------------------------------------
# pins: residency held by no slot (chat-session keep-alives)
# --------------------------------------------------------------------------


def test_pin_survives_slot_release():
    """A pinned prefix outlives its slot: release_slot decrefs but the pin
    keeps the pages (and their index entries) resident and adoptable."""
    _, layout = _layout()
    pager = PageAllocator(layout)
    prompt = np.arange(16, dtype=np.int32)
    pager.ensure_range(0, 0, 16)
    pager.register_prefix(0, prompt, upto=16)
    ids = [int(p) for p in pager.table[0, :2]]
    pager.pin_pages(ids)
    pager.check()
    assert pager.release_slot(0) == []  # pin holds them: nothing freed
    pager.check()
    assert pager.pages_in_use == 0 or all(pager.refcount[p] == 1
                                          for p in ids)
    # still resident + still indexed: a longer prompt adopts them
    longer = np.concatenate([prompt, prompt]).astype(np.int32)
    n, hit = pager.lookup_prefix(longer)
    assert n == 16 and list(hit) == ids
    pager.adopt_prefix(1, hit)
    pager.check()
    # sharer releases, then the pin: only the unpin frees
    assert pager.release_slot(1) == []
    assert sorted(pager.unpin_pages(ids)) == sorted(ids)
    pager.check()
    assert pager.lookup_prefix(longer) == (0, ()), "unpin left the index"


def test_unpin_is_exact_inverse_and_loud():
    _, layout = _layout()
    pager = PageAllocator(layout)
    pager.ensure_range(0, 0, 8)
    (p,) = [int(q) for q in pager.table[0, :1]]
    pager.pin_pages([p])
    pager.pin_pages([p])  # pins stack like refcounts
    pager.check()
    assert pager.refcount[p] == 3 and pager.pins[p] == 2
    assert pager.unpin_pages([p]) == []
    pager.release_slot(0)
    pager.check()
    assert pager.unpin_pages([p]) == [p]  # last holder frees
    pager.check()
    with pytest.raises(AssertionError, match="not pinned"):
        pager.unpin_pages([p])
    with pytest.raises(AssertionError, match="freed"):
        pager.pin_pages([p])  # pins extend residency, never resurrect


def test_check_catches_pin_refcount_drift():
    _, layout = _layout()
    pager = PageAllocator(layout)
    pager.ensure_range(0, 0, 8)
    pager.pins[int(pager.table[0, 0])] += 1  # pin without the refcount
    with pytest.raises(AssertionError, match="refcount drift"):
        pager.check()


# --------------------------------------------------------------------------
# freed pages are re-zeroed across every store leaf
# --------------------------------------------------------------------------


EXPECTED_POOL_LEAVES = {
    "bf16": {"k", "v"},
    "int8": {"k", "v", "k_scale", "v_scale"},
    "bgpp": {"k_planes", "k_sign", "k_scale", "v", "v_scale"},
}


@pytest.mark.parametrize("fmt", ["bf16", "int8", "bgpp"])
def test_zero_pages_scrubs_every_leaf(fmt):
    cfg, layout = _layout(fmt=fmt)
    cache = kvc.init_cache_arrays(cfg, layout)
    assert set(cache["global"].keys()) == EXPECTED_POOL_LEAVES[fmt]
    filled = {n: jnp.full_like(a, 3) for n, a in cache["global"].items()}
    ids = jnp.asarray([1, 3, -1, -1], jnp.int32)  # -1 padding must drop
    zeroed = kvc.zero_pages(dict(filled), ids, layout.page_size)
    ps = layout.page_size
    for n, a in zeroed.items():
        tok = np.moveaxis(np.asarray(a), kvc._tok_dim(n), 1)
        for p in (1, 3):
            assert np.all(tok[:, p * ps:(p + 1) * ps] == 0), f"{n}: page {p}"
        for p in (0, 2):
            assert np.all(tok[:, p * ps:(p + 1) * ps] == 3), \
                f"{n}: page {p} touched"


@pytest.mark.parametrize("fmt", ["bf16", "int8", "bgpp"])
def test_scheduler_eviction_zeroes_freed_pages(fmt):
    """Drive a real request through the paged scheduler; after it finishes
    every pool leaf must be all-zero again (its pages were freed and
    scrubbed on device)."""
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    from repro.models import model_zoo
    params, _ = model_zoo.init(jax.random.key(0), cfg)
    layout = kvc.layout_for(cfg, 2, 32, kv_format=fmt, layout="paged",
                            page_size=8)
    sched = Scheduler(params, cfg, layout, chunk_budget=6)
    rng = np.random.default_rng(0)
    sched.submit(Request(
        rid=0, prompt=rng.integers(0, cfg.vocab_size, (11,)).astype(np.int32),
        max_new_tokens=3,
    ))
    sched.run(max_steps=200)
    assert len(sched.finished) == 1
    sched.pager.check()
    assert sched.pager.pages_in_use == 0
    for n, a in sched.cache["global"].items():
        assert not np.any(np.asarray(a)), f"{n}: stale bytes survived eviction"


# --------------------------------------------------------------------------
# a slot row is a paged row: the two layouts store one token-major order
# --------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", ["bf16", "int8"])
def test_slot_rows_equal_paged_pool_rows(fmt):
    """Admit the same prompts into a slot scheduler and a paged one and
    decode a few steps in both: slot row ``[gi, b, s]`` of ``k``/``v``
    (token-major ``(Hk, D)``) must equal the pool row the paged table
    maps ``(b, s)`` to, and the int8 scales ``[gi, b, :, s]`` the pool's
    ``(Hk,)`` scale row, at every written position of every slot."""
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    from repro.models import model_zoo
    params, _ = model_zoo.init(jax.random.key(0), cfg)
    B, S, ps = 3, 32, 8
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 13, 9)]
    caches = {}
    for kind in ("slot", "paged"):
        layout = kvc.layout_for(cfg, B, S, kv_format=fmt, layout=kind,
                                page_size=ps)
        sched = Scheduler(params, cfg, layout, chunk_budget=8)
        for i, p in enumerate(prompts):
            sched.submit(Request(rid=i, prompt=p, max_new_tokens=12))
        for _ in range(6):
            sched.step()
        assert not sched.finished and all(
            s.state.name == "DECODING" for s in sched.slots)
        caches[kind] = sched.cache
    table = sched.pager.table  # the paged scheduler ran last
    slot, pool = caches["slot"]["global"], caches["paged"]["global"]
    pos = np.asarray(caches["slot"]["pos"])
    np.testing.assert_array_equal(pos, np.asarray(caches["paged"]["pos"]))
    for b in range(B):
        s = np.arange(pos[b])  # every position a step has written
        rows = table[b, s // ps] * ps + s % ps
        assert np.all(table[b, s // ps] >= 0)
        for n in slot:
            got, want = np.asarray(slot[n]), np.asarray(pool[n])[:, rows]
            if kvc.token_major(slot, n):  # (L, B, S, Hk*D)
                got = got[:, b, :pos[b]]
            else:  # scales (L, B, Hk, S) against pool rows (L, n, Hk)
                got = np.swapaxes(got[:, b, :, :pos[b]], 1, 2)
            np.testing.assert_array_equal(got, want, err_msg=f"{n} slot {b}")


# --------------------------------------------------------------------------
# sharing legitimacy: only identical page-aligned prefixes may share
# --------------------------------------------------------------------------


def _assert_sharing_legit(sched):
    """Any page mapped by >1 slot must back the same logical page index of
    requests whose prompts agree on every token that page covers."""
    pager = sched.pager
    owners = {}
    for b in range(pager.table.shape[0]):
        for pi in range(pager.table.shape[1]):
            p = int(pager.table[b, pi])
            if p >= 0:
                owners.setdefault(p, []).append((b, pi))
    for p, lst in owners.items():
        if len(lst) < 2:
            continue
        assert len({pi for _, pi in lst}) == 1, \
            f"page {p} mapped at different logical indices: {lst}"
        n = (lst[0][1] + 1) * pager.page_size
        prompts = []
        for b, _ in lst:
            req = sched.slots[b].request
            assert req is not None, f"page {p} shared with an empty slot {b}"
            assert req.prompt_len >= n
            prompts.append(np.asarray(req.prompt[:n]))
        for q in prompts[1:]:
            assert np.array_equal(prompts[0], q), \
                f"page {p} shared across unrelated prompts"


def _drive(reqs, fmt="int8"):
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    from repro.models import model_zoo
    params, _ = model_zoo.init(jax.random.key(0), cfg)
    layout = kvc.layout_for(cfg, 2, 48, kv_format=fmt, layout="paged",
                            page_size=8)
    sched = Scheduler(params, cfg, layout, chunk_budget=6)
    for r in reqs:
        sched.submit(r)
    shared_seen = 0
    for _ in range(500):
        if not sched.num_pending:
            break
        sched.step()
        sched.pager.check()
        _assert_sharing_legit(sched)
        shared_seen += int(np.any(sched.pager.refcount > 1))
    assert len(sched.finished) == len(reqs), "trace did not drain"
    return sched, shared_seen


def test_unrelated_prompts_never_share_pages(rng):
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    # distinct leading tokens => no page-aligned common prefix exists
    reqs = [Request(
        rid=i,
        prompt=np.concatenate([[i], rng.integers(
            0, cfg.vocab_size, (int(rng.integers(8, 20)),))]).astype(np.int32),
        max_new_tokens=3, arrival_step=2 * i,
    ) for i in range(4)]
    sched, shared_seen = _drive(reqs)
    assert shared_seen == 0
    assert sched.prefix_hit_tokens == 0


def test_eager_admission_paged_matches_slot(rng):
    """The eager (whole-prompt B=1) admission path also supports paged
    layouts — admit() maps the pages, prefill_into_slot writes through the
    table — and must stay bit-identical to the slot layout (no other suite
    drives eager × paged)."""
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    from repro.models import model_zoo
    params, _ = model_zoo.init(jax.random.key(0), cfg)
    reqs = [Request(
        rid=i,
        prompt=rng.integers(0, cfg.vocab_size, (int(rng.integers(5, 12)),))
        .astype(np.int32),
        max_new_tokens=3, arrival_step=2 * i,
    ) for i in range(2)]
    out = {}
    for lay in ("slot", "paged"):
        layout = kvc.layout_for(cfg, 2, 32, kv_format="int8", layout=lay,
                                page_size=8)
        sched = Scheduler(params, cfg, layout, admission="eager",
                          record_logits=True,
                          prefill_kw=dict(block_q=16, block_k=32))
        for r in reqs:
            sched.submit(Request(rid=r.rid, prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens,
                                 arrival_step=r.arrival_step))
        sched.run(max_steps=200)
        assert len(sched.finished) == 2
        if sched.pager is not None:
            sched.pager.check()
            assert sched.pager.pages_in_use == 0
        out[lay] = {r.rid: r for r in sched.finished}
    for rid in out["slot"]:
        a, b = out["slot"][rid], out["paged"][rid]
        assert a.generated == b.generated
        for x, y in zip(a.logit_rows, b.logit_rows):
            assert np.array_equal(x, y)


def test_shared_prefix_sharing_is_prefix_aligned(rng):
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    prefix = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    reqs = [Request(
        rid=i,
        prompt=np.concatenate([prefix, rng.integers(
            0, cfg.vocab_size, (int(rng.integers(3, 8)),))]).astype(np.int32),
        max_new_tokens=8, arrival_step=6 * i,
    ) for i in range(3)]
    sched, shared_seen = _drive(reqs)
    # sharing must actually have happened (the per-step asserts above
    # proved every instance was prefix-aligned)
    assert shared_seen > 0
    assert sched.prefix_hit_tokens >= 16
