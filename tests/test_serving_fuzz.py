"""Randomized admission-trace oracle for the continuous-batching scheduler.

N requests with random prompt lengths, arrival steps, and decode budgets
are driven through chunked admission (fixed-shape prefill chunks
interleaved with batched decode), then each request is re-run ALONE through
a slot-layout scheduler and compared token-for-token / logit-row-for-row:

  * bf16   — greedy decode, generated tokens AND per-token logits must be
             bit-identical (slot isolation + chunk determinism);
  * int8 / bgpp — teacher-forced continuations (so quantized near-tie
             argmax flips can't compound), per-token logits within 1e-5.

The joint run is parametrized over ``layout`` ∈ {slot, paged}: the paged
joint trace (pooled KV pages, page-table translation, prefix reuse) is
checked against *slot-layout* alone runs, which is the cross-layout
bit-exactness contract of the paged cache.  The shared-prefix tests force
prefix reuse (deterministic arrival overlap) and assert both that reuse
happened and that logits still match the slot oracle exactly.

The cancellation axis (TestCancellationFuzz) injects random mid-flight
cancels/disconnects into the joint run: ``PageAllocator.check()`` must
hold after every step, the pool must drain to zero pages, and every
SURVIVING request must still match its alone run to the same bars.

The spec_decode axis (TestSpecDecodeFuzz) runs the joint trace through
speculative decoding — random ``gamma``, random draft quality including
adversarially-wrong drafts — and compares against NON-speculative alone
runs: speculation may only change how many serve_steps were spent, never
a single token or logit bit, and every draft/verify/rollback round must
leave the page allocator clean (``check()`` between steps, zero pages
leaked at the end).

The seed comes from the ``rng_seed`` fixture (stable per test node id) and
can be pinned via ``REPRO_FUZZ_SEED`` — CI runs the kv-format × layout
matrix with a fixed seed; the nightly workflow runs the ``slow`` suite
with a date-derived seed and, on failure, uploads the JSON trace each
oracle dumps to ``REPRO_FUZZ_TRACE_DIR`` for offline replay.
"""

import contextlib
import dataclasses
import json
import math
import os

import numpy as np
import pytest

import jax

from repro.configs import apply_weight_format_override, get_config
from repro.configs.base import MCBPOptions
from repro.models import model_zoo
from repro.serving import kv_cache as kvc
from repro.serving import sharded as shd
from repro.serving.request import Request
from repro.serving.scheduler import Scheduler

jax.config.update("jax_platform_name", "cpu")

ARCHS = {"dense": "phi4-mini-3.8b", "swa": "gemma3-4b",
         # deepseek smoke is the sharding-parity arch: the only smoke dense
         # config whose 4 q/kv heads divide the 4-way "model" axis
         "mesh": "deepseek-7b"}
MAX_SEQ = 48
SLOTS = 2
# sharded parity runs 4 slots: at mesh (2, 4) the per-device attend then
# keeps b=2 — XLA CPU's attend lowering is only bit-stable against the
# single-device program while neither per-device leading dim collapses to
# (b=1 AND h=1), the mesh analogue of the fixed-batch-shape caveat on
# _compare_to_alone_runs
MESH_SLOTS = 4
# logits of a tensor-parallel ("model" > 1) run against one device's: the
# two all-reduces of each layer add 'model' float32 partial sums of wo's
# and the MLP down projection's contraction where one device adds it in
# one order, a rounding change of a few float32 units (2**-23 relative)
# per sublayer.  Four of them (two layers) carried through the head to the
# smoke model's logits (largest about 3.7) measured 2.5e-6 on the bf16
# trace; 1e-5, about 20 units of the largest logit, keeps 4x of room and
# is the budget the teacher-forced int8/bgpp oracle already holds
TP_TOL = 1e-5
MESHES = [(1, 1), (2, 1), (1, 4), (2, 4)]
PAGE_SIZE = 8
CHUNK_BUDGET = 6  # buckets (4, 6): lengths 3..20 hit off-bucket/exact/multi

_MODELS = {}


def _model(key):
    if key not in _MODELS:
        cfg = get_config(ARCHS[key], smoke=True)
        # keep-all BGPP: the progressive gather machinery runs but selects
        # every key, so the oracle isn't confounded by forced sparsity on
        # near-uniform random-init attention (same stance as test_serving)
        cfg = dataclasses.replace(
            cfg, mcbp=MCBPOptions(bgpp_rounds=4, bgpp_keep_ratio=1.0)
        )
        params, _ = model_zoo.init(jax.random.key(0), cfg)
        _MODELS[key] = (cfg, params)
    return _MODELS[key]


def _layout_for(cfg, kv_format, layout, slots=SLOTS):
    return kvc.layout_for(cfg, slots, MAX_SEQ, kv_format=kv_format,
                          layout=layout, page_size=PAGE_SIZE)


def _random_requests(rng, cfg, n, teacher_forced):
    reqs = []
    for rid in range(n):
        max_new = int(rng.integers(2, 6))
        reqs.append(Request(
            rid=rid,
            prompt=rng.integers(
                0, cfg.vocab_size, (int(rng.integers(3, 21)),)
            ).astype(np.int32),
            max_new_tokens=max_new,
            arrival_step=int(rng.integers(0, 9)),
            forced_tokens=rng.integers(0, cfg.vocab_size, (max_new,))
            .astype(np.int32) if teacher_forced else None,
        ))
    return reqs


def _clone(req, arrival_step):
    return Request(rid=req.rid, prompt=req.prompt,
                   max_new_tokens=req.max_new_tokens,
                   arrival_step=arrival_step,
                   forced_tokens=req.forced_tokens)


@contextlib.contextmanager
def _dump_failing_trace(meta, reqs):
    """On oracle failure, write a replayable JSON trace (prompts, budgets,
    arrivals, seed) to REPRO_FUZZ_TRACE_DIR — the nightly workflow uploads
    that directory as a run artifact."""
    try:
        yield
    except AssertionError:
        out_dir = os.environ.get("REPRO_FUZZ_TRACE_DIR")
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            trace = dict(meta)
            trace["requests"] = [{
                "rid": r.rid,
                "prompt": np.asarray(r.prompt).tolist(),
                "max_new_tokens": r.max_new_tokens,
                "arrival_step": r.arrival_step,
                "forced_tokens": None if r.forced_tokens is None
                else np.asarray(r.forced_tokens).tolist(),
            } for r in reqs]
            name = "-".join(str(v) for v in meta.values()) + ".json"
            with open(os.path.join(out_dir, name), "w") as f:
                json.dump(trace, f, indent=2)
        raise


def _run(cfg, params, layout, reqs, shared=None, admission="chunked",
         rules=None, sched_kw=None):
    kw = {} if rules is None else {"rules": rules}
    kw.update(sched_kw or {})
    sched = Scheduler(
        params, cfg, layout, admission=admission, chunk_budget=CHUNK_BUDGET,
        record_logits=True, shared_fns=shared,
        prefill_kw=dict(block_q=16, block_k=32) if admission == "eager" else None,
        **kw,
    )
    for r in reqs:
        sched.submit(r)
    sched.run(max_steps=2000)
    assert len(sched.finished) == len(reqs), "trace did not drain"
    if admission == "chunked":
        assert max(sched.prefill_tokens_per_step, default=0) <= CHUNK_BUDGET, (
            "chunk budget violated between decode steps"
        )
    if sched.pager is not None:
        sched.pager.check()
    # the kv-read counter must account exactly for the executed steps
    stats = sched.stats()
    kv = stats["kv_read"]
    assert kv["decode_bytes"] == kv["decode_steps"] * kv["decode_bytes_per_step"]
    # ... and so must the weight-read counter (same step count, static
    # per-step price from the serve-time weight plan)
    wr = stats["weight_read"]
    assert wr["decode_bytes"] == wr["decode_steps"] * wr["decode_bytes_per_step"]
    if layout.kv_format == "bgpp":
        assert kv["bgpp"]["full_rows_per_slot"] <= math.ceil(
            cfg.mcbp.bgpp_keep_ratio * layout.max_seq
        ), "bgpp decode may not fetch more full rows than the keep ratio"
    return sched, {r.rid: r for r in sched.finished}


def _compare_to_alone_runs(cfg, params, reqs, joint, arch_key, kv_format,
                           layout, joint_shared=None, slots=SLOTS,
                           admission="chunked", alone_kw=None, exact=None):
    """Re-run each request alone on the SLOT layout and compare — the slot
    path is the oracle for both layouts.  ``joint_shared``: the joint
    scheduler's compiled fns, reusable only when the joint run itself was
    the slot layout.  ``slots`` must match the joint run's batch: XLA
    reductions are only bit-stable at a fixed batch shape.  ``admission``
    must match the joint run's too — eager (whole-forward) and chunked
    (cache-attend) prefills produce their first-token logits through
    different float paths, so each admission mode oracles against itself.
    ``alone_kw``: extra Scheduler kwargs for the alone runs — the spec
    axis pins them non-speculative regardless of REPRO_SPEC_DECODE.
    ``exact`` (default: bf16 caches) demands bit-identical logits; else
    they agree within 1e-5 (:data:`TP_TOL` for a tensor-parallel joint
    run)."""
    exact = kv_format == "bf16" if exact is None else exact
    slot_layout = _layout_for(cfg, kv_format, "slot", slots=slots)
    shared = joint_shared
    for r in reqs:
        alone_sched, alone = _run(cfg, params, slot_layout, [_clone(r, 0)],
                                  shared=shared, admission=admission,
                                  sched_kw=alone_kw)
        shared = alone_sched.shared_fns()
        got, want = joint[r.rid], alone[r.rid]
        assert len(got.generated) == len(want.generated)
        assert len(got.logit_rows) == len(want.logit_rows)
        for t, (g, w) in enumerate(zip(got.logit_rows, want.logit_rows)):
            if exact:
                assert np.array_equal(g, w), (
                    f"{arch_key}/{kv_format}/{layout} rid {r.rid} token {t}: "
                    f"staggered logits not bit-identical to the slot-layout "
                    f"alone run (max |d| {np.max(np.abs(g - w))})"
                )
            else:
                err = float(np.max(np.abs(g - w)))
                assert err <= 1e-5, (
                    f"{arch_key}/{kv_format}/{layout} rid {r.rid} "
                    f"token {t}: |d|={err}"
                )
        if exact:
            assert got.generated == want.generated, (
                f"{arch_key}/{kv_format}/{layout} rid {r.rid}: greedy "
                f"tokens diverge"
            )


def _fuzz_oracle(arch_key, kv_format, seed, n_requests, layout="slot",
                 admission="chunked", weight_format=None):
    seed = int(os.environ.get("REPRO_FUZZ_SEED", seed))
    rng = np.random.default_rng(seed)
    cfg, params = _model(arch_key)
    if weight_format is not None:
        cfg = apply_weight_format_override(cfg, weight_format)
    reqs = _random_requests(rng, cfg, n_requests,
                            teacher_forced=kv_format != "bf16")
    meta = {"oracle": "fuzz", "arch": arch_key, "kv_format": kv_format,
            "layout": layout, "admission": admission, "seed": seed}
    if weight_format is not None:
        meta["weight_format"] = weight_format
    with _dump_failing_trace(meta, reqs):
        joint_sched, joint = _run(
            cfg, params, _layout_for(cfg, kv_format, layout),
            [_clone(r, r.arrival_step) for r in reqs],
            admission=admission,
        )
        _compare_to_alone_runs(
            cfg, params, reqs, joint, arch_key, kv_format, layout,
            joint_shared=joint_sched.shared_fns()
            if layout == "slot" else None,
            admission=admission,
        )


def _shared_prefix_oracle(kv_format, seed):
    """Deterministic arrival overlap on THREE slots: request 0 prefills a
    32-token system prompt (4 pages) and keeps decoding; requests 1/2
    arrive the SAME step while it is resident, so both are assigned slots
    together and one queues behind the other with its adoption pending —
    the regression shape for the batched decode's garbage writes (a
    waiting slot must hold no shared pages, or the donor's prompt KV gets
    corrupted at its device pos).  Both must adopt the pages AND still
    match the slot-layout alone runs exactly."""
    seed = int(os.environ.get("REPRO_FUZZ_SEED", seed))
    rng = np.random.default_rng(seed)
    cfg, params = _model("dense")
    teacher = kv_format != "bf16"
    prefix = rng.integers(0, cfg.vocab_size, (32,)).astype(np.int32)

    def req(rid, tail_len, max_new, arrival):
        return Request(
            rid=rid,
            prompt=np.concatenate([prefix, rng.integers(
                0, cfg.vocab_size, (tail_len,)).astype(np.int32)]),
            max_new_tokens=max_new,
            arrival_step=arrival,
            forced_tokens=rng.integers(0, cfg.vocab_size, (max_new,))
            .astype(np.int32) if teacher else None,
        )

    # rid 0: resident past step 8 (prefill ~6 steps + 10 decode steps);
    # rid 1/2 arrive together at step 8 with its 4 prompt pages registered
    reqs = [req(0, 4, 10, 0), req(1, 5, 4, 8), req(2, 3, 3, 8)]
    meta = {"oracle": "shared-prefix", "arch": "dense",
            "kv_format": kv_format, "layout": "paged", "seed": seed}
    with _dump_failing_trace(meta, reqs):
        # pinned non-speculative: the scenario's residency window assumes
        # one token per decode step (rid 0 must still hold its pages when
        # rid 2 advances); the spec × adoption interplay has its own
        # long-donor scenario in TestSpecDecodeFuzz
        joint_sched, joint = _run(
            cfg, params, _layout_for(cfg, kv_format, "paged", slots=3),
            [_clone(r, r.arrival_step) for r in reqs],
            sched_kw={"spec_decode": False},
        )
        assert joint_sched.prefix_hit_tokens >= 64, (
            f"both late requests must adopt the 32-token prefix: "
            f"{joint_sched.prefix_hit_tokens} tokens adopted"
        )
        _compare_to_alone_runs(cfg, params, reqs, joint, "dense", kv_format,
                               "paged", slots=3)


@pytest.mark.parametrize("layout", ["slot", "paged"])
class TestFuzzOracle:
    def test_dense_bf16(self, rng_seed, layout):
        _fuzz_oracle("dense", "bf16", rng_seed, 4, layout=layout)

    def test_dense_int8(self, rng_seed, layout):
        _fuzz_oracle("dense", "int8", rng_seed, 4, layout=layout)

    def test_dense_bgpp(self, rng_seed, layout):
        _fuzz_oracle("dense", "bgpp", rng_seed, 4, layout=layout)

    def test_dense_bgpp_eager(self, rng_seed, layout):
        # eager whole-prompt admission over the two-phase paged decode:
        # phase-1 selection sees KV written by the B=1 prefill path, and
        # the logits must still match slot-layout EAGER alone runs (each
        # admission mode oracles itself — eager and chunked prefill
        # produce first-token logits through different float paths)
        _fuzz_oracle("dense", "bgpp", rng_seed, 3, layout=layout,
                     admission="eager")

    @pytest.mark.slow
    def test_dense_bf16_eager(self, rng_seed, layout):
        _fuzz_oracle("dense", "bf16", rng_seed, 3, layout=layout,
                     admission="eager")

    def test_swa_bf16(self, rng_seed, layout):
        # gemma3 mixes ring + global stacks: paged pools behind the rings
        # (prefix reuse stays off — rings can't skip prefill)
        _fuzz_oracle("swa", "bf16", rng_seed, 4, layout=layout)

    @pytest.mark.slow
    def test_swa_int8(self, rng_seed, layout):
        _fuzz_oracle("swa", "int8", rng_seed, 4, layout=layout)

    @pytest.mark.slow
    def test_swa_bgpp(self, rng_seed, layout):
        _fuzz_oracle("swa", "bgpp", rng_seed, 4, layout=layout)

    @pytest.mark.slow
    def test_dense_bf16_heavy(self, rng_seed, layout):
        _fuzz_oracle("dense", "bf16", rng_seed + 1, 7, layout=layout)


@pytest.mark.parametrize("weight_format", ["int8", "bstc"])
class TestWeightFormatOracle:
    """weight_format axis of the fuzz matrix: the quantized serve-time
    weight path must be scheduling-invariant.  Joint staggered runs and
    alone runs derive IDENTICAL records from the same raw params, so with
    bf16 KV every logit row is bit-exact between them — any divergence
    means the weight path leaks scheduling state (slot order, admission
    interleaving) into the projections."""

    def test_dense_slot(self, rng_seed, weight_format):
        _fuzz_oracle("dense", "bf16", rng_seed, 4,
                     weight_format=weight_format)

    def test_dense_paged(self, rng_seed, weight_format):
        _fuzz_oracle("dense", "bf16", rng_seed, 4, layout="paged",
                     weight_format=weight_format)

    @pytest.mark.slow
    def test_dense_int8_kv(self, rng_seed, weight_format):
        # both axes quantized at once: int8 KV fuzz tolerance still holds
        # with the weight path quantized identically on both sides
        _fuzz_oracle("dense", "int8", rng_seed, 4,
                     weight_format=weight_format)

    @pytest.mark.slow
    def test_swa_slot(self, rng_seed, weight_format):
        _fuzz_oracle("swa", "bf16", rng_seed, 4,
                     weight_format=weight_format)


# --------------------------------------------------------------------------
# spec_decode axis: speculative greedy must be BIT-identical to non-spec
# --------------------------------------------------------------------------


def _run_spec(cfg, params, layout, reqs, sched_kw, admission="chunked"):
    """Joint speculative run with the leak gates armed between steps:
    ``PageAllocator.check()`` after EVERY draft/verify/rollback round, a
    fully drained pool at the end, and the byte-accounting laws intact
    (every physical serve_step — draft or verify — pays the full static
    per-step price)."""
    sched = Scheduler(params, cfg, layout, admission=admission,
                      chunk_budget=CHUNK_BUDGET, record_logits=True,
                      prefill_kw=dict(block_q=16, block_k=32)
                      if admission == "eager" else None,
                      **sched_kw)
    assert sched.spec.enabled, "spec axis requires an enabled scheduler"
    for r in reqs:
        sched.submit(r)
    for _ in range(2000):
        if not sched.num_pending:
            break
        sched.step()
        if sched.pager is not None:
            sched.pager.check()
    assert not sched.num_pending, "trace did not drain"
    assert len(sched.finished) == len(reqs)
    stats = sched.stats()
    kv, wr, sp = stats["kv_read"], stats["weight_read"], stats["spec"]
    assert kv["decode_bytes"] == kv["decode_steps"] * kv["decode_bytes_per_step"]
    assert wr["decode_bytes"] == wr["decode_steps"] * wr["decode_bytes_per_step"]
    assert sp["rounds"] > 0, "the trace never actually speculated"
    assert sp["accepted_tokens"] == stats["decoded_tokens"]
    if sched.pager is not None:
        sched.pager.check()
        assert sched.pager.pages_in_use == 0, "spec rollback leaked pages"
    return sched, {r.rid: r for r in sched.finished}


def _spec_fuzz_oracle(arch_key, kv_format, seed, n_requests, layout,
                      draft="planes", admission="chunked"):
    """The tentpole oracle: a speculative joint run (random gamma, random
    draft quality — truncated planes, perfect, or adversarially wrong) is
    compared against NON-speculative slot-layout alone runs, to the same
    bars as the base oracle (bit-exact bf16 / 1e-5 teacher-forced).
    Wrong drafts may only cost steps, never change a single logit."""
    seed = int(os.environ.get("REPRO_FUZZ_SEED", seed))
    rng = np.random.default_rng(seed)
    cfg, params = _model(arch_key)
    teacher = kv_format != "bf16"
    reqs = _random_requests(rng, cfg, n_requests, teacher_forced=teacher)
    gamma = int(rng.integers(1, 5))
    sched_kw = {"spec_decode": True, "draft_gamma": gamma}
    if draft == "planes":
        # planes >= 7 makes the serve weights the (perfect) draft model,
        # so the random range also covers high-acceptance rounds
        sched_kw["draft_planes"] = int(rng.integers(1, 9))
    elif draft == "adversarial":
        drng = np.random.default_rng(seed + 1)
        sched_kw["draft_fn"] = \
            lambda req, t: int(drng.integers(0, cfg.vocab_size))
    elif draft == "perfect":
        assert teacher, "perfect drafts read the teacher-forced tail"
        sched_kw["draft_fn"] = lambda req, t: (
            int(req.forced_tokens[t]) if t < len(req.forced_tokens) else 0)
    else:
        raise ValueError(draft)
    meta = {"oracle": "spec-fuzz", "arch": arch_key, "kv_format": kv_format,
            "layout": layout, "draft": draft, "gamma": gamma,
            "planes": sched_kw.get("draft_planes", 0), "seed": seed,
            "admission": admission}
    with _dump_failing_trace(meta, reqs):
        joint_sched, joint = _run_spec(
            cfg, params, _layout_for(cfg, kv_format, layout),
            [_clone(r, r.arrival_step) for r in reqs], sched_kw,
            admission=admission)
        sp = joint_sched.stats()["spec"]
        if draft == "perfect":
            for r in joint.values():
                assert all(a == gamma + 1 for a in r.spec_accepts[:-1]), \
                    (r.rid, r.spec_accepts)
        for r in joint.values():
            assert all(1 <= a <= gamma + 1 for a in r.spec_accepts)
            assert sum(r.spec_accepts) == len(r.generated) - 1
        assert sp["drafted_tokens"] == gamma * joint_sched.spec_slot_rounds
        # the alone runs are pinned NON-speculative (kwarg beats any
        # REPRO_SPEC_DECODE in the environment): spec vs non-spec IS the
        # comparison, on top of joint-vs-alone scheduling invariance
        _compare_to_alone_runs(
            cfg, params, reqs, joint, arch_key, kv_format, layout,
            joint_shared=joint_sched.shared_fns()
            if layout == "slot" else None,
            admission=admission,
            alone_kw={"spec_decode": False},
        )


def _spec_prefix_oracle(seed):
    """Speculation over ADOPTED pages: request 0 prefills a 32-token
    prefix and keeps speculating long enough (24 decode tokens, so >= 6
    rounds even at full gamma+1 acceptance) that requests 1/2 arrive and
    adopt its prompt pages while its rollback path is live.  Every
    ``rewind_slot`` in the trace therefore runs against a pool holding
    shared pages — the frontier-sharing guard and the digest dereg must
    leave the adopted prefix intact, both late requests must hit it, and
    the logits must still match non-speculative alone runs exactly."""
    seed = int(os.environ.get("REPRO_FUZZ_SEED", seed))
    rng = np.random.default_rng(seed)
    cfg, params = _model("dense")
    prefix = rng.integers(0, cfg.vocab_size, (32,)).astype(np.int32)

    def req(rid, tail_len, max_new, arrival):
        return Request(
            rid=rid,
            prompt=np.concatenate([prefix, rng.integers(
                0, cfg.vocab_size, (tail_len,)).astype(np.int32)]),
            max_new_tokens=max_new,
            arrival_step=arrival,
        )

    reqs = [req(0, 4, 24, 0), req(1, 5, 4, 8), req(2, 3, 3, 8)]
    sched_kw = {"spec_decode": True, "draft_gamma": 3, "draft_planes": 4}
    meta = {"oracle": "spec-prefix", "arch": "dense", "kv_format": "bf16",
            "layout": "paged", "draft": "planes", "gamma": 3, "planes": 4,
            "seed": seed}
    with _dump_failing_trace(meta, reqs):
        joint_sched, joint = _run_spec(
            cfg, params, _layout_for(cfg, "bf16", "paged", slots=3),
            [_clone(r, r.arrival_step) for r in reqs], sched_kw)
        assert joint_sched.prefix_hit_tokens >= 64, (
            f"both late requests must adopt the 32-token prefix: "
            f"{joint_sched.prefix_hit_tokens} tokens adopted"
        )
        _compare_to_alone_runs(cfg, params, reqs, joint, "dense", "bf16",
                               "paged", slots=3,
                               alone_kw={"spec_decode": False})


@pytest.mark.parametrize("layout", ["slot", "paged"])
class TestSpecDecodeFuzz:
    """spec_decode axis of the fuzz matrix (tentpole acceptance): the
    speculative scheduler's output must be bit-identical to
    non-speculative greedy decode on bf16 (<= 1e-5 teacher-forced on
    int8/bgpp), across layouts and draft qualities, with the page
    allocator clean after every round and zero pages leaked."""

    def test_spec_dense_bf16_planes(self, rng_seed, layout):
        _spec_fuzz_oracle("dense", "bf16", rng_seed, 4, layout)

    def test_spec_dense_bf16_adversarial(self, rng_seed, layout):
        _spec_fuzz_oracle("dense", "bf16", rng_seed, 4, layout,
                          draft="adversarial")

    def test_spec_dense_int8_perfect(self, rng_seed, layout):
        _spec_fuzz_oracle("dense", "int8", rng_seed, 4, layout,
                          draft="perfect")

    def test_spec_dense_bgpp_adversarial(self, rng_seed, layout):
        _spec_fuzz_oracle("dense", "bgpp", rng_seed, 4, layout,
                          draft="adversarial")

    def test_spec_dense_bf16_eager(self, rng_seed, layout):
        # eager (whole-forward) admission: speculation only touches decode
        # rounds, so it must be transparent under either prefill path
        _spec_fuzz_oracle("dense", "bf16", rng_seed, 4, layout,
                          admission="eager")

    @pytest.mark.slow
    def test_spec_dense_int8_planes(self, rng_seed, layout):
        _spec_fuzz_oracle("dense", "int8", rng_seed, 4, layout)

    @pytest.mark.slow
    def test_spec_dense_bgpp_perfect(self, rng_seed, layout):
        _spec_fuzz_oracle("dense", "bgpp", rng_seed, 4, layout,
                          draft="perfect")

    @pytest.mark.slow
    def test_spec_dense_bf16_heavy(self, rng_seed, layout):
        _spec_fuzz_oracle("dense", "bf16", rng_seed + 1, 7, layout)


class TestSpecPrefixAdoption:
    """Rollback-heavy speculation while other slots share the donor's
    prompt pages (paged layout only — adoption is a page concept)."""

    def test_spec_prefix_reuse_paged_bf16(self, rng_seed):
        _spec_prefix_oracle(rng_seed)


# --------------------------------------------------------------------------
# sharding parity: identical traces at mesh 1x1 vs (data, model) shards
# --------------------------------------------------------------------------

_MESH_BASE = {}


def _mesh_base_run(kv_format, layout, seed):
    """The single-device joint trace every mesh compares against, cached per
    (format, layout, seed) — compiled fns are NEVER shared across rules."""
    key = (kv_format, layout, seed)
    if key not in _MESH_BASE:
        cfg, params = _model("mesh")
        rng = np.random.default_rng(seed)
        reqs = _random_requests(rng, cfg, 6,
                                teacher_forced=kv_format != "bf16")
        _, joint = _run(cfg, params,
                        _layout_for(cfg, kv_format, layout, slots=MESH_SLOTS),
                        [_clone(r, r.arrival_step) for r in reqs])
        _MESH_BASE[key] = (reqs, joint)
    return _MESH_BASE[key]


def _sharded_parity_oracle(kv_format, layout, mesh, seed,
                           check_alone_runs=False):
    """Run the SAME request trace through a (data, model)-meshed scheduler
    and through a single-device one, and demand the joint traces match —
    bit-exactly for bf16 caches on a data-only mesh, within 1e-5 for
    int8/bgpp (teacher-forced, as in the base oracle) and within
    :data:`TP_TOL` wherever ``model > 1`` sums row-parallel partials; the
    greedy tokens of a bf16 cache must match on every mesh.  Also audits
    the mesh columns of the kv_read counter: interconnect bytes are zero
    exactly at 1x1, positive whenever the layers actually shard, and the
    per-device column recombines to the single-device total."""
    d, m = mesh
    if jax.device_count() < d * m:
        pytest.skip(f"mesh {d}x{m} needs {d * m} host devices; set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    seed = int(os.environ.get("REPRO_FUZZ_SEED", seed))
    cfg, params = _model("mesh")
    exact = kv_format == "bf16" and m == 1
    reqs, want = _mesh_base_run(kv_format, layout, seed)
    meta = {"oracle": "sharded-parity", "arch": "mesh",
            "kv_format": kv_format, "layout": layout,
            "mesh": f"{d}x{m}", "seed": seed}
    with _dump_failing_trace(meta, reqs):
        rules = shd.rules_for(d, m)
        sched, got = _run(
            cfg, params, _layout_for(cfg, kv_format, layout,
                                     slots=MESH_SLOTS),
            [_clone(r, r.arrival_step) for r in reqs], rules=rules,
        )
        for r in reqs:
            g, w = got[r.rid], want[r.rid]
            assert len(g.logit_rows) == len(w.logit_rows)
            for t, (a, b) in enumerate(zip(g.logit_rows, w.logit_rows)):
                if exact:
                    assert np.array_equal(a, b), (
                        f"{kv_format}/{layout}@{d}x{m} rid {r.rid} token "
                        f"{t}: sharded logits not bit-identical to the "
                        f"1x1 run (max |d| {np.max(np.abs(a - b))})"
                    )
                else:
                    err = float(np.max(np.abs(a - b)))
                    assert err <= TP_TOL, (
                        f"{kv_format}/{layout}@{d}x{m} rid {r.rid} "
                        f"token {t}: |d|={err}"
                    )
            if kv_format == "bf16":
                assert g.generated == w.generated, (
                    f"{kv_format}/{layout}@{d}x{m} rid {r.rid}: greedy "
                    f"tokens diverge under sharding"
                )
        kv = sched.stats()["kv_read"]
        assert kv["mesh"] == {"data": d, "model": m}
        per_dev = kv["decode_bytes_per_device_per_step"] * kv["kv_shards"]
        assert abs(per_dev - kv["decode_bytes_per_step"]) <= kv["kv_shards"]
        if (d, m) == (1, 1):
            assert kv["interconnect_bytes_per_step"] == 0
            assert kv["interconnect_bytes"] == 0
        elif m > 1:  # layers actually shard: the all-reduces are priced
            assert kv["interconnect_bytes_per_step"] > 0
            assert kv["interconnect_bytes"] > 0
        if check_alone_runs:
            # the satellite contract: the SHARDED joint trace itself is
            # also pinned to single-device slot-layout alone runs
            _compare_to_alone_runs(cfg, params, reqs, got, "mesh",
                                   kv_format, layout, slots=MESH_SLOTS,
                                   exact=exact)


@pytest.mark.parametrize("layout", ["slot", "paged"])
class TestShardedParity:
    """Mesh (1,1)/(2,1)/(1,4)/(2,4) x layout x kv-format parity (tentpole
    acceptance).  Above-1x1 meshes skip unless the host exposes enough
    devices (the sharded-serving CI job forces 8)."""

    @pytest.mark.parametrize("mesh", MESHES,
                             ids=[f"{d}x{m}" for d, m in MESHES])
    def test_sharded_bf16(self, layout, mesh):
        _sharded_parity_oracle("bf16", layout, mesh, 0,
                               check_alone_runs=mesh == (2, 4))

    def test_sharded_int8_2x4(self, layout):
        _sharded_parity_oracle("int8", layout, (2, 4), 0)

    def test_sharded_bgpp_2x4(self, layout):
        _sharded_parity_oracle("bgpp", layout, (2, 4), 0)

    @pytest.mark.slow
    def test_sharded_int8_1x4(self, layout):
        _sharded_parity_oracle("int8", layout, (1, 4), 0)

    @pytest.mark.slow
    def test_sharded_bgpp_2x1(self, layout):
        _sharded_parity_oracle("bgpp", layout, (2, 1), 0)


# --------------------------------------------------------------------------
# cancellation axis: random disconnects must leak nothing and perturb nobody
# --------------------------------------------------------------------------


def _cancel_plan(rng, reqs):
    """Random cancel/disconnect plan over roughly half the trace.  Token
    triggers model a client hanging up after k streamed tokens (guaranteed
    to fire while the request is still DECODING when ``k <
    max_new_tokens``); step triggers land at arbitrary scheduler steps,
    catching requests queued, mid-chunked-prefill, decoding — or already
    gone (cancel() idempotence).  The first victim is always a token
    trigger so every plan produces at least one live cancel."""
    plan = {}
    victims = list(rng.permutation(len(reqs))[:max(1, len(reqs) // 2)])
    sure = max(range(len(reqs)), key=lambda i: reqs[i].max_new_tokens)
    if sure not in victims:
        victims[0] = sure
    for idx in victims:
        r = reqs[idx]
        if idx == sure or rng.random() < 0.5:
            # v <= max_new - 2: the prefill-completion step can bank TWO
            # tokens at once (first token + same-step decode), so two
            # tokens of headroom guarantee the cancel lands while live
            plan[r.rid] = ("tokens",
                           int(rng.integers(1, r.max_new_tokens - 1)))
        else:
            plan[r.rid] = ("step", int(rng.integers(1, 30)))
    return plan


def _run_with_cancels(cfg, params, layout, reqs, plan):
    """Joint chunked run with mid-flight cancels injected between steps —
    ``PageAllocator.check()`` after EVERY step is the leak gate, and the
    pool must fully drain once the trace ends."""
    sched = Scheduler(params, cfg, layout, admission="chunked",
                      chunk_budget=CHUNK_BUDGET, record_logits=True)
    by_rid = {r.rid: r for r in reqs}
    for r in reqs:
        sched.submit(r)
    pending = dict(plan)
    for _ in range(2000):
        if not sched.num_pending:
            break
        sched.step()
        for rid, (kind, v) in list(pending.items()):
            r = by_rid[rid]
            if ((kind == "tokens" and len(r.generated) >= v)
                    or (kind == "step" and sched.step_count >= v)):
                sched.cancel(rid)  # False when already finished: idempotent
                del pending[rid]
        if sched.pager is not None:
            sched.pager.check()
    assert not sched.num_pending, "trace did not drain"
    assert len(sched.finished) + len(sched.cancelled) == len(reqs)
    assert max(sched.prefill_tokens_per_step, default=0) <= CHUNK_BUDGET
    if sched.pager is not None:
        sched.pager.check()
        assert sched.pager.pages_in_use == 0, "cancellation leaked pages"
    return sched, {r.rid: r for r in sched.finished}


def _cancel_fuzz_oracle(arch_key, kv_format, seed, n_requests, layout):
    seed = int(os.environ.get("REPRO_FUZZ_SEED", seed))
    rng = np.random.default_rng(seed)
    cfg, params = _model(arch_key)
    reqs = _random_requests(rng, cfg, n_requests,
                            teacher_forced=kv_format != "bf16")
    for r in reqs:
        # the token-trigger guarantee in _cancel_plan needs >= 3 decode
        # tokens of budget; pad the teacher-forced tail to match
        if r.max_new_tokens < 3:
            extra = 3 - r.max_new_tokens
            r.max_new_tokens = 3
            if r.forced_tokens is not None:
                r.forced_tokens = np.concatenate([
                    r.forced_tokens,
                    rng.integers(0, cfg.vocab_size, (extra,))
                    .astype(np.int32),
                ])
    clones = [_clone(r, r.arrival_step) for r in reqs]
    for c in clones:  # stir priority scheduling into the fuzzed order too
        c.priority = "interactive" if rng.random() < 0.5 else "batch"
    plan = _cancel_plan(rng, clones)
    meta = {"oracle": "cancel-fuzz", "arch": arch_key,
            "kv_format": kv_format, "layout": layout, "seed": seed,
            "plan": ",".join(f"{r}@{k}{v}" for r, (k, v) in plan.items())}
    with _dump_failing_trace(meta, reqs):
        sched, joint = _run_with_cancels(
            cfg, params, _layout_for(cfg, kv_format, layout), clones, plan)
        assert len(sched.cancelled) >= 1, "plan produced no live cancel"
        survivors = [r for r in reqs if r.rid in joint]
        assert survivors, "every request was cancelled; nothing to oracle"
        _compare_to_alone_runs(cfg, params, survivors, joint, arch_key,
                               kv_format, layout)


@pytest.mark.parametrize("layout", ["slot", "paged"])
class TestCancellationFuzz:
    """Front-door cancellation axis of the fuzz matrix: random cancels and
    disconnects at arbitrary lifecycle points must leak zero pages and
    leave every surviving request's logits exactly what an alone run
    produces (bit-exact bf16 / 1e-5 teacher-forced elsewhere)."""

    def test_dense_bf16_cancel(self, rng_seed, layout):
        _cancel_fuzz_oracle("dense", "bf16", rng_seed, 5, layout)

    def test_dense_int8_cancel(self, rng_seed, layout):
        _cancel_fuzz_oracle("dense", "int8", rng_seed, 4, layout)

    @pytest.mark.slow
    def test_dense_bgpp_cancel(self, rng_seed, layout):
        _cancel_fuzz_oracle("dense", "bgpp", rng_seed, 4, layout)

    @pytest.mark.slow
    def test_swa_bf16_cancel(self, rng_seed, layout):
        _cancel_fuzz_oracle("swa", "bf16", rng_seed, 4, layout)

    @pytest.mark.slow
    def test_dense_bf16_cancel_heavy(self, rng_seed, layout):
        _cancel_fuzz_oracle("dense", "bf16", rng_seed + 1, 8, layout)


class TestSharedPrefixReuse:
    # "paged" in the names keys these into the paged half of the CI
    # kv-format × layout fuzz matrix
    def test_prefix_reuse_paged_bf16(self, rng_seed):
        _shared_prefix_oracle("bf16", rng_seed)

    @pytest.mark.slow
    def test_prefix_reuse_paged_int8(self, rng_seed):
        _shared_prefix_oracle("int8", rng_seed)

    @pytest.mark.slow
    def test_prefix_reuse_paged_bgpp(self, rng_seed):
        _shared_prefix_oracle("bgpp", rng_seed)
