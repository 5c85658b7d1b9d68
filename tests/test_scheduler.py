"""Continuous-batching scheduler: slot lifecycle, eviction/reuse isolation,
admission ordering, and trace/stats plumbing."""

import json

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.models import model_zoo
from repro.serving import kv_cache as kvc
from repro.serving.request import Request, SlotState
from repro.serving.scheduler import Scheduler

jax.config.update("jax_platform_name", "cpu")

ARCH = "phi4-mini-3.8b"
MAX_SEQ = 64


@pytest.fixture(scope="module")
def served():
    cfg = get_config(ARCH, smoke=True)
    params, _ = model_zoo.init(jax.random.key(0), cfg)
    return cfg, params


def make_sched(cfg, params, slots=2, kv_format="int8", admission="chunked"):
    layout = kvc.layout_for(cfg, slots, MAX_SEQ, kv_format=kv_format)
    return Scheduler(params, cfg, layout, admission=admission, chunk_budget=8,
                     prefill_kw=dict(block_q=8, block_k=8))


def make_requests(cfg, n, rng, max_new=4, stagger=2):
    return [
        Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, (int(rng.integers(6, 14)),))
            .astype(np.int32),
            max_new_tokens=max_new,
            arrival_step=i * stagger,
        )
        for i in range(n)
    ]


class TestLifecycle:
    def test_all_requests_finish_and_slots_recycle(self, served):
        cfg, params = served
        rng = np.random.default_rng(0)
        sched = make_sched(cfg, params, slots=2)
        reqs = make_requests(cfg, 5, rng)  # 5 requests > 2 slots => reuse
        for r in reqs:
            sched.submit(r)
        stats = sched.run(max_steps=200)

        assert stats["finished_requests"] == 5
        assert all(s.state is SlotState.EMPTY for s in sched.slots)
        assert all(len(r.generated) == r.max_new_tokens for r in sched.finished)
        # FIFO admission among arrived requests
        assert [r.rid for r in sorted(sched.finished,
                                      key=lambda r: r.admitted_step)] == [
            r.rid for r in sorted(sched.finished, key=lambda r: r.arrival_step)
        ]
        for r in sched.finished:
            assert r.queue_wait_steps >= 0
            assert r.latency_steps >= len(r.generated) - 1
        # EMPTY slots keep stepping their pos harmlessly (their rows are
        # garbage by design); eviction + the next admission reset them
        for s in sched.slots:
            sched.cache = kvc.reset_slot(sched.cache, sched.layout, s.index)
        assert np.all(np.asarray(sched.cache["pos"]) == 0)
        json.dumps(stats)  # trace must be JSON-serializable

    def test_occupancy_tracked(self, served):
        cfg, params = served
        rng = np.random.default_rng(1)
        sched = make_sched(cfg, params, slots=2)
        for r in make_requests(cfg, 4, rng, max_new=3, stagger=0):
            sched.submit(r)
        stats = sched.run(max_steps=100)
        assert 0.0 < stats["mean_occupancy"] <= 1.0
        # with 4 back-to-back requests on 2 slots the busy steps are full
        assert stats["mean_occupancy"] > 0.5

    def test_max_seq_clamps_decode(self, served):
        cfg, params = served
        rng = np.random.default_rng(2)
        sched = make_sched(cfg, params, slots=1)
        prompt = rng.integers(0, cfg.vocab_size, (MAX_SEQ - 3,)).astype(np.int32)
        sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=64))
        stats = sched.run(max_steps=100)
        assert stats["finished_requests"] == 1
        (req,) = sched.finished
        # prompt_len + generated - 1 never reaches max_seq
        assert req.prompt_len + len(req.generated) - 1 <= MAX_SEQ
        assert len(req.generated) < 64

    def test_rejects_malformed_prompts(self, served):
        cfg, params = served
        sched = make_sched(cfg, params, slots=1)
        with pytest.raises(ValueError):  # empty prompt: no logits to sample
            sched.submit(Request(rid=0, prompt=np.zeros((0,), np.int32),
                                 max_new_tokens=2))
        with pytest.raises(ValueError):  # no decode slot left below max_seq
            sched.submit(Request(
                rid=1, prompt=np.zeros((MAX_SEQ,), np.int32),
                max_new_tokens=2))

    def test_buckets_smaller_than_budget(self, served):
        """Custom buckets below chunk_budget: admission chunks at the
        largest bucket instead of overrunning it."""
        cfg, params = served
        rng = np.random.default_rng(7)
        layout = kvc.layout_for(cfg, 1, MAX_SEQ, kv_format="int8")
        sched = Scheduler(params, cfg, layout, admission="chunked",
                          chunk_budget=16, buckets=(4,))
        sched.submit(Request(
            rid=0, prompt=rng.integers(0, cfg.vocab_size, (9,))
            .astype(np.int32), max_new_tokens=2))
        sched.run(max_steps=100)
        assert len(sched.finished) == 1
        assert max(sched.prefill_tokens_per_step) <= 16

    def test_chunked_from_eager_shared_fns(self, served):
        """shared_fns from an eager scheduler lack a ChunkedPrefill; a
        chunked scheduler must build its own instead of crashing."""
        cfg, params = served
        rng = np.random.default_rng(8)
        eager = make_sched(cfg, params, slots=1, admission="eager")
        sched = Scheduler(params, cfg, eager.layout, admission="chunked",
                          chunk_budget=8, shared_fns=eager.shared_fns())
        sched.submit(Request(
            rid=0, prompt=rng.integers(0, cfg.vocab_size, (10,))
            .astype(np.int32), max_new_tokens=2))
        sched.run(max_steps=100)
        assert len(sched.finished) == 1

    def test_eos_stops_decode(self, served):
        cfg, params = served
        rng = np.random.default_rng(3)
        sched = make_sched(cfg, params, slots=1)
        eos = 7

        def eos_after_two(logits):
            # deterministic stand-in sampler: emit eos from the 2nd token on
            return np.full((logits.shape[0],), eos, np.int32)

        sched.sample_fn = eos_after_two
        prompt = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
        sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=16,
                             eos_id=eos))
        stats = sched.run(max_steps=50)
        assert stats["finished_requests"] == 1
        assert sched.finished[0].generated[-1] == eos
        assert len(sched.finished[0].generated) < 16


class TestEagerAdmission:
    """The PR-2 whole-prompt admission path stays available as the
    reference/baseline (``admission="eager"``)."""

    def test_lifecycle_and_trace(self, served):
        cfg, params = served
        rng = np.random.default_rng(5)
        sched = make_sched(cfg, params, slots=2, admission="eager")
        for r in make_requests(cfg, 4, rng, max_new=3):
            sched.submit(r)
        stats = sched.run(max_steps=200)
        assert stats["admission"] == "eager"
        assert stats["finished_requests"] == 4
        # eager admission spends whole prompts in one step — the budget
        # audit records it (that's exactly what chunked admission bounds)
        assert stats["max_prefill_tokens_per_step"] >= 6
        json.dumps(stats)

    def test_matches_chunked_admission_logits(self, served):
        """Chunked and eager admission are different prefill numerics of
        the same math: teacher-forced per-token logits must agree tightly
        (bf16, no greedy compounding)."""
        cfg, params = served
        rng = np.random.default_rng(6)
        reqs = [
            (rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
             rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32))
            for n in (9, 14)
        ]

        def run(admission):
            sched = make_sched(cfg, params, slots=2, kv_format="bf16",
                               admission=admission)
            sched.record_logits = True
            for rid, (prompt, forced) in enumerate(reqs):
                sched.submit(Request(rid=rid, prompt=prompt,
                                     max_new_tokens=4, arrival_step=rid,
                                     forced_tokens=forced))
            sched.run(max_steps=100)
            return {r.rid: r.logit_rows for r in sched.finished}

        chunked, eager = run("chunked"), run("eager")
        for rid in chunked:
            for t, (g, e) in enumerate(zip(chunked[rid], eager[rid])):
                err = float(np.max(np.abs(g - e)))
                assert err < 5e-3, f"rid {rid} token {t}: |d|={err}"


class TestSlotIsolation:
    @pytest.mark.parametrize("admission", ["chunked", "eager"])
    def test_concurrent_greedy_matches_alone(self, served, admission):
        """Greedy decodes of a request must be identical whether it shares
        the batch with others (incl. slot reuse after eviction) or runs
        with every other slot EMPTY."""
        cfg, params = served
        rng = np.random.default_rng(4)
        prompts = [
            rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in (9, 13, 7)
        ]

        shared = {}

        def run(selected):
            sched = make_sched(cfg, params, slots=2, kv_format="bf16",
                               admission=admission)
            if shared:
                sched.serve_step = shared["serve_step"]
                sched.chunked = shared["chunked"]
            shared.update(sched.shared_fns())
            for i in selected:
                sched.submit(Request(rid=i, prompt=prompts[i],
                                     max_new_tokens=5, arrival_step=2 * i))
            sched.run(max_steps=100)
            return {r.rid: r.generated for r in sched.finished}

        joint = run([0, 1, 2])  # request 2 reuses the slot request 0 held
        for rid in range(3):
            alone = run([rid])
            assert joint[rid] == alone[rid], f"request {rid} not isolated"


def test_mesh_without_param_specs_raises(served, monkeypatch):
    """On a mesh the weights are placed from the model's spec tree; a
    family without one raises instead of replicating the whole model on
    every device."""
    from repro.serving import sharded as shd

    cfg, params = served

    def no_specs(cfg_):
        raise ValueError(f"no param specs for family {cfg_.family!r}")

    monkeypatch.setattr(model_zoo, "param_specs", no_specs)
    layout = kvc.layout_for(cfg, 2, MAX_SEQ, kv_format="int8")
    with pytest.raises(ValueError, match="no param specs"):
        Scheduler(params, cfg, layout, shd.rules_for(1, 1))
