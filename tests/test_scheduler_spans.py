"""The scheduler's host spans, read back from a profiler trace on the CPU.

A smoke-size phi4 model serves a handful of requests through
``AsyncServer`` while ``jax.profiler`` traces, and the ``.xplane.pb`` is
read back with ``ProfileData``.  The spans must describe what the step
did: one decode dispatch, logits pull and harvest in each step that
decodes, prefill chunks whose ``(rid, offset, tokens)`` arguments tile
every prompt, one first-token pull per request; and tracing must not
change a served token.
"""

import asyncio
import collections
import glob

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import model_zoo
from repro.serving import kv_cache as kvc
from repro.serving import scheduler as sc
from repro.serving.server import AsyncServer

jax.config.update("jax_platform_name", "cpu")

MAX_SEQ = 64
PROMPTS = (5, 13, 21, 8, 30)  # off-bucket, exact and multi-chunk prompts
MAX_NEW = (4, 3, 5, 2, 3)


@pytest.fixture(scope="module")
def served():
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    params, _ = model_zoo.init(jax.random.key(0), cfg)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in PROMPTS]
    return cfg, params, prompts


def _serve(sched, prompts):
    """Submit every prompt at once through a pumped AsyncServer and return
    each stream's tokens, in submission order."""

    async def main():
        server = AsyncServer(sched)
        pump = asyncio.ensure_future(server.run())
        streams = [server.submit(p, m) for p, m in zip(prompts, MAX_NEW)]
        out = [[t async for t in s] for s in streams]
        server.close()
        await pump
        return out

    return asyncio.run(main())


def _spans(logdir):
    """Every ``sched.*`` event of the host planes: (name, start, end,
    args), per thread line."""
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                   for ev in line.events if ev.name.startswith("sched.")]
            if evs:
                lines.append(sorted(evs, key=lambda e: e[1]))
    return lines


@pytest.fixture(scope="module", params=[("chunked", "slot"),
                                        ("eager", "slot"),
                                        ("chunked", "paged")],
                ids="-".join)
def run(request, served, tmp_path_factory):
    """The same requests served twice on one set of compiled steps: once
    plain, once under the profiler."""
    cfg, params, prompts = served
    admission, kind = request.param
    layout = kvc.layout_for(cfg, 3, MAX_SEQ, kv_format="bf16", layout=kind,
                            page_size=8)

    def sched(shared=None):
        return sc.Scheduler(params, cfg, layout, admission=admission,
                            chunk_budget=8, shared_fns=shared)

    first = sched()
    plain = _serve(first, prompts)
    second = sched(first.shared_fns())
    logdir = tmp_path_factory.mktemp(f"spans-{admission}-{kind}")
    jax.profiler.start_trace(str(logdir))
    try:
        traced = _serve(second, prompts)
    finally:
        jax.profiler.stop_trace()
    return {"admission": admission, "layout": kind, "prompts": prompts, "plain": plain,
            "traced": traced, "sched": second, "lines": _spans(logdir)}


def _steps(run):
    """Each ``sched.step`` with the spans inside it, on its own thread."""
    (evs,) = run["lines"]  # the pump runs every step on one thread
    out = []
    for ev in evs:
        if ev[0] == sc.SPAN_STEP:
            inner = [e for e in evs if e is not ev
                     and ev[1] <= e[1] and e[2] <= ev[2]]
            out.append((ev, inner))
    return out


def test_tracing_changes_no_token(run):
    assert run["traced"] == run["plain"]
    assert [len(t) for t in run["traced"]] == list(MAX_NEW)


def test_one_step_span_per_step(run):
    steps = _steps(run)
    assert len(steps) == run["sched"].step_count
    assert [ev[3]["step"] for ev, _ in steps] == list(range(len(steps)))
    for _, inner in steps:
        assert [e[0] for e in inner].count(sc.SPAN_ADMIT) == 1


def test_decoding_steps_hold_one_dispatch_pull_and_harvest(run):
    decoding = 0
    for step, inner in _steps(run):
        names = collections.Counter(e[0] for e in inner)
        if not names[sc.SPAN_DECODE]:
            assert not names[sc.SPAN_LOGITS] and not names[sc.SPAN_SAMPLE]
            continue
        decoding += 1
        assert (names[sc.SPAN_DECODE], names[sc.SPAN_LOGITS],
                names[sc.SPAN_SAMPLE]) == (1, 1, 1)
        by = {e[0]: e for e in inner}
        dec, pull, pick = (by[sc.SPAN_DECODE], by[sc.SPAN_LOGITS],
                           by[sc.SPAN_SAMPLE])
        assert by[sc.SPAN_ADMIT][2] <= dec[1] <= dec[2] <= pull[1]
        assert pull[2] <= pick[1]
        assert 1 <= dec[3]["decoding"] <= 3
    assert decoding == run["sched"].decode_steps


def test_prefill_chunks_tile_every_prompt(run):
    chunks = collections.defaultdict(list)
    for step, inner in _steps(run):
        for e in inner:
            if e[0] == sc.SPAN_PREFILL_CHUNK:
                chunks[e[3]["rid"]].append((e[3]["offset"], e[3]["tokens"]))
    assert sorted(chunks) == list(range(len(PROMPTS)))
    for rid, parts in chunks.items():
        parts.sort()
        ends = np.cumsum([0] + [n for _, n in parts])
        assert [off for off, _ in parts] == list(ends[:-1])
        assert ends[-1] == PROMPTS[rid]
        if run["admission"] == "chunked":
            assert all(n <= 8 for _, n in parts)
        else:
            assert len(parts) == 1


def test_first_token_once_per_request(run):
    rids = [e[3]["rid"] for _, inner in _steps(run) for e in inner
            if e[0] == sc.SPAN_FIRST_TOKEN]
    assert sorted(rids) == list(range(len(PROMPTS)))


def test_page_table_pushes_only_on_paged_layouts(run):
    (evs,) = run["lines"]
    steps = [e for e in evs if e[0] == sc.SPAN_STEP]
    pushes = [e for e in evs if e[0] == sc.SPAN_SYNC_PAGES]
    assert bool(pushes) == (run["layout"] == "paged")
    assert all(any(s[1] <= e[1] and e[2] <= s[2] for s in steps)
               for e in pushes)
