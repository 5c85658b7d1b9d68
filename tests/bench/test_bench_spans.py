"""The reading of the program's ``sched.*`` spans (``bench/spans.py``): on
hand-made intervals, on the older fixture that has no program spans (what
the parent program leaves in a trace), and on a trace recorded on one TPU
v5e with the spans (``data/phi4-chat-int8-spans.xplane.pb``: five
scheduler steps of a ``phi4-chat-int8`` window, the first with a prefill
chunk, cut by ``trim_spans.py``)."""

import types
from pathlib import Path

import pytest

from bench import drive, spans as sp, trace as tr

DATA = Path(__file__).resolve().parent / "data"
OLD = DATA / "phi4-chat-int8.xplane.pb"
NEW = DATA / "phi4-chat-int8-spans.xplane.pb"


@pytest.mark.parametrize("gaps,spans,want", [
    ([(0, 10)], [(2, 4), (3, 6)], 4),  # overlapping spans count once
    ([(0, 10), (20, 30)], [(5, 25)], 10),  # one span over two gaps
    ([(0, 10)], [(10, 20), (-5, 0)], 0),  # touching is not inside
    ([(0, 10), (12, 14)], [(1, 2), (8, 13), (13.5, 30)], 4.5),
    ([], [(0, 1)], 0),
])
def test_overlap_ns(gaps, spans, want):
    assert sp.overlap_ns(gaps, spans) == pytest.approx(want)


def test_innermost_is_the_latest_start():
    spans = {"sched.step": [(0, 100)], "sched.admit": [(1, 20)],
             "sched.prefill_chunk": [(5, 10)]}
    assert sp.innermost(spans, 7) == "sched.prefill_chunk"
    assert sp.innermost(spans, 15) == "sched.admit"
    assert sp.innermost(spans, 50) == "sched.step"
    assert sp.innermost(spans, 100) is None


def test_without_program_spans_the_labels_are_the_benchmarks():
    reduced = tr.reduce(str(OLD))
    spans = sp.collect(str(OLD))
    assert spans == {}
    dev = reduced.devices[0]
    assert [sp.gap_label(reduced, spans, a, b) for a, b in dev.gaps] == [
        tr.gap_label(reduced, a, b) for a, b in dev.gaps]
    out = sp.split(reduced, spans)
    assert out["logits_idle_ms"] is None and out["admit_idle_ms"] is None
    assert out["named_share"] is None


def _req(rid, submit, admit=-1.0):
    return types.SimpleNamespace(rid=rid, submit_time=submit,
                                 admit_time=admit)


def _result(dues, sents):
    recs = []
    for due, sent in zip(dues, sents):
        recs.append(drive.Sent(planned=None, due=due, sent=sent))
    return {"records": recs, "due_open": 10.0, "due_close": 20.0,
            "open": 10.0, "close": 20.0}


def test_queue_wait_counts_the_unadmitted_at_the_close():
    result = _result([9.0, 11.0, 12.0, 13.0, 19.0],
                     [9.0, 11.0, 12.5, 13.0, None])
    # requests as the scheduler received them: the warm-up first
    reqs = [_req(-1, 0.0, 0.0), _req(0, 9.0, 9.5), _req(1, 11.0, 11.25),
            _req(2, 12.5, 13.5), _req(3, 13.0)]
    # in the window: waits 0.25, 1.0, 7.0 (never admitted), 1.0 (never
    # sent: from its due time)
    want = sorted([0.25, 1.0, 7.0, 1.0])
    got = sp.queue_wait_p90_s(result, reqs)
    assert got == pytest.approx(want[2] + 0.7 * (want[3] - want[2]))
    # due in the profiler's start: left out
    assert sp.queue_wait_p90_s(result, reqs, skip=(12.9, 13.1)) == \
        pytest.approx(1.0)
    # the two sides disagree on what was sent: no reading
    assert sp.queue_wait_p90_s(result, reqs[:-1]) is None


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce(str(NEW)), sp.collect(str(NEW))


def test_fixture_holds_one_program_step_per_benchmark_step(recorded):
    reduced, spans = recorded
    steps = spans[sp.STEP]
    for a, b in reduced.host[drive.STEP_SPAN]:
        assert sum(1 for s, e in steps if a <= s and e <= b) == 1
    assert sp.count_in_window(reduced, spans, sp.PREFILL_CHUNK) >= 1
    dev = reduced.devices[0]
    assert sp.count_in_window(reduced, spans, sp.DECODE) == \
        dev.module_calls(tr.SERVE_STEP)


def test_idle_inside_spans_is_part_of_the_gaps(recorded):
    reduced, spans = recorded
    dev = reduced.devices[0]
    idle = sum(b - a for a, b in dev.gaps)
    in_step = sp.idle_inside(reduced, spans, [sp.STEP])
    named = sp.idle_inside(reduced, spans, sp.CHILDREN)
    assert 0 < named <= in_step <= idle
    parts = [sp.idle_inside(reduced, spans, [n]) for n in sp.CHILDREN]
    assert sum(parts) == pytest.approx(named)  # the children never overlap
    out = sp.split(reduced, spans)
    assert out["named_share"] >= 0.9
    assert out["logits_idle_ms"] > 0 and out["sample_idle_ms"] > 0
    assert out["admit_idle_ms"] > 0


def test_gaps_inside_a_step_are_named_by_the_program(recorded):
    reduced, spans = recorded
    dev = reduced.devices[0]
    steps = spans[sp.STEP]
    named = 0
    for a, b in dev.gaps:
        label = sp.gap_label(reduced, spans, a, b)
        mid = 0.5 * (a + b)
        if any(s <= mid < e for s, e in steps):
            assert label.startswith("host in sched.")
            named += 1
        else:
            assert label == tr.gap_label(reduced, a, b)
    assert named >= 5


def test_named_decode_kernel_is_still_read(recorded):
    reduced, _ = recorded
    dev = reduced.devices[0]
    assert dev.kernel_calls == 32 * dev.module_calls(tr.SERVE_STEP)
    assert "paged_flash_decode" in dev.ops
