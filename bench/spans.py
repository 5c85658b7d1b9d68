"""Where the device idles inside the scheduler's own steps.

``Scheduler.step`` writes host spans named ``sched.*`` on the profiler's
clock (``repro.serving.scheduler``: ``sched.step`` around the whole step,
and inside it ``sched.admit``, ``sched.prefill_chunk``,
``sched.first_token``, ``sched.sync_pages``, ``sched.decode``,
``sched.logits``, ``sched.sample``, ``sched.spec_round``).  This module
reads them from the same ``.xplane.pb`` that ``bench/trace.py`` reduces,
and intersects them with each device's idle gaps: how much of the time the
device waits falls inside which span.

As a tool it serves one traced run of a cell, as ``bench/run.py --trace
1`` does, and prints that run's result line with a ``spans`` object added:

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s> \\
        [--xplane <copy.xplane.pb>]

``spans`` holds the idle split per decode step and per prefill chunk, the
share of the idle time inside ``sched.step`` that a named child span
covers, the longest gaps labelled by the innermost program span, the mean
host time of each span, the requests' queue wait (admission time less
submission time), and what one span costs on this host with no profiler
running and with one.  ``--xplane`` keeps a copy of the raw trace (the
fixture in ``tests/bench/data`` is cut from one by
``tests/bench/trim_spans.py``).
"""

from __future__ import annotations

import argparse
import collections
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
PREFIX = "sched."
STEP = "sched.step"
ADMIT = "sched.admit"
PREFILL_CHUNK = "sched.prefill_chunk"
DECODE = "sched.decode"
LOGITS = "sched.logits"
SAMPLE = "sched.sample"
CHILDREN = (ADMIT, DECODE, LOGITS, SAMPLE)  # what a decoding step is made of

Spans = Dict[str, List[Tuple[float, float]]]


def collect(path: str) -> Spans:
    """The program's host spans in a trace: name -> [(start, end)] (ns)."""
    from jax.profiler import ProfileData

    out: Spans = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out[ev.name].append((ev.start_ns, ev.end_ns))
    return dict(out)


def overlap_ns(gaps: Sequence[Tuple[float, float]], intervals) -> float:
    """Length of ``gaps`` (disjoint) that lies inside the union of
    ``intervals``."""
    from bench.trace import _union

    spans = _union(intervals)
    total, j = 0.0, 0
    for a, b in sorted(gaps):
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < b:
            total += min(b, spans[k][1]) - max(a, spans[k][0])
            k += 1
    return total


def idle_inside(trace, spans: Spans, names: Sequence[str]) -> float:
    """Device idle time (ns) inside the union of the named spans, the mean
    over devices."""
    intervals = [iv for n in names for iv in spans.get(n, [])]
    per_dev = [overlap_ns(d.gaps, intervals) for d in trace.devices]
    return sum(per_dev) / len(per_dev)


def count_in_window(trace, spans: Spans, name: str) -> int:
    """The named spans that start inside the traced window."""
    w0, w1 = trace.window_ns
    return sum(1 for a, _ in spans.get(name, []) if w0 <= a < w1)


def innermost(spans: Spans, t: float):
    """The name of the latest-starting program span that holds ``t``: with
    spans nested on one thread, the innermost."""
    best = None
    for name, ivs in spans.items():
        for a, b in ivs:
            if a <= t < b and (best is None or a > best[0]):
                best = (a, name)
    return best[1] if best else None


def gap_label(trace, spans: Spans, a: float, b: float) -> str:
    """What the host was doing in the device's idle gap ``[a, b)``: the
    innermost program span at its middle, or else what ``bench/trace.py``
    says."""
    from bench.trace import gap_label as benchmark_label

    name = innermost(spans, 0.5 * (a + b))
    return f"host in {name}" if name else benchmark_label(trace, a, b)


def split(trace, spans: Spans) -> Dict:
    """The idle split of one traced window, and its ten longest gaps."""
    ms = 1e-6
    decodes = count_in_window(trace, spans, DECODE)
    chunks = count_in_window(trace, spans, PREFILL_CHUNK)
    in_step = idle_inside(trace, spans, [STEP])
    named = idle_inside(trace, spans, CHILDREN)  # each lies inside a step
    w0, w1 = trace.window_ns
    host = {}
    for name, ivs in sorted(spans.items()):
        inside = [b - a for a, b in ivs if w0 <= a < w1]
        if inside:
            host[name] = {"count": len(inside),
                          "mean_ms": float(np.mean(inside)) * ms,
                          "idle_ms": idle_inside(trace, spans, [name]) * ms}
    dev = max(trace.devices, key=lambda d: d.busy_ns)
    gaps = sorted(dev.gaps, key=lambda g: g[0] - g[1])[:10]
    idle = np.mean([sum(b - a for a, b in d.gaps) for d in trace.devices])
    return {
        "logits_idle_ms": (idle_inside(trace, spans, [LOGITS]) * ms / decodes
                           if decodes else None),
        "sample_idle_ms": (idle_inside(trace, spans, [SAMPLE]) * ms / decodes
                           if decodes else None),
        "admit_idle_ms": (idle_inside(trace, spans, [ADMIT]) * ms / chunks
                          if chunks else None),
        "decode_steps": decodes,
        "prefill_chunks": chunks,
        "idle_ms": float(idle) * ms,
        "idle_in_step_ms": in_step * ms,
        "named_share": float(named / in_step) if in_step else None,
        "host": host,
        "idle_gaps": [[gap_label(trace, spans, a, b), (b - a) * 1e-9]
                      for a, b in gaps],
    }


def queue_wait_p90_s(result: Dict, requests, skip=(0.0, 0.0)):
    """90th percentile of admission time less submission time over the
    requests planned for the window but those due in ``skip``; one not
    admitted (or not sent) by the close counts at the close.  ``requests``
    are the scheduler's requests in the order it received them, which is
    the order the clients sent theirs."""
    from bench import drive

    close = result["close"]
    sent = sorted((r for r in result["records"] if r.sent is not None),
                  key=lambda r: r.sent)
    got = [q for q in requests if q.rid >= 0]  # the warm-up request is -1
    if len(got) != len(sent):
        return None
    of = {id(r): q for r, q in zip(sent, got)}
    waits = []
    for r in drive._due(result, skip):
        q = of.get(id(r))
        if q is None:
            waits.append(close - r.due)
            continue
        admitted = q.admit_time if q.admit_time >= 0 else close
        waits.append(min(admitted, close) - q.submit_time)
    return float(np.percentile(waits, 90)) if waits else None


def span_cost_us(n: int = 100_000, traced: bool = False) -> float:
    """Microseconds for one enter and exit of a span with four arguments,
    the costliest the scheduler writes; under a profiler when ``traced``."""
    import jax
    from jax.profiler import TraceAnnotation

    logdir = tempfile.mkdtemp() if traced else None
    if traced:
        jax.profiler.start_trace(logdir)
    t0 = time.perf_counter()
    for i in range(n):
        with TraceAnnotation(PREFILL_CHUNK, rid=i, slot=3, offset=256,
                             tokens=256):
            pass
    us = (time.perf_counter() - t0) / n * 1e6
    if traced:
        jax.profiler.stop_trace()
        shutil.rmtree(logdir, ignore_errors=True)
    return us


def main(argv=None) -> None:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--xplane", default=None,
                    help="keep a copy of the raw trace at this path")
    opts = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from repro.serving.scheduler import Scheduler

    from bench import run, trace as tr
    from bench.spec import load_cell

    cell = load_cell(opts.workload, ROOT)
    devs = run.check_devices(jax, cell.chips)
    run.enable_compile_cache(jax)
    cost = {"off_us": span_cost_us()}
    # the run is bench/run.py's own; these wrappers only keep what it
    # reads and throws away: the trace, the requests, the profiler's start
    got: Dict = {"requests": []}
    reduce, submit, start = tr.reduce, Scheduler.submit, jax.profiler.start_trace

    def keep_reduce(path):
        got["spans"] = collect(path)
        if opts.xplane:
            shutil.copy(path, opts.xplane)
        got["trace"] = reduce(path)
        return got["trace"]

    def keep_submit(self, request):
        got["requests"].append(request)
        return submit(self, request)

    def keep_start(*a, **k):
        t0 = time.perf_counter()
        start(*a, **k)
        got["started"] = (t0, time.perf_counter())

    seen: Dict = {}
    tr.reduce, Scheduler.submit = keep_reduce, keep_submit
    jax.profiler.start_trace = keep_start
    try:
        out = run.run_cell(cell, opts.seed, opts.seconds, True,
                           t_start=t_start, devs=devs, keep=seen.update)
    finally:
        tr.reduce, Scheduler.submit = reduce, submit
        jax.profiler.start_trace = start
    cost["on_us"] = span_cost_us(20_000, traced=True)
    # as run.py leaves out of its traced tails: due while the profiler
    # started, up to the traced part's opening
    t_trace = max(0.0, opts.seconds - float(cell.serving["trace_s"]))
    skip = (got["started"][0],
            max(got["started"][1], seen["due_open"] + t_trace))
    info = split(got["trace"], got["spans"])
    info["queue_wait_p90_s"] = queue_wait_p90_s(seen, got["requests"], skip)
    info["span_cost"] = cost
    out["spans"] = info
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
