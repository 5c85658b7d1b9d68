"""Cut a trace recorded by ``bench/spans.py --xplane`` down to a test
fixture of a few scheduler steps with the program's spans.

    python tests/bench/trim_spans.py <recorded.xplane.pb> <out.xplane.pb>

As ``trim_xplane.py`` cuts, but the host keeps the program's ``sched.*``
spans beside the benchmark's ``bench.*`` ones (names only), and the window
is narrowed to ``STEPS`` consecutive ``bench.step`` spans, the first of them
one that runs a prefill chunk: a new ``bench.window`` span over them
replaces the recorded one.  Times stay as recorded.
"""

from __future__ import annotations

import sys

from trim_xplane import LINES, _plane, _short

STEPS = 5


def trim(src: str, dst: str) -> None:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(src).planes)
    host = [(ev.name, ev.start_ns, ev.end_ns) for p in planes
            if p.name.startswith("/host:") for line in p.lines
            for ev in line.events
            if ev.name.startswith(("bench.", "sched."))]
    windows = [(a, b) for n, a, b in host if n == "bench.window"]
    if not windows:
        raise ValueError(f"{src}: no bench.window span")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    bench_steps = sorted((a, b) for n, a, b in host
                         if n == "bench.step" and w0 <= a and b <= w1)
    chunks = [a for n, a, _ in host if n == "sched.prefill_chunk"]
    first = next(i for i, (a, b) in enumerate(bench_steps)
                 if i + STEPS <= len(bench_steps)
                 and any(a <= c < b for c in chunks))
    w0, w1 = bench_steps[first][0], bench_steps[first + STEPS - 1][1]
    host = [(n, a, b) for n, a, b in host
            if n != "bench.window" and b >= w0 and a <= w1]
    host.append(("bench.window", w0, w1))
    text = [_plane(0, "/host:CPU", [("python3", sorted(
        host, key=lambda e: e[1]))])]
    for p in planes:
        if not p.name.startswith("/device:TPU:"):
            continue
        lines = [(line.name, [(_short(ev.name), ev.start_ns, ev.end_ns)
                              for ev in line.events
                              if ev.end_ns >= w0 and ev.start_ns <= w1])
                 for line in p.lines if line.name in LINES]
        text.append(_plane(len(text), p.name, lines))
    with open(dst, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace("\n".join(text)))


if __name__ == "__main__":
    trim(sys.argv[1], sys.argv[2])
