"""Cut a trace recorded by ``bench/run.py --trace 1`` down to a test
fixture: what ``bench/trace.py`` reads, and nothing else.

    python tests/bench/trim_xplane.py <recorded.xplane.pb> <out.xplane.pb>

Kept: the host spans the benchmark writes (``bench.*``), and on every
``/device:TPU:<n>`` plane the ``XLA Modules`` and ``XLA Ops`` events that
overlap the ``bench.window`` span, with their times as recorded.  An op's
name is its HLO instruction; it is cut to the instruction's name and, for
a Mosaic kernel, its ``custom_call_target`` (what the reduction reads).
"""

from __future__ import annotations

import json
import re
import sys

LINES = ("XLA Modules", "XLA Ops")
TARGET = re.compile(r'custom_call_target="[^"]+"')


def _short(name: str) -> str:
    if " = " not in name:
        return name
    head = name.split(" = ", 1)[0]
    target = TARGET.search(name)
    return f"{head} = {target.group(0)}" if target else head


def _plane(pid: int, name: str, lines) -> str:
    """Text proto of one XPlane: ``lines`` is ``[(name, [(name, start_ns,
    end_ns)])]``; times become offsets from each line's first event."""
    meta, out = {}, [f"planes {{ id: {pid} name: {json.dumps(name)}"]
    for lid, (lname, events) in enumerate(lines):
        t0 = min((int(a) for _, a, _ in events), default=0)
        out.append(f" lines {{ id: {lid} name: {json.dumps(lname)} "
                   f"timestamp_ns: {t0}")
        for ename, a, b in events:
            mid = meta.setdefault(ename, len(meta) + 1)
            out.append(f"  events {{ metadata_id: {mid} "
                       f"offset_ps: {int(round((a - t0) * 1000))} "
                       f"duration_ps: {int(round((b - a) * 1000))} }}")
        out.append(" }")
    for ename, mid in meta.items():
        out.append(f" event_metadata {{ key: {mid} value {{ id: {mid} "
                   f"name: {json.dumps(ename)} }} }}")
    out.append("}")
    return "\n".join(out)


def trim(src: str, dst: str) -> None:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(src).planes)
    host = [(ev.name, ev.start_ns, ev.end_ns) for p in planes
            if p.name.startswith("/host:") for line in p.lines
            for ev in line.events if ev.name.startswith("bench.")]
    windows = [(a, b) for n, a, b in host if n == "bench.window"]
    if not windows:
        raise ValueError(f"{src}: no bench.window span")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    host = [(n, a, b) for n, a, b in host if b >= w0 and a <= w1]
    text = [_plane(0, "/host:CPU", [("python3", host)])]
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
