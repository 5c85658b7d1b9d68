"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: per device, busy time, time per step program, per op and per
kernel, and idle gaps, all inside the traced window.

The window is the host span ``bench.window`` that the benchmark writes
around the traced part of its window.  Device planes are
``/device:TPU:<n>``; their ``XLA Modules`` line holds one event per
program run (``jit_serve_step``, ``jit_prefill_chunk``, ...) and their
``XLA Ops`` line one event per op.  Decode kernels are the Mosaic custom
calls inside ``jit_serve_step``.  Read with ``jax.profiler.ProfileData``
alone.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import re
from typing import Dict, List, Tuple

from bench.drive import CLIENT_SPAN, DRAINED_MARK, STEP_SPAN, WINDOW_SPAN

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SERVE_STEP = "jit_serve_step"
PREFILL_CHUNK = "jit_prefill_chunk"


def is_kernel(name: str) -> bool:
    """A Mosaic (Pallas) kernel launch: on TPU an op's event name is its HLO
    instruction, and a kernel's is a ``tpu_custom_call``."""
    return 'custom_call_target="tpu_custom_call"' in name


def op_name(name: str) -> str:
    """``%copy.12 = bf16[...] copy(...)`` -> ``copy``: the instruction's
    name without its number, so that the ops of every step add up."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


@dataclasses.dataclass
class Device:
    """One device's events inside the window (ns)."""

    index: int
    busy_ns: float = 0.0
    modules: Dict[str, List[float]] = dataclasses.field(
        default_factory=lambda: collections.defaultdict(list))
    ops: Dict[str, float] = dataclasses.field(  # by op_name
        default_factory=lambda: collections.defaultdict(float))
    kernel_ns: float = 0.0  # kernels inside jit_serve_step
    kernel_calls: int = 0
    gaps: List[Tuple[float, float]] = dataclasses.field(default_factory=list)

    def module_ns(self, prefix: str) -> float:
        return sum(sum(v) for k, v in self.modules.items()
                   if k.startswith(prefix))

    def module_calls(self, prefix: str) -> int:
        return sum(len(v) for k, v in self.modules.items()
                   if k.startswith(prefix))


@dataclasses.dataclass
class Trace:
    window_ns: Tuple[float, float]
    devices: List[Device]
    host: Dict[str, List[Tuple[float, float]]]  # span name -> intervals

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9


def _union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a, b, w):
    return max(a, w[0]), min(b, w[1])


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def reduce(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host: Dict[str, List[Tuple[float, float]]] = collections.defaultdict(list)
    planes = list(pd.planes)
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in (WINDOW_SPAN, STEP_SPAN, CLIENT_SPAN,
                               DRAINED_MARK):
                    host[ev.name].append((ev.start_ns, ev.end_ns))
    if not host[WINDOW_SPAN]:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span on the host")
    window = max(host[WINDOW_SPAN], key=lambda w: w[1] - w[0])
    devices = []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = Device(index=int(m.group(1)))
        lines = {line.name: line for line in plane.lines}
        serve = []
        for ev in lines["XLA Modules"].events if "XLA Modules" in lines else []:
            a, b = _clip(ev.start_ns, ev.end_ns, window)
            if b > a:
                dev.modules[ev.name].append(b - a)
                if ev.name.startswith(SERVE_STEP):
                    serve.append((a, b))
        busy = []
        for ev in lines["XLA Ops"].events if "XLA Ops" in lines else []:
            a, b = _clip(ev.start_ns, ev.end_ns, window)
            if b <= a:
                continue
            busy.append((a, b))
            dev.ops[op_name(ev.name)] += b - a
            if is_kernel(ev.name) and any(s <= a < e for s, e in serve):
                dev.kernel_ns += b - a
                dev.kernel_calls += 1
        busy = _union(busy)
        dev.busy_ns = sum(b - a for a, b in busy)
        edges = [window[0]] + [x for ab in busy for x in ab] + [window[1]]
        dev.gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
        devices.append(dev)
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    return Trace(window_ns=window, devices=sorted(devices,
                                                   key=lambda d: d.index),
                 host=dict(host))


def _inside(t: float, spans) -> bool:
    return any(a <= t < b for a, b in spans)


def gap_label(trace: Trace, a: float, b: float) -> str:
    """What the host was doing during the device's idle gap ``[a, b)``."""
    mid = 0.5 * (a + b)
    if _inside(mid, trace.host.get(STEP_SPAN, [])):
        return "host inside Scheduler.step"
    if _inside(mid, trace.host.get(CLIENT_SPAN, [])):
        return "host in client code"
    drained = [s for s, _ in trace.host.get(DRAINED_MARK, []) if s <= a]
    steps = [s for s, _ in trace.host.get(STEP_SPAN, []) if s <= a]
    if drained and (not steps or max(drained) >= max(steps)):
        return "idle: no request pending"
    return "host in event loop between steps"


def breakdown(trace: Trace, top: int = 10) -> Dict:
    """The device ops that took most time and the longest idle gaps, each
    labelled by what the host was doing then (the busiest device)."""
    dev = max(trace.devices, key=lambda d: d.busy_ns)
    ops = sorted(dev.ops.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(dev.gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[n, ns * 1e-9] for n, ns in ops],
        "idle_gaps": [[gap_label(trace, a, b), (b - a) * 1e-9]
                      for a, b in gaps],
    }
