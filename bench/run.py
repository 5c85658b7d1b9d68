"""Serve one benchmark cell on the accelerator and print its numbers.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, from the root of a checkout.  It reads the cell from
``BENCHMARK.json`` and ``bench/`` by name, refuses to run without a TPU or
with fewer chips than the cell asks for, makes the weights from the seed,
compiles and warms every program the cell's traffic uses, serves a warm
period of that traffic, and then measures a window of ``--seconds``
through the program's ``AsyncServer``.  With ``--trace 1`` the last
seconds of the window are traced and the per-layer metrics are reported
instead of the end-to-end ones.

After the window, the program's state is freed and a sample of the
requests it finished is compared with the plain float32 reference (see
``check``).  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``, each number compared
beside its limit; the same numbers end standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


class NoDevice(SystemExit):
    """The accelerator the cell needs is not there: no result is printed."""


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_devices(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"[bench] needs a TPU; JAX found {devs[0].platform} "
                       f"({devs[0].device_kind}) x{len(devs)}")
    if len(devs) < chips:
        raise NoDevice(f"[bench] the cell needs {chips} chips; JAX found "
                       f"{len(devs)}")
    return devs


def enable_compile_cache(jax) -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program cached, so only a
    checkout's first run of a cell compiles."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


class CompileLog:
    """Backend compiles as JAX's monitoring events report them."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **kw):
        if event == self.EVENT:
            self.times.append(time.perf_counter())

    def between(self, a: float, b: float) -> int:
        return sum(1 for t in self.times if a <= t < b)


@dataclasses.dataclass
class Readings:
    """What the per-layer metric readers see of one run."""

    window: Dict
    steps: list  # scheduler steps of the measured window
    traced_steps: list  # scheduler steps inside the traced part
    trace: object
    conf: Dict
    counts: object
    kv_format: str
    peaks: Dict
    chips: int
    peak_bytes: int


def peaks_for(kind: str) -> Dict:
    with open(ROOT / "bench" / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def warm_programs(sched) -> None:
    """Compile and run every program the traffic uses: the slot reset, each
    chunk bucket and the decode step, with one request through the
    scheduler whose prompt takes the full budget and then the tail bucket."""
    import numpy as np

    from repro.serving.request import Request

    buckets = sched.chunked.buckets
    prompt = np.arange(buckets[-1] + buckets[0] // 2, dtype=np.int32) % 997
    sched.submit(Request(rid=-1, prompt=prompt, max_new_tokens=3,
                         arrival_step=sched.step_count))
    while sched.num_pending:
        sched.step()
    sched.finished.clear()


def peak_bytes(devs) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_start: float = T_START, devs=None, keep=None) -> Dict:
    """One run of ``cell`` -> the result object.  ``devs`` are the devices
    the run reports; ``keep(result)`` sees the raw window and the weights
    (the knee sweep and the control read them)."""
    import jax

    from repro.distributed import sharding as sh
    from repro.launch.mesh import make_mesh
    from repro.serving.scheduler import Scheduler

    from bench import check, drive, loadgen, system

    devs = devs or jax.devices()
    gc.collect()  # what an earlier run in this process left behind
    clog = CompileLog(jax)
    cfg = system.model_config(cell)
    mesh = make_mesh(*cell.config["mesh"])
    rules = sh.rules_for_mesh(mesh)
    s = cell.serving
    weights = system.make_weights(cell, cfg, rules, seed)
    jax.block_until_ready(weights)
    layout = system.layout(cell, cfg)
    tap = drive.LogitTap(check.columns(cfg.vocab_size, seed))
    sched = Scheduler(weights, cfg, layout, rules, admission="chunked",
                      chunk_budget=int(s["chunk_budget"]), sample_fn=tap)
    planned = loadgen.plan(cell.traffic, seed, cfg.vocab_size,
                           float(s["warm_s"]), seconds, layout.max_seq)
    marks, traced = [], {}
    if trace:
        # the traced part ends the window, so that writing the trace out
        # happens after the window has closed
        trace_dir = ROOT / "bench" / ".traces" / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
        t_trace = max(0.0, seconds - float(s["trace_s"]))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans only, no call tracing

        def enter():
            # made after the profiler started, or it records nothing
            traced["span"] = jax.profiler.TraceAnnotation(drive.WINDOW_SPAN)
            traced["t0"] = time.perf_counter()
            traced["span"].__enter__()

        def leave():
            traced["span"].__exit__(None, None, None)
            traced["t1"] = time.perf_counter()

        def start():
            traced["start"] = time.perf_counter()
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)

        marks = [(t_trace - 0.5, start), (t_trace, enter), (seconds, leave)]
    with mesh:
        warm_programs(sched)
        result = drive.run_traffic(sched, planned, float(s["warm_s"]),
                                   seconds, marks)
    if trace:
        if "t1" not in traced:
            leave()
        jax.profiler.stop_trace()
    if keep is not None:
        keep(dict(result, weights=weights))
    t_open, t_close = result["open"], result["close"]
    window = drive.summarize(result)
    if trace:  # the profiler's start holds the loop: not the system's time
        skip = (traced["start"], traced["t0"])
        window["ttft_p90_s"] = drive.ttft_p90_s(result, skip)
        window["sender_lag_p99_ms"] = drive.sender_lag_p99_ms(result, skip)
    window["compiles_in_window"] = clog.between(t_open, t_close)
    window["setup_s"] = t_open - t_start
    steps = [st for st in result["steps"] if t_open <= st.t0 < t_close]
    peak = peak_bytes(devs[:cell.chips])
    # free the program's state before the reference runs: the requests'
    # stream hooks reach the server and through it the scheduler and cache
    for rec in result["records"]:
        if rec.request is not None:
            rec.request.on_token = rec.request.on_finish = None
    del sched
    gc.collect()
    reduced = None
    if trace:
        from bench import trace as tr

        reduced = tr.reduce(tr.find_xplane(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
    seen = {}
    checks = check.compare(cell, weights, result["records"], seed, window,
                           seen)
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": int(peak)}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": window["attempted"], "failed": window["failed"]}
    if trace:
        from bench import trace as tr
        from bench.spec import CODE, load_module

        t0, t1 = traced["t0"], traced["t1"]
        readings = Readings(
            window=window, steps=steps,
            traced_steps=[st for st in steps if t0 <= st.t0 < t1],
            trace=reduced, conf=cell.config,
            counts=load_module(CODE / "counts" /
                               f"{cell.config['reference']}.py"),
            kv_format=layout.kv_format, peaks=peaks_for(devs[0].device_kind),
            chips=cell.chips, peak_bytes=peak)
        metrics = {}
        for m in cell.per_layer():
            v = load_module(CODE / "metrics" / f"{m['name']}.py").read(
                readings)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        dev["busy_s"] = sum(d.busy_ns for d in reduced.devices) * 1e-9 / len(
            reduced.devices)
        dev["window_s"] = reduced.window_s
        out["breakdown"] = tr.breakdown(reduced)
    else:
        metrics = {m["name"]: {"value": float(window[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end()
                   if window.get(m["name"]) is not None}
    out["metrics"] = metrics
    out["device"] = dev
    out["window"] = {k: window[k] for k in ("seconds", "tokens", "gaps",
                                            "compiles_in_window")}
    out["reference"] = seen
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    opts = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.spec import load_cell

    cell = load_cell(opts.workload, ROOT)
    import jax

    devs = check_devices(jax, cell.chips)
    enable_compile_cache(jax)
    out = run_cell(cell, opts.seed, opts.seconds, bool(opts.trace),
                   devs=devs)
    print(f"[bench] compiles in the window: "
          f"{out['window']['compiles_in_window']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"[bench] check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
