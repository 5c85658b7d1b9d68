"""Engine step: device time of one batched decode program
(``jit_serve_step``) in the trace, averaged over runs and devices."""

from bench.trace import SERVE_STEP


def read(run):
    if run.trace is None:
        return None
    ns = sum(d.module_ns(SERVE_STEP) for d in run.trace.devices)
    n = sum(d.module_calls(SERVE_STEP) for d in run.trace.devices)
    return ns * 1e-6 / n if n else None
