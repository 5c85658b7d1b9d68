"""Engine step: share of the device's busy time spent in chunked prefill
(``jit_prefill_chunk``), over all devices."""

from bench.trace import PREFILL_CHUNK


def read(run):
    if run.trace is None:
        return None
    busy = sum(d.busy_ns for d in run.trace.devices)
    pre = sum(d.module_ns(PREFILL_CHUNK) for d in run.trace.devices)
    return 100.0 * pre / busy if busy else None
