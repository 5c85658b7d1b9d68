"""Sharding: device time of the collective ops (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all, and the ``-start`` /
``-done`` halves of their asynchronous forms, by ``trace.op_name``) over
the device's busy time, on the device where that share is largest."""

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def read(run):
    if run.trace is None:
        return None
    shares = [100.0 * sum(ns for name, ns in d.ops.items()
                          if name.startswith(COLLECTIVES)) / d.busy_ns
              for d in run.trace.devices if d.busy_ns]
    return max(shares) if shares else None
