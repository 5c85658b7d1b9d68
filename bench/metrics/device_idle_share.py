"""Device: share of the traced window in which no op ran, on the device
that idles most."""


def read(run):
    if run.trace is None:
        return None
    busy = min(d.busy_ns for d in run.trace.devices) * 1e-9
    return 100.0 * (1.0 - busy / run.trace.window_s)
