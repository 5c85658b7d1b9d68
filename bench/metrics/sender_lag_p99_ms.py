"""Front door: how late the 99th-percentile client sent its request after
it was due.  The pump runs ``Scheduler.step()`` on the clients' event loop,
so this is how long a step holds arrivals back."""


def read(run):
    return run.window.get("sender_lag_p99_ms")
