"""Admission: the 90th-percentile time from a request's due time to its
first token at the client, over the requests planned for the window but
those due while a traced run's profiler starts.  A host that stands still
for seconds moves it by half and more, so it is read here and not held to
a bound end to end."""


def read(run):
    return run.window.get("ttft_p90_s")
