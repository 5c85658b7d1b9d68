"""Device: ``peak_bytes_in_use`` after the window, on the fullest device."""


def read(run):
    return run.peak_bytes / 2 ** 30 if run.peak_bytes else None
