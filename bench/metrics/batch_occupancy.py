"""Scheduler: mean share of slots holding a request over the window's
steps (``Scheduler.occupancy``)."""


def read(run):
    occ = [s.occupancy for s in run.steps]
    return 100.0 * sum(occ) / len(occ) if occ else None
