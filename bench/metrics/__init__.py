"""One reader per per-layer metric, ``<metric>.py`` with ``read(run)``:
the metric's value, or None where the run holds nothing to read it from.
``run`` is the :class:`bench.run.Readings` of one traced run."""
