"""On-chip serving benchmark: cells found by name, traffic from data files,
a plain float32 reference, and per-layer metric readers (see ``run.py``)."""
