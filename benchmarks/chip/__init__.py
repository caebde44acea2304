"""On-chip benchmark of decoder training: one cell = one model
configuration under one traffic mix, run once by ``run.py``.

Everything that belongs to one configuration, mix or per-layer metric is
a file found by its name (``configs/``, ``traffic/``, ``metrics/``,
``reference/``, ``limits/``); the code here is the general harness.
"""
