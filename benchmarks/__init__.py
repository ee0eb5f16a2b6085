"""The on-chip benchmark of ddls-tpu (BENCHMARK.json names this
directory under ``paths``): one command, ``run.py``, runs one cell once.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a data file found by the name BENCHMARK.json gives
it; ``paths/<path>.py`` drives the program from outside and
``sources/<kind>.py`` reads one kind of per-layer metric. PERF.md says
what each cell and metric is for.
"""
