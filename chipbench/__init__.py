"""The benchmark the driver runs on the chip (BENCHMARK.json).

``run.py`` is the command.  Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a data file
of its own (``configs/``, ``traffic/``, ``metrics/``), found by name;
see README.md for how a later PR adds one.
"""
