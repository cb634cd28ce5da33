"""dslib's benchmark: the harness, its data files and its yardstick.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own that the harness finds by the name
``BENCHMARK.json`` gives it (``configs/``, ``traffic/``, ``metrics/``,
``readers/``, ``drivers/``, ``reference/``).  From the program the
benchmark takes only the public entry points and the counters of
``dislib_tpu.utils.profiling``.
"""
