"""The benchmark: one command, data files for configurations, traffic
and per-layer metrics, and the yardstick (generators, reference, FLOPs,
peaks, trace reduction). See ``PERF.md`` and ``BENCHMARK.json``."""
