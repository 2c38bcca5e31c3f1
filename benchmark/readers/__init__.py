"""Readers of per-layer metrics, one file per kind. ``read(metric,
run)`` takes the metric's own file (merged with its entry in
``BENCHMARK.json``) and what the run saw: ``run["counters"]`` (engine
counters over the window and the engine's arguments), ``run["trace"]``
(a ``trace_reduce.Trace`` or None), ``run["memory"]``,
``run["harness"]`` (what the harness clocked), ``run["config"]``,
``run["traffic"]``, ``run["device_kind"]``, ``run["chips"]``. It
returns a number, or None where there is nothing to read."""
