"""Per-layer metric readers, one file each, found by the metric's name.

Each module has ``read(ctx) -> float | None``: the metric's value from
the traced run (``ctx`` is ``bench.run.TraceContext``), or None where
the trace holds nothing for it, in which case the harness leaves the
metric out of the result line.
"""
