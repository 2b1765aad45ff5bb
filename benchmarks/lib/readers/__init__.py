"""Metric readers. Each module has ``read(ctx, **args) -> float | None``:
``ctx`` is the run's ``harness.Collected``, ``args`` the ``args`` of the
metric's file under ``metrics/``. A reader that finds nothing to read
returns None and the metric is left out of the line."""
