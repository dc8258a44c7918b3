"""Per-layer metric readers, found by name. A reader is a module with

    read(ctx, **args) -> float | None

``ctx`` is the run's ``harness.context.Context``; ``args`` come from the
metric's ``layer_metrics/<name>.json``. A reader that finds nothing to
read returns None and the harness leaves the metric out of the line.
"""
