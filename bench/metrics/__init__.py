"""Per-layer metric readers: ``<metric name>.py`` each defines
``read(ctx) -> float | None``; ``None`` when the run has nothing to read."""
