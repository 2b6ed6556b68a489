"""End-to-end metrics, one module per metric, found by the metric's
name; each exposes ``read(run) -> float | None`` (see bench.run)."""
