"""Per-layer metrics, one module per metric, found by the metric's name.

Each exposes ``read(run) -> float | None``; ``run`` is the dict that
``bench.run`` assembles after a traced window (records, window bounds,
reduced trace, device, configuration, peaks).  A reader with nothing to
read returns None and the metric is left out of the result line.
"""
