"""Share of the window in which no operation ran on the device (the
union of device op intervals is busy time), as a percentage."""

from bench import tracereduce


def read(run: dict) -> float | None:
    trace = run.get("trace")
    if trace is None or not trace.ops:
        return None
    return 100.0 * (1.0 - tracereduce.busy_s(trace) / trace.window_s)
