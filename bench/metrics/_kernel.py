"""Roofline share of one Pallas kernel family over a traced window."""

from __future__ import annotations

from bench import tracereduce


def roofline(run: dict, op: str, family: str) -> float | None:
    """The least time the algorithm's work of ``family`` (a kernel family
    of the configuration's counts, ``run["counts"].step_calls``) needs
    on this chip -- per call the larger of its compute and memory bounds
    at the served precision's width, summed -- for the member-steps
    completed in the window, over the device time of the kernel's events
    from the window's start to the last of those completions (the
    stretch that ``member_steps_per_s`` divides by), as a percentage.
    None without a trace or events."""
    trace = run.get("trace")
    if trace is None or not run["member_steps"]:
        return None
    secs, n = tracereduce.kernel_seconds(trace, op, run["busy_to_s"])
    if n == 0 or secs <= 0:
        return None
    calls = run["counts"].step_calls(run["model"],
                                     run["value_bytes"])[family]
    t_min = sum(w.min_seconds(run["peaks"])[0] * n for w, n in calls)
    return 100.0 * t_min * run["member_steps"] / secs
