"""Whole model step: the algorithm's operations per member-step (the
configuration's counts, ``run["counts"].member_step``) times the
member-steps completed in the window, over the time from the window's
start to the last of those completions (the stretch
``member_steps_per_s`` divides by) and the chip's peak operations per
second, as a percentage."""


def read(run: dict) -> float | None:
    if not run["member_steps"]:
        return None
    flops = (run["counts"].member_step(run["model"]).flops
             * run["member_steps"])
    return 100.0 * flops / run["busy_to_s"] / run["peaks"]["flops_per_s"]
