"""Member-steps (6 forecast hours of one member) whose chunk event
reached the client inside the window, over the time from the window's
start to the last of those events.  Every member-step completed in the
window counts, with all the time it took; the tail after the last
completion holds only work still in flight (at full width one chunk,
two member-steps, takes several seconds, so a plain count over the
window would move in steps of a whole chunk)."""


def read(run: dict) -> float | None:
    steps = run["member_steps"]
    return steps / run["busy_to_s"] if steps else None
