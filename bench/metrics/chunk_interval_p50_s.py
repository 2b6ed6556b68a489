"""Median time between consecutive chunks of one rollout, as the engine
reports it (``chunk_s`` of each chunk event received in the window)."""

import statistics


def read(run: dict) -> float | None:
    vals = [ev["chunk_s"] for rec in run["records"]
            for t, ev in rec["events"]
            if ev.get("event") == "chunk" and "chunk_s" in ev
            and run["t0"] <= t <= run["t1"]]
    return statistics.median(vals) if vals else None
