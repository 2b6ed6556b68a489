"""Closed loop: ``clients`` callers, each sending its next request as
soon as its last one ends, until the window closes.

With ``finish_in_flight`` false a request still running at the close is
cut there (its stream is closed, which cancels the rollout); with true
the caller waits for it to end."""

from __future__ import annotations

import random
import threading
import time

from bench.loadgen.common import draw_spec, new_record


def run(send, traffic: dict, base_spec: dict, rng: random.Random,
        t0: float, seconds: float) -> list[dict]:
    t_end = t0 + seconds
    n = int(traffic["clients"])
    finish = bool(traffic.get("finish_in_flight", True))
    seeds = [rng.randrange(2**62) for _ in range(n)]
    records: list[list[dict]] = [[] for _ in range(n)]

    def client(i: int) -> None:
        crng = random.Random(seeds[i])
        while time.perf_counter() < t_end:
            rec = new_record(time.perf_counter(),
                             draw_spec(base_spec, traffic["draw"], crng))
            records[i].append(rec)
            send(rec, None if finish else t_end)

    threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                name=f"bench-client-{i}") for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [r for rs in records for r in rs]
