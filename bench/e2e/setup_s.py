"""Set-up: service start (host plans, checkpoint restore, warm compile)
and the warm requests, up to the window's start.  Making the weights,
once per checkout, is the benchmark's data and is not counted."""


def read(run: dict) -> float:
    return run["setup_s"]
