"""Peak device memory in use (``memory_stats()["peak_bytes_in_use"]``)
after the window, in GB."""


def read(run: dict) -> float | None:
    peak = run["device"].get("memory_peak_bytes")
    return None if not peak else peak / 1e9
