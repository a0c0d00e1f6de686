"""``memory_stats()["peak_bytes_in_use"]`` after the window, fullest chip."""


def read(run: dict) -> float | None:
    peak = run["memory_peak_bytes"]
    return peak / 1e9 if peak else None
