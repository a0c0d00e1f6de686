"""Trained seed nodes over the window's wall-clock duration (host clock)."""


def read(run: dict) -> float | None:
    seeds = sum(r["seeds"] for r in run["steps"])
    return seeds / run["window_s"] if run["window_s"] > 0 else None
