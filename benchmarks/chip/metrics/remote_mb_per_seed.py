"""The meter's remote bytes over the window (misses plus rebuild fetches),
in MB per trained seed node."""


def read(run: dict) -> float | None:
    seeds = sum(r["seeds"] for r in run["steps"])
    if not seeds:
        return None
    return sum(r["remote_bytes"] for r in run["steps"]) / 1e6 / seeds
