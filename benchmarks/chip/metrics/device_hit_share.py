"""Share of the remote rows the window's steps requested (the worker's
hits plus misses) that the device tier served (``TierStats.device_hits``)."""


def read(run: dict) -> float | None:
    asked = sum(r["remote_rows"] for r in run["steps"])
    if not asked:
        return None
    return 100.0 * sum(r["device_hits"] for r in run["steps"]) / asked
