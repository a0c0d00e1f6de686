"""Median per step of the harness's clock around ``worker.step`` minus the
engine's two spans (``prep_s + step_s``): the controller, the cache probe,
the device-tier gather and its copy to the host, feature resolution."""
import numpy as np


def read(run: dict) -> float | None:
    host = [r["wall_s"] - r["prep_s"] - r["step_s"] for r in run["steps"]
            if r["prep_s"] is not None and r["step_s"] is not None]
    return float(np.median(host)) * 1e3 if host else None
