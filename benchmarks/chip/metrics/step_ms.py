"""Median of the engine's ``step_s`` span over the window's steps: the
compiled step, ended by ``block_until_ready``."""
import numpy as np


def read(run: dict) -> float | None:
    spans = [r["step_s"] for r in run["steps"] if r["step_s"] is not None]
    return float(np.median(spans)) * 1e3 if spans else None
