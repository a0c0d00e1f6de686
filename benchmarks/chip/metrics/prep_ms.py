"""Median of the engine's ``prep_s`` span over the window's steps: host
build of the block layers, their upload (waited for) and the free."""
import numpy as np


def read(run: dict) -> float | None:
    spans = [r["prep_s"] for r in run["steps"] if r["prep_s"] is not None]
    return float(np.median(spans)) * 1e3 if spans else None
