"""95th percentile of ``worker.step``'s wall time over the window's steps
(host clock; linear interpolation between order statistics)."""
import numpy as np


def read(run: dict) -> float | None:
    walls = [r["wall_s"] for r in run["steps"]]
    return float(np.percentile(walls, 95)) if walls else None
