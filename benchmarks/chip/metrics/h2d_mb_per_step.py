"""Median per step of the bytes the program uploads, in MB: the engine's
layers and padded input and the device tier's table uploads (the
``h2d_bytes`` arguments of its ``engine.upload`` and
``tier.table_upload`` spans), over the steps that ran the engine."""
import numpy as np

import hostspans


def read(run: dict) -> float | None:
    win = hostspans.window(run)
    if win is None:
        return None
    mb = [st["counters"].get("h2d_bytes", 0) / 1e6 for st in win["steps"]
          if "engine.upload" in st["spans"]]
    return float(np.median(mb)) if mb else None
