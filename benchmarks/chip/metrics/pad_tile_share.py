"""Share of the 128x128 tiles the engine uploaded over the window that are
power-of-two padding (the ``pad_tiles`` and ``tiles`` arguments of its
``engine.upload`` spans)."""
import hostspans


def read(run: dict) -> float | None:
    win = hostspans.window(run)
    if win is None:
        return None
    tiles = sum(st["counters"].get("tiles", 0) for st in win["steps"])
    pad = sum(st["counters"].get("pad_tiles", 0) for st in win["steps"])
    return 100.0 * pad / tiles if tiles else None
