"""Share of the step's input rows over the window that the device adds as
padding up to the input's bucket (the ``pad_input_rows`` and
``input_rows`` arguments of the engine's ``engine.upload`` spans). A run
whose program counts no input rows, as one that uploads its input padded
on the host, has no reading."""
import hostspans


def read(run: dict) -> float | None:
    win = hostspans.window(run)
    if win is None:
        return None
    real = sum(st["counters"].get("input_rows", 0) for st in win["steps"])
    pad = sum(st["counters"].get("pad_input_rows", 0) for st in win["steps"])
    return 100.0 * pad / (real + pad) if real + pad else None
