"""Share of the neighbour-table slots the engine uploaded over the window
that belong to padded or neighbourless destinations (the
``pad_msg_slots`` and ``msg_slots`` arguments of its ``engine.upload``
spans). A run whose program builds no neighbour table has no reading."""
import hostspans


def read(run: dict) -> float | None:
    win = hostspans.window(run)
    if win is None:
        return None
    slots = sum(st["counters"].get("msg_slots", 0) for st in win["steps"])
    pad = sum(st["counters"].get("pad_msg_slots", 0) for st in win["steps"])
    return 100.0 * pad / slots if slots else None
