"""The program's ``worker.rebuild`` spans inside the window (controller
decision, rebuild plan, device tier load), in ms per window step: 0 where
no rebuild boundary fell in the window."""
import hostspans


def read(run: dict) -> float | None:
    win = hostspans.window(run)
    if win is None:
        return None
    return win["spans"].get("worker.rebuild", 0.0) * 1e3 / len(win["steps"])
