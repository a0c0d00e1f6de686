"""The energy meter's joules over the window, per trained seed node.

One node's ``gpu_j + cpu_j`` (not times the number of nodes): the paper's
power model times the measured compute spans (``prep_s + step_s``), plus
the modeled network and rebuild work.
"""


def read(run: dict) -> float | None:
    seeds = sum(r["seeds"] for r in run["steps"])
    joules = sum(r["energy_j"] for r in run["steps"])
    return joules / seeds if seeds else None
