"""The program's own host spans in a traced run, step by step.

While the profiler runs, the program's span recorder (``repro.obs.wall``)
writes each of its spans into the trace as a host event on the device's
clock (``engine.build``, ``engine.upload``, ``worker.features``, ...), its
transfer counters as the event's arguments (``h2d_bytes``, ``d2h_bytes``,
``tiles``, ``pad_tiles``). The run record keeps no host event, so these
are read back from the ``.xplane.pb`` the run left in
``harness.TRACE_DIR``.

The trace is taken for the run only if its ``bench.window`` lasts the
run's ``window_s`` and holds as many ``bench.step`` spans as the run has
steps. Without a trace, with another run's, or with a program that
records no span, ``window`` returns ``None`` and so does every reader.
"""
from __future__ import annotations

import functools
import os

import numpy as np

import harness
import xtrace

PREFIXES = ("engine.", "worker.", "cache.", "tier.")
# the window span and the harness's clock around it differ by the
# annotation's own enter and exit
WINDOW_MATCH_S = 0.01


def host_events(path: str) -> list[tuple]:
    """``(start_s, end_s, name, {argument: value})`` of the harness's and
    the program's host spans in a trace."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if ev.duration_ns <= 0 or not name.startswith(
                        PREFIXES + (xtrace.SPAN_PREFIX,)):
                    continue
                s = ev.start_ns * 1e-9
                out.append((s, s + ev.duration_ns * 1e-9, name,
                            dict(ev.stats)))
    return out


@functools.lru_cache(maxsize=1)
def _events_of(path: str, mtime: float) -> list[tuple]:
    return host_events(path)


def load_events() -> list[tuple] | None:
    """The host spans of the newest trace under ``harness.TRACE_DIR``."""
    path = xtrace.find_xplane(harness.TRACE_DIR)
    return _events_of(path, os.path.getmtime(path)) if path else None


def _seconds(events: list[tuple], lo: float, hi: float) -> dict:
    """Per span name, the seconds of ``[lo, hi]`` its spans cover (a name
    nested in itself counts once)."""
    by_name: dict = {}
    for s, e, name, _ in events:
        by_name.setdefault(name, []).append((s, e))
    return {name: sum(e - s for s, e in xtrace.union(iv, lo, hi))
            for name, iv in by_name.items()}


def _counters(events: list[tuple]) -> dict:
    out: dict = {}
    for *_, args in events:
        for k, v in args.items():
            out[k] = out.get(k, 0) + v
    return out


def window(run: dict) -> dict | None:
    """``{"spans": {name: s}, "steps": [{"spans": ..., "counters": ...}]}``:
    the program's spans over the run's window and inside each of its
    ``bench.step`` spans, in order; ``None`` where there is nothing."""
    events = load_events()
    if not events:
        return None
    wins = [ev for ev in events if ev[2] == xtrace.WINDOW_SPAN]
    if len(wins) != 1:
        return None
    lo, hi = wins[0][:2]
    if abs((hi - lo) - run["window_s"]) > WINDOW_MATCH_S:
        return None
    inside = [ev for ev in events if lo <= ev[0] and ev[1] <= hi]
    steps = sorted((ev for ev in inside if ev[2] == "bench.step"),
                   key=lambda ev: ev[0])
    program = [ev for ev in inside if ev[2].startswith(PREFIXES)]
    if len(steps) != len(run["steps"]) or not program:
        return None
    per_step = []
    for s, e, *_ in steps:
        mine = [ev for ev in program if s <= ev[0] and ev[1] <= e]
        per_step.append({"spans": _seconds(mine, s, e),
                         "counters": _counters(mine)})
    return {"spans": _seconds(program, lo, hi), "steps": per_step}


def median_ms(run: dict, *names: str) -> float | None:
    """Median over the window's steps of the summed seconds of ``names``
    (steps with none of them left out), in ms."""
    win = window(run)
    if win is None:
        return None
    per = [sum(st["spans"].get(n, 0.0) for n in names)
           for st in win["steps"] if any(n in st["spans"] for n in names)]
    return float(np.median(per)) * 1e3 if per else None
