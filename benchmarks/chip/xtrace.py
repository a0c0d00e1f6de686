"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Device operations are the events of the ``XLA Ops`` line of each device
plane (``/device:TPU:<n>``); on a TPU an event's name is the op's HLO
text, so a Pallas kernel is found by its function's name in it. The
window is the harness's own host span ``bench.window``. Busy time is the union of the device operations'
intervals inside the window, averaged over the devices that ran any; the
idle gaps between them are labelled by what the host was doing at their
midpoint: the innermost ``bench.*`` span and, where there is one, the
innermost other host event (``bench.step/<event>``).
"""
from __future__ import annotations

import glob
import heapq
import os

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


def find_xplane(log_dir: str) -> str | None:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def read_events(path: str) -> dict:
    """Device ops and host events of one trace, times in seconds.

    Returns ``{"devices": {plane: [(start, end, name), ...]},
    "host": [(start, end, name), ...]}``.
    """
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    for plane in prof.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    # an XLA op's event name is its HLO text: keep the
                    # instruction's name
                    ops.append((s, s + ev.duration_ns * 1e-9,
                                ev.name.split(" = ")[0]))
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    s = ev.start_ns * 1e-9
                    host.append((s, s + ev.duration_ns * 1e-9, ev.name))
    return {"devices": devices, "host": host}


def union(intervals: list[tuple], lo: float, hi: float) -> list[tuple]:
    """Merged ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    merged: list[list] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def gaps(busy: list[tuple], lo: float, hi: float) -> list[tuple]:
    """The idle ``(start, end)`` stretches of ``[lo, hi]`` between ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _innermost(events: list[tuple], times: list[float]) -> list:
    """For each of ``times``, the name of the event ``(start, end, name)``
    covering it (``start <= t < end``) that started last, the first listed
    on a tie; ``None`` where none covers it. One sweep over the times in
    order, the events that have started on a heap by their start."""
    order = sorted(range(len(events)), key=lambda i: events[i][0])
    out: list = [None] * len(times)
    heap: list = []
    k = 0
    for q in sorted(range(len(times)), key=lambda j: times[j]):
        t = times[q]
        while k < len(order) and events[order[k]][0] <= t:
            heapq.heappush(heap, (-events[order[k]][0], order[k]))
            k += 1
        # an event that has ended before t has ended for every later time
        while heap and events[heap[0][1]][1] <= t:
            heapq.heappop(heap)
        if heap:
            out[q] = events[heap[0][1]][2]
    return out


def _labels(gaps_: list[tuple], host: list[tuple]) -> list[str]:
    """What the host was doing at each gap's midpoint: the innermost bench
    span and, where there is one, the innermost other host event."""
    mids = [(s + e) / 2 for s, e in gaps_]
    spans = [h for h in host if h[2].startswith(SPAN_PREFIX)
             and h[2] != WINDOW_SPAN]
    others = [h for h in host if not h[2].startswith(SPAN_PREFIX)]
    out = []
    for span, other in zip(_innermost(spans, mids), _innermost(others, mids)):
        label = "host" if span is None else span
        out.append(label if other is None else f"{label}/{other}")
    return out


def reduce(events: dict) -> dict | None:
    """Window, busy time, per-op device time and labelled idle gaps.

    ``None`` when the trace holds no device operation.
    """
    devices, host = events["devices"], events["host"]
    if not devices:
        return None
    windows = [h for h in host if h[2] == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0][0], windows[0][1]
    else:
        every = [op for ops in devices.values() for op in ops]
        lo, hi = min(o[0] for o in every), max(o[1] for o in every)
    per_op: dict = {}
    busy_total = 0.0
    all_gaps: list[tuple] = []
    for ops in devices.values():
        inside = [o for o in ops if o[1] > lo and o[0] < hi]
        busy = union([(o[0], o[1]) for o in inside], lo, hi)
        busy_total += sum(e - s for s, e in busy)
        all_gaps += gaps(busy, lo, hi)
        for s, e, name in inside:
            rec = per_op.setdefault(name, [0.0, 0])
            rec[0] += min(e, hi) - max(s, lo)
            rec[1] += 1
    gap_sums: dict = {}
    for (s, e), label in zip(all_gaps, _labels(all_gaps, host)):
        gap_sums[label] = gap_sums.get(label, 0.0) + (e - s)
    return {
        "window_s": hi - lo,
        "busy_s": busy_total / len(devices),
        "n_devices": len(devices),
        "ops": per_op,
        "gaps": sorted(gap_sums.items(), key=lambda kv: -kv[1]),
    }


def kernel_seconds(trace: dict | None, pattern: str) -> tuple[float, int]:
    """Device seconds and event count of the ops whose name contains
    ``pattern``, per device."""
    if not trace:
        return 0.0, 0
    secs, n = 0.0, 0
    for name, (sec, count) in trace["ops"].items():
        if pattern in name:
            secs += sec
            n += count
    return secs / trace["n_devices"], n // trace["n_devices"]


def breakdown(trace: dict) -> dict:
    """The ``breakdown`` of a traced run's result line."""
    ops = sorted(trace["ops"].items(), key=lambda kv: -kv[1][0])[:TOP]
    return {
        "device_ops": [[name, rec[0] / trace["n_devices"]]
                       for name, rec in ops],
        "idle_gaps": [[label, secs / trace["n_devices"]]
                      for label, secs in trace["gaps"][:TOP]],
    }
