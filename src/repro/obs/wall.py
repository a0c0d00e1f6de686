"""Host-clock spans of the measured training path.

greentrace (:mod:`repro.obs.tracer`) stamps its events on the simulator's
virtual clocks and never carries a host time. This recorder is its
host-clock counterpart: where a measured step's wall time goes (tile
build, upload, compiled step, cache probe, device gather, ...). The two
never mix: a span recorded here is never written into a ``Tracer``'s
events, and greentrace never reads these times.

A span is a context manager::

    with spans.span("engine.upload", h2d_bytes=n):
        ...

It is taken on the host's monotonic clock (``time.perf_counter_ns``),
never on an injectable clock such as ``ComputeEngine.clock``, and it opens
a ``jax.profiler.TraceAnnotation`` of the same name (its keyword arguments
become the annotation's arguments), so it lands in a profiler trace on
the device's clock. In memory each span is a :class:`SpanRecord`: its
parent (from a per-thread stack), its self time (its duration less its
children's), and the ``(rank, epoch, step)`` that identifies the training
step it belongs to. Records are kept in a bounded buffer; per-name totals
and counts are kept apart from it, so a reader can take deltas around a
step however long the run.

A recorder records while ``enabled`` is set, or while a profiler trace is
being taken: an operator who profiles the measured lane gets the spans in
the trace without a second switch. Otherwise ``span`` returns one shared
no-op object: it reads no clock and allocates nothing.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

_clock = time.perf_counter_ns
# True while a profiler trace is being taken (TraceMe is recording)
_profiling = TraceAnnotation.is_enabled


class SpanRecord(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: str | None
    rank: int
    epoch: int
    step: int
    self_ns: int
    args: dict


class _NoSpan:
    """The span of a recorder that is not recording."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def note(self, **args) -> None:
        pass


NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("_rec", "name", "args", "_ann", "_t0", "_child_ns",
                 "_parent")

    def __init__(self, rec: SpanRecorder, name: str, args: dict):
        self._rec, self.name, self.args = rec, name, args

    def note(self, **args) -> None:
        """Arguments known only inside the span (e.g. a copy's bytes)."""
        self.args.update(args)
        self._ann.set_metadata(**args)

    def __enter__(self):
        stack = self._rec._stack()
        self._parent = stack[-1] if stack else None
        stack.append(self)
        self._child_ns = 0
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self._t0 = _clock()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = _clock()
        self._ann.__exit__(*exc)
        self._rec._stack().pop()
        dur = t1 - self._t0
        if self._parent is not None:
            self._parent._child_ns += dur
        self._rec._record(self, t1, dur)
        return False


class SpanRecorder:
    """Per-rank host-clock span recorder (see the module docstring)."""

    def __init__(self, rank: int = 0, enabled: bool = False,
                 capacity: int = 65536):
        self.rank = int(rank)
        self.enabled = bool(enabled)
        self.epoch = -1
        self.step = -1
        self.records: collections.deque = collections.deque(maxlen=capacity)
        self.totals_ns: dict = {}
        self.self_ns: dict = {}
        self.counts: dict = {}
        self._local = threading.local()
        self._lock = threading.Lock()

    def at(self, epoch: int, step: int) -> None:
        """Tag the spans that follow with this training step."""
        self.epoch, self.step = epoch, step

    def span(self, name: str, **args):
        if not (self.enabled or _profiling()):
            return NO_SPAN
        return _Span(self, name, args)

    def totals(self) -> dict:
        """``{name: (count, total_ns, self_ns)}`` so far."""
        with self._lock:
            return {k: (self.counts[k], self.totals_ns[k], self.self_ns[k])
                    for k in self.counts}

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, sp: _Span, t1: int, dur: int) -> None:
        own = dur - sp._child_ns
        rec = SpanRecord(
            sp.name, sp._t0, t1,
            sp._parent.name if sp._parent is not None else None,
            self.rank, self.epoch, self.step, own, sp.args,
        )
        name = sp.name
        with self._lock:
            self.records.append(rec)
            self.counts[name] = self.counts.get(name, 0) + 1
            self.totals_ns[name] = self.totals_ns.get(name, 0) + dur
            self.self_ns[name] = self.self_ns.get(name, 0) + own


class NullSpans:
    """A recorder that never records: for objects built without a worker."""

    enabled = False
    rank = -1

    def at(self, epoch: int, step: int) -> None:
        pass

    def span(self, name: str, **args):
        return NO_SPAN

    def totals(self) -> dict:
        return {}


NULL_SPANS = NullSpans()
