"""Host-clock span recorder (``repro.obs.wall``): nesting, self time, the
step identifier, the bounded buffer, and that a recorder that is off
records nothing and reads no clock."""
import threading

import pytest

from repro.obs import wall
from repro.obs.wall import NULL_SPANS, NO_SPAN, SpanRecorder


@pytest.fixture
def ticks(monkeypatch):
    """A host clock that advances 10 ns per read."""
    state = {"t": 0}

    def clock():
        state["t"] += 10
        return state["t"]

    monkeypatch.setattr(wall, "_clock", clock)
    return state


def _by_name(rec):
    return {r.name: r for r in rec.records}


def test_parent_nesting_and_self_time(ticks):
    rec = SpanRecorder(rank=2, enabled=True)
    with rec.span("outer"):
        with rec.span("mid"):
            with rec.span("leaf"):
                pass
        with rec.span("mid2"):
            pass
    got = _by_name(rec)
    assert [r.name for r in rec.records] == ["leaf", "mid", "mid2", "outer"]
    assert got["outer"].parent is None
    assert got["mid"].parent == "outer" and got["mid2"].parent == "outer"
    assert got["leaf"].parent == "mid"
    for r in rec.records:
        assert r.start_ns < r.end_ns and r.rank == 2
    dur = {k: r.end_ns - r.start_ns for k, r in got.items()}
    assert got["leaf"].self_ns == dur["leaf"]
    assert got["mid"].self_ns == dur["mid"] - dur["leaf"]
    assert got["outer"].self_ns == dur["outer"] - dur["mid"] - dur["mid2"]
    count, total, own = rec.totals()["outer"]
    assert (count, total, own) == (1, dur["outer"], got["outer"].self_ns)


def test_step_identifier_and_arguments(ticks):
    rec = SpanRecorder(enabled=True)
    rec.at(3, -1)
    with rec.span("a"):
        pass
    rec.at(3, 7)
    with rec.span("b", h2d_bytes=5) as sp:
        sp.note(d2h_bytes=6)
    a, b = rec.records
    assert (a.epoch, a.step) == (3, -1)
    assert (b.epoch, b.step) == (3, 7)
    assert b.args == {"h2d_bytes": 5, "d2h_bytes": 6}


def test_bounded_buffer_keeps_totals(ticks):
    rec = SpanRecorder(enabled=True, capacity=3)
    for _ in range(5):
        with rec.span("x"):
            pass
    assert len(rec.records) == 3
    assert rec.totals()["x"][0] == 5
    assert rec.totals()["x"][1] == 5 * 10


def test_off_records_nothing_and_reads_no_clock(monkeypatch):
    def no_clock():
        raise AssertionError("a recorder that is off read the clock")

    monkeypatch.setattr(wall, "_clock", no_clock)
    rec = SpanRecorder()
    for r in (rec, NULL_SPANS):
        sp = r.span("x", h2d_bytes=1)
        assert sp is NO_SPAN
        with sp as inner:
            inner.note(d2h_bytes=2)
    assert not rec.records and rec.totals() == {}


def test_follows_the_profiler(ticks, monkeypatch):
    """While a profiler trace is being taken, a recorder that is not
    enabled records; the null recorder never does."""
    monkeypatch.setattr(wall, "_profiling", lambda: True)
    rec = SpanRecorder()
    with rec.span("x"):
        pass
    assert [r.name for r in rec.records] == ["x"]
    assert NULL_SPANS.span("x") is NO_SPAN


def test_threads_keep_their_own_parents(ticks):
    rec = SpanRecorder(enabled=True)

    def other():
        with rec.span("builder"):
            pass

    with rec.span("main"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert _by_name(rec)["builder"].parent is None
