"""Tests of the ``pad_input_share`` reader (``benchmarks/chip/metrics``)
and of its declaration in ``BENCHMARK.json``, on the CPU.

    PYTHONPATH=src python -m pytest -q tests/benchmark/test_pad_input_share.py

The reader sums the ``input_rows`` and ``pad_input_rows`` arguments of
the window's ``engine.upload`` spans; it is silent on no trace, on a
program without spans, on another run's trace, and on a program that
counts no input rows (one that pads its input on the host).
"""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import harness  # noqa: E402
import hostspans  # noqa: E402

RUN = {"window_s": 10.0, "steps": [{}, {}]}


def _program_spans():
    """A 10 s window of two steps. Step 1 uploads 100 tiles and 750 input
    rows (250 more padded on the device); step 2 uploads 100 tiles and
    900 input rows (100 padded)."""
    up1 = {"h2d_bytes": 2_000_000, "tiles": 100, "pad_tiles": 25,
           "input_rows": 750, "pad_input_rows": 250}
    up2 = {"h2d_bytes": 4_000_000, "tiles": 100, "pad_tiles": 15,
           "input_rows": 900, "pad_input_rows": 100}
    return [
        (0.0, 10.0, "bench.window", {}),
        (0.5, 4.5, "bench.step", {}), (5.0, 9.5, "bench.step", {}),
        (0.6, 4.4, "worker.step", {}),
        (2.0, 3.0, "engine.build", {}), (3.0, 3.8, "engine.upload", up1),
        (5.1, 9.4, "worker.step", {}),
        (6.0, 8.0, "engine.build", {}), (8.0, 9.0, "engine.upload", up2),
    ]


def _upload_spans(counters: list[dict]) -> list[tuple]:
    """A 10 s window of one step per entry of ``counters``, each with an
    ``engine.upload`` span carrying them."""
    out = [(0.0, 10.0, "bench.window", {})]
    for i, c in enumerate(counters):
        out += [(i + 0.5, i + 0.9, "bench.step", {}),
                (i + 0.6, i + 0.7, "engine.upload", c)]
    return out


def test_pad_input_share_on_synthetic_spans(monkeypatch):
    monkeypatch.setattr(hostspans, "load_events", _program_spans)
    assert harness.reader("pad_input_share")(RUN) == pytest.approx(17.5)


def test_pad_input_share_finds_nothing(monkeypatch):
    """No trace; a program without spans; a trace of another run (its
    window lasts otherwise, or holds another number of steps)."""
    read = harness.reader("pad_input_share")
    bench_only = [ev for ev in _program_spans()
                  if ev[2].startswith("bench.")]
    for events, r in ((None, RUN), (bench_only, RUN),
                      (_program_spans(), dict(RUN, window_s=12.0)),
                      (_program_spans(), dict(RUN, steps=[{}]))):
        monkeypatch.setattr(hostspans, "load_events", lambda e=events: e)
        assert read(r) is None


def test_pad_input_share_reads_the_upload_counters(monkeypatch):
    """The input's padding rows over all its rows, summed over the
    window's steps; silent where the upload spans count no input rows,
    as a program that pads its input on the host."""
    spans = _upload_spans([
        {"input_rows": 900, "pad_input_rows": 100, "tiles": 10},
        {"input_rows": 2700, "pad_input_rows": 1300, "tiles": 10}])
    monkeypatch.setattr(hostspans, "load_events", lambda: spans)
    assert harness.reader("pad_input_share")(RUN) == pytest.approx(28.0)
    host_padded = _upload_spans([{"h2d_bytes": 10, "tiles": 10}] * 2)
    monkeypatch.setattr(hostspans, "load_events", lambda: host_padded)
    assert harness.reader("pad_input_share")(RUN) is None


def test_pad_input_share_is_declared_for_both_cells():
    spec = harness.load_spec()
    m = {m["name"]: m for m in spec["per_layer"]}["pad_input_share"]
    assert m["workloads"] == [c["name"] for c in spec["workloads"]] == [
        "sage-reddit.paper-schedule", "pna-reddit.paper-schedule"]
    assert (m["unit"], m["better"], m["source"], m["moves"]) == (
        "%", "lower", "program_counter", "seeds_per_s")
    assert m["layer"] == (
        "host preparation (train/compute.ComputeEngine, prep_s)")
