"""Tests of the readers of the program's host spans (``benchmarks/chip``
``hostspans.py`` and the metrics that use it), on the CPU at small sizes.

    PYTHONPATH=src python -m pytest -q tests/benchmark/test_hostspans.py

Each reader on synthetic spans, and on no trace, a program without spans
or another run's trace; and a traced tiny run, in which the program's
spans must be host events of the ``.xplane.pb`` on the profiler's clock.
"""
from __future__ import annotations

import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
DATA = os.path.join(HERE, "data")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import harness  # noqa: E402
import hostspans  # noqa: E402
import xtrace  # noqa: E402


# ------------------------------------------------------------ synthetic
def _program_spans():
    """A 10 s window of two steps. Step 1 rebuilds, uploads the tier's
    table and 100 tiles (25 of them padding); step 2 uploads 100 tiles
    (15 padding). Seconds: build 1 and 2, upload 0.8 and 1, features
    0.2 + resolve 0.2 and 0.2, rebuild 0.5 (inside a nested decide)."""
    up1 = {"h2d_bytes": 2_000_000, "tiles": 100, "pad_tiles": 25}
    up2 = {"h2d_bytes": 4_000_000, "tiles": 100, "pad_tiles": 15}
    return [
        (0.0, 10.0, "bench.window", {}),
        (0.5, 4.5, "bench.step", {}), (5.0, 9.5, "bench.step", {}),
        (0.6, 4.4, "worker.step", {}),
        (0.7, 1.2, "worker.rebuild", {}), (0.8, 0.9, "worker.decide", {}),
        (1.3, 1.5, "worker.features", {}),
        (1.35, 1.4, "tier.table_upload", {"h2d_bytes": 1_000_000}),
        (1.6, 1.8, "worker.resolve", {}),
        (2.0, 3.0, "engine.build", {}), (3.0, 3.8, "engine.upload", up1),
        (5.1, 9.4, "worker.step", {}),
        (5.2, 5.4, "worker.features", {}),
        (6.0, 8.0, "engine.build", {}), (8.0, 9.0, "engine.upload", up2),
    ]


SPAN_READINGS = {"tile_build_ms": 1500.0, "upload_ms": 900.0,
                 "h2d_mb_per_step": 3.5, "pad_tile_share": 20.0,
                 "features_ms": 300.0, "rebuild_ms": 250.0}


@pytest.mark.parametrize("name", sorted(SPAN_READINGS))
def test_span_reader_on_synthetic_spans(monkeypatch, name):
    monkeypatch.setattr(hostspans, "load_events", _program_spans)
    run = {"window_s": 10.0, "steps": [{}, {}]}
    assert harness.reader(name)(run) == pytest.approx(SPAN_READINGS[name])


@pytest.mark.parametrize("name", sorted(SPAN_READINGS))
def test_span_reader_finds_nothing(monkeypatch, name):
    """No trace; a program without spans; a trace of another run (its
    window lasts otherwise, or holds another number of steps)."""
    read = harness.reader(name)
    run = {"window_s": 10.0, "steps": [{}, {}]}
    bench_only = [ev for ev in _program_spans()
                  if ev[2].startswith("bench.")]
    for events, r in ((None, run), (bench_only, run),
                      (_program_spans(), dict(run, window_s=12.0)),
                      (_program_spans(), dict(run, steps=[{}]))):
        monkeypatch.setattr(hostspans, "load_events", lambda e=events: e)
        assert read(r) is None


# ------------------------------------------------------------ traced run
def _tiny_plan():
    with open(os.path.join(DATA, "tiny.json")) as f:
        config = json.load(f)
    with open(os.path.join(DATA, "tiny-traffic.json")) as f:
        traffic = json.load(f)
    spec = harness.load_spec()
    metrics = spec["end_to_end"] + [
        m for m in spec["per_layer"] if m["source"] != "device_trace"]
    return {"cell": {"name": "tiny", "chips": 1}, "config": config,
            "traffic": traffic, "metrics": metrics}


@pytest.fixture(scope="module")
def tiny_cache(tmp_path_factory):
    """The tiny configuration's fixtures, made once for the module in a
    temporary directory; the policy artifacts, ``REPRO_ARTIFACTS`` and
    the compile cache that a run sets are restored afterwards, so that no
    other test sees them."""
    import jax

    import fixtures
    from repro.launch import compile_cache
    from repro.train import policy

    tmp = tmp_path_factory.mktemp("bench_cache")
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fixtures, "CACHE_ROOT", str(tmp))
        mp.setattr(policy, "ARTIFACT_DIR", str(tmp / "artifacts"))
        mp.setenv("REPRO_ARTIFACTS", str(tmp / "artifacts"))
        mp.setattr(compile_cache, "enable_compile_cache", lambda: "off")
        yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)


def test_traced_run_shares_the_profilers_clock(tiny_cache, monkeypatch,
                                              tmp_path):
    """A traced run: the program's spans are host events of the
    ``.xplane.pb``, each inside a ``bench.step`` and as long as the
    worker's recorder measured it; the span readers find them."""
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    workers = []
    build = harness.build_worker

    def keep(*a, **k):
        workers.append(build(*a, **k))
        return workers[-1]

    monkeypatch.setattr(harness, "build_worker", keep)
    out = harness.run(_tiny_plan(), 7, 0.0, True, time.perf_counter(),
                      require_chip=False)
    assert out["correct"] is True, out["checks"]
    assert set(SPAN_READINGS) <= set(out["metrics"])
    events = hostspans.host_events(xtrace.find_xplane(harness.TRACE_DIR))
    steps = [ev for ev in events if ev[2] == "bench.step"]
    records = workers[0][0].spans.records
    for name in ("engine.build", "engine.upload", "engine.run",
                 "worker.features"):
        traced = sorted((ev for ev in events if ev[2] == name),
                        key=lambda ev: ev[0])
        recorded = [r for r in records if r.name == name]
        assert traced and len(traced) == len(recorded), name
        for ev, rec in zip(traced, recorded):
            assert any(s <= ev[0] and ev[1] <= e for s, e, *_ in steps)
            want = (rec.end_ns - rec.start_ns) * 1e-9
            assert abs((ev[1] - ev[0]) - want) <= max(2e-3, 0.05 * want)
