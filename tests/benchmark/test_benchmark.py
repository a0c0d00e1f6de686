"""Tests of the chip benchmark (``benchmarks/chip``), on the CPU at small
sizes.

    PYTHONPATH=src python -m pytest -q tests/benchmark

The trace reduction on a synthetic trace and on a trace recorded on the
chip, GraphSAGE's work functions against hand counts, discovery of cells,
configurations and metrics by name, the command's refusal without a chip,
and the correctness check: a sound run passes, and the control, the
faults a training cell can have and a faulty sampler are caught.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
DATA = os.path.join(HERE, "data")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import harness  # noqa: E402
import reference  # noqa: E402
import work  # noqa: E402
import xtrace  # noqa: E402

PEAK = work.PEAKS["TPU v5 lite"]


# ------------------------------------------------------------ trace
def _synthetic():
    # device busy [1,2) and [1.5,3) overlap; [5,6) lies in a step; the
    # window is [0, 8); one op outside the window is ignored
    host = [(0.0, 8.0, "bench.window"), (0.5, 4.0, "bench.step"),
            (3.2, 3.9, "TransferToDevice"), (4.0, 7.0, "bench.step"),
            (9.0, 9.5, "bench.end_epoch")]
    ops = [(1.0, 2.0, "%block_spmm_kernel.4"),
           (1.5, 3.0, "%block_spmm_kernel.4"),
           (5.0, 6.0, "gather.1"),
           (8.5, 9.0, "late")]
    return {"devices": {"/device:TPU:0": ops}, "host": host}


def test_union_and_gaps():
    busy = xtrace.union([(1.0, 2.0), (1.5, 3.0), (5.0, 6.0), (-1, 0.5)],
                        0.0, 8.0)
    assert busy == [(0.0, 0.5), (1.0, 3.0), (5.0, 6.0)]
    assert xtrace.gaps(busy, 0.0, 8.0) == [(0.5, 1.0), (3.0, 5.0),
                                            (6.0, 8.0)]


def test_reduce_synthetic():
    tr = xtrace.reduce(_synthetic())
    assert tr["window_s"] == 8.0
    assert tr["busy_s"] == pytest.approx(3.0)   # [1,3) and [5,6)
    assert tr["ops"]["%block_spmm_kernel.4"] == [2.5, 2]
    assert "late" not in tr["ops"]
    assert xtrace.kernel_seconds(tr, "block_spmm_kernel") == (2.5, 2)
    gaps = dict(tr["gaps"])
    # [0,1) in the first step; [3,5): midpoint 4.0 opens the second step;
    # [6,8): midpoint 7.0 is outside both steps
    assert gaps["bench.step"] == pytest.approx(3.0)
    assert gaps["host"] == pytest.approx(2.0)
    bd = xtrace.breakdown(tr)
    assert bd["device_ops"][0] == ["%block_spmm_kernel.4", 2.5]
    assert len(bd["idle_gaps"]) <= xtrace.TOP


def test_reduce_recorded_chip_trace():
    """A window of ``sage-reddit.paper-schedule`` traced on one TPU v5e:
    one step whose device time is the compiled step and the tier gather,
    the rest of the ~30 s waiting on the host's tile upload."""
    tr = xtrace.reduce(xtrace.read_events(
        os.path.join(DATA, "reddit_window.xplane.pb")))
    assert tr["n_devices"] == 1
    assert tr["window_s"] == pytest.approx(30.553, abs=1e-3)
    assert tr["busy_s"] == pytest.approx(0.1452, abs=1e-3)
    # layer-0 and layer-1 forward, layer-1 transposed
    assert xtrace.kernel_seconds(tr, "block_spmm_kernel")[1] == 3
    assert xtrace.kernel_seconds(tr, "gather_rows_kernel")[1] == 1
    bd = xtrace.breakdown(tr)
    assert bd["device_ops"][0][0].startswith("%jvp_jit_block_spmm_kernel")
    assert bd["idle_gaps"][0][0] == "bench.step/MapDmaBuffer"
    assert bd["idle_gaps"][0][1] > 25.0


def _label_by_scan(t, host):
    """The gap label at ``t`` by a plain scan over every host event."""
    covering = [h for h in host if h[0] <= t < h[1]]
    spans = [h for h in covering if h[2].startswith("bench.")
             and h[2] != "bench.window"]
    others = [h for h in covering if not h[2].startswith("bench.")]
    label = max(spans, key=lambda h: h[0])[2] if spans else "host"
    if others:
        label += "/" + max(others, key=lambda h: h[0])[2]
    return label


@pytest.mark.parametrize("seed", range(4))
def test_gap_labels_match_a_plain_scan(seed):
    """The sweep that labels idle gaps gives what a scan over every host
    event gives, ties in start and nested, equal and empty events
    included."""
    rng = np.random.default_rng(seed)
    names = ["bench.step", "bench.window", "bench.x", "a", "b", "c"]
    host = []
    for _ in range(200):
        s = float(rng.integers(0, 40)) / 4 if rng.random() < 0.5 \
            else float(rng.random() * 10)
        host.append((s, s + float(rng.choice([0.0, 0.25, rng.random()])),
                     str(rng.choice(names))))
    gaps_ = [(float(a), float(a + rng.random()))
             for a in rng.random(300) * 10]
    gaps_ += [(h[0], h[0]) for h in host[:20]] + [(h[1], h[1])
                                                  for h in host[:20]]
    assert xtrace._labels(gaps_, host) == [
        _label_by_scan((s + e) / 2, host) for s, e in gaps_]


def test_reduce_without_device_ops():
    assert xtrace.reduce({"devices": {}, "host": []}) is None


def test_idle_share_reader():
    run = {"trace": xtrace.reduce(_synthetic())}
    assert harness.reader("idle_share")(run) == pytest.approx(62.5)
    assert harness.reader("idle_share")({"trace": None}) is None


# ------------------------------------------------------------ work
LAYERS = [{"n_src": 10, "n_dst": 4, "n_edges": 12},
          {"n_src": 4, "n_dst": 2, "n_edges": 5}]
DIMS = [6, 3, 2]
# a GraphSAGE configuration of widths DIMS, as the model module reads it
HAND = {"name": "hand",
        "model": {"arch": "graphsage", "n_layers": 2, "d_hidden": DIMS[1],
                  "aggregator": "mean", "dtype": "float32",
                  "matmul_precision": "highest"},
        "graph": {"n_feat": DIMS[0], "n_classes": DIMS[2]},
        "training": {"fanouts": [10, 25]}}
SAGE = harness.model(HAND)


def test_model_flops_hand_count():
    # layer 0: agg 2*12*6=144, proj 2*2*4*6*3=288 (fwd and weight grads)
    # layer 1: agg 2*5*3=30, proj 2*2*2*3*2=48, three times, plus the
    # transposed aggregation 30
    want = (144 + 288 * 2) + (30 + 48 * 3 + 30)
    assert SAGE.model_flops(LAYERS, HAND) == want


def test_spmm_calls_hand_count():
    calls = SAGE.kernel_calls(LAYERS, HAND)["block_spmm_kernel"]
    assert [(c["layer"], c["pass"]) for c in calls] == [
        (0, "forward"), (1, "forward"), (1, "transposed")]
    assert calls[0]["flops"] == 2 * 12 * 6
    assert calls[0]["bytes"] == (10 + 4) * 6 * 4 + 2 * 12 * 4
    assert calls[2]["bytes"] == (4 + 2) * 3 * 4 + 2 * 5 * 4


def test_least_time_and_gather():
    assert work.gather_bytes(10, 8) == 10 * (2 * 8 * 4 + 4)
    t = work.least_time(PEAK["flops"], 1.0, PEAK)
    assert t == pytest.approx(1.0)
    t = work.least_time(0.0, PEAK["hbm_bw"] * 2, PEAK)
    assert t == pytest.approx(2.0)
    with pytest.raises(ValueError):
        work.peaks("no such chip")


def test_mfu_and_roofline_readers():
    steps = [{"layers": LAYERS, "seeds": 2, "rows_gathered": 10,
              "model_flops": SAGE.model_flops(LAYERS, HAND),
              "kernel_calls": SAGE.kernel_calls(LAYERS, HAND)}]
    tr = xtrace.reduce(_synthetic())
    run = {"steps": steps, "n_feat": DIMS[0], "peaks": PEAK,
           "window_s": 2.0, "trace": tr}
    mfu = harness.reader("mfu")(run)
    assert mfu == pytest.approx(
        100 * SAGE.model_flops(LAYERS, HAND) / 2.0 / PEAK["flops"])
    least = sum(work.least_time(c["flops"], c["bytes"], PEAK)
                for c in SAGE.kernel_calls(LAYERS, HAND)["block_spmm_kernel"])
    spmm = harness.reader("spmm_roofline")
    secs, n = xtrace.kernel_seconds(tr, spmm.__globals__["KERNEL"])
    assert (secs, n) == (2.5, 2)
    assert spmm(run) == pytest.approx(100 * least / secs)
    assert harness.reader("mfu")(dict(run, peaks=None)) is None
    assert spmm(dict(run, trace=None)) is None


# ------------------------------------------------------------ discovery
def test_discovery_by_name():
    spec = harness.load_spec()
    for cell in spec["workloads"]:
        for trace in (False, True):
            plan = harness.plan(spec, cell["name"], trace)
            assert plan["config"]["name"] == cell["config"]
            assert plan["traffic"]["scenario"]
            for m in plan["metrics"]:
                assert callable(harness.reader(m["name"]))
        e2e = {m["name"] for m in harness.metrics_for(spec, cell["name"],
                                                      False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_for(spec, cell["name"], True)
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(cfg["limits"]) == {"blocks", "x_rows", "loss",
                                      "grad_norm", "update_norm"}


def test_every_metric_file_is_named_in_the_spec():
    spec = harness.load_spec()
    named = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics"))
             if f.endswith(".py")}
    assert named == files


def test_command_refuses_without_a_chip():
    spec = harness.load_spec()
    cell = spec["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3", "--seconds", "10", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "{" not in proc.stdout


# ------------------------------------------------------------ correctness
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


def _run(seed=7):
    import time

    return harness.run(_tiny_plan(), seed, 0.0, False, time.perf_counter(),
                       require_chip=False)


def test_sound_run_is_correct(tiny_cache):
    out = _run()
    assert out["correct"] is True, out["checks"]
    assert list(out)[-1] == "checks"
    assert {"seeds_per_s", "energy_j_per_seed", "step_s_p95",
            "setup_s"} <= set(out["metrics"])
    assert out["attempted"] >= 1 and out["failed"] == 0


def _state_unchanged(monkeypatch):
    from repro.train.compute import ComputeEngine

    real = ComputeEngine.step

    def step(self, mb, x_in):
        before = (self.params, self.opt_state, self.error)
        t = real(self, mb, x_in)
        self.params, self.opt_state, self.error = before
        return t

    monkeypatch.setattr(ComputeEngine, "step", step)


def _half_batch(monkeypatch):
    import dataclasses

    from repro.train.compute import ComputeEngine

    real = ComputeEngine.step

    def step(self, mb, x_in):
        last = mb.blocks[-1]
        mask = np.asarray(last.dst_mask).copy()
        mask[len(mask) // 2:] = False
        half = dataclasses.replace(
            mb, blocks=mb.blocks[:-1] + [dataclasses.replace(
                last, dst_mask=mask)])
        return real(self, half, x_in)

    monkeypatch.setattr(ComputeEngine, "step", step)


def _altered_row(monkeypatch):
    from repro.store import DevicePayloadTier

    real = DevicePayloadTier.gather_slots

    def gather_slots(self, slot_idx):
        rows = real(self, slot_idx).copy()
        if len(rows):
            rows[0] += 1.0
        return rows

    monkeypatch.setattr(DevicePayloadTier, "gather_slots", gather_slots)


def _sampled(monkeypatch, alter):
    from repro.graph import sampling

    real = sampling.sample_blocks

    def sample_blocks(*args, **kw):
        mb = real(*args, **kw)
        alter(mb.blocks[0])
        return mb

    monkeypatch.setattr(sampling, "sample_blocks", sample_blocks)


def _edge_altered(monkeypatch):
    # the first edge runs from the destination to itself: no graph edge
    def alter(b):
        b.edge_src = b.edge_src.copy()
        b.edge_src[0] = b.dst_pos[b.edge_dst[0]]

    _sampled(monkeypatch, alter)


def _edge_dropped(monkeypatch):
    def alter(b):
        b.edge_mask = b.edge_mask.copy()
        b.edge_mask[0] = False

    _sampled(monkeypatch, alter)


@pytest.mark.parametrize("fault, caught_by", [
    (_state_unchanged, "grad_norm"), (_half_batch, "loss"),
    (_altered_row, "x_rows"), (_edge_altered, "blocks"),
    (_edge_dropped, "blocks")])
def test_fault_is_caught(tiny_cache, monkeypatch, fault, caught_by):
    fault(monkeypatch)
    out = _run()
    assert out["correct"] is False, out["checks"]
    c = out["checks"][caught_by]
    assert c["value"] > c["limit"], out["checks"]


def _chain():
    """Two layers over the graph 1->0, 2->0, 3->1 (and nothing into 2, 3),
    fan-outs 2 at the seeds and 1 below, seeds [0]."""
    from repro.graph.sampling import Block, MiniBatch

    def blk(src, dst, es, ed, pos):
        src, dst = np.array(src), np.array(dst)
        return Block(src_nodes=src, dst_nodes=dst, edge_src=np.array(es),
                     edge_dst=np.array(ed), edge_mask=np.ones(len(es), bool),
                     src_mask=np.ones(len(src), bool), dst_pos=np.array(pos),
                     dst_mask=np.ones(len(dst), bool))

    top = blk([0, 1, 2], [0], [1, 2], [0, 0], [0])
    # layer below: destinations 0, 1, 2; 2 has no in-neighbour
    low = blk([0, 1, 2, 3], [0, 1, 2], [1, 3], [0, 1], [0, 1, 2])
    mb = MiniBatch(blocks=[low, top], input_nodes=low.src_nodes,
                   input_mask=low.src_mask, seeds=np.array([0]),
                   seed_mask=np.ones(1, bool))
    indptr = np.array([0, 2, 3, 3, 3])
    indices = np.array([1, 2, 3])
    return mb, indptr, reference.edge_keys(indptr, indices)


def test_block_faults_hand_cases():
    mb, indptr, keys = _chain()
    assert reference.block_faults(mb, indptr, keys, [2, 1]) == 0
    # fan-outs read output layer first: swapped, 0 and 1 each draw wrong
    assert reference.block_faults(mb, indptr, keys, [1, 2]) == 3
    low, top = mb.blocks
    low.edge_src[1] = 2                  # 2 -> 1 is no edge
    assert reference.block_faults(mb, indptr, keys, [2, 1]) == 1
    low.edge_src[1] = 3
    top.dst_pos[0] = 1                   # seed 0 mapped onto node 1's row
    assert reference.block_faults(mb, indptr, keys, [2, 1]) == 1


def test_control_fails_a_limit(tiny_cache):
    """The reference at three bf16 passes, in the program's place, on
    three seeds: each fails at least one of the configuration's limits."""
    import readings

    plan = _tiny_plan()
    config, traffic = plan["config"], plan["traffic"]
    from repro.core.cost_model import CostModelParams

    import fixtures

    arrays = fixtures.graph_arrays(config, log=lambda m: None)
    graph = fixtures.program_graph(arrays)
    params = CostModelParams(**config["cost_model"])
    opt = config["training"]["optimizer"]
    mod = harness.model(config)
    for seed in (1, 2, 3):
        cfg = harness.program_config(config, traffic, seed, None, params)
        mbs = harness.presample(cfg, graph, arrays["owner"])
        batches = [reference.batch_arrays(mb, arrays["features"],
                                          arrays["labels"])
                   for mb in mbs[: harness.CHECK_STEPS]]
        p0 = mod.init_params(seed, config)
        ref = reference.train(mod.forward, p0, batches, opt)
        nums = reference.compare(
            readings.planted("control", mod.forward, p0, batches, opt), ref,
            p0, opt["b1"])
        nums.pop("update_leaf")
        assert any(v > config["limits"][k] for k, v in nums.items()), nums
