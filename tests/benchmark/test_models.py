"""Tests of the benchmark's model modules (``benchmarks/chip/models``), on
the CPU at small sizes.

    PYTHONPATH=src python -m pytest -q tests/benchmark/test_models.py

Every module owes the same six functions; every configuration names a
model that has a module; an unknown model, or settings a module's
reference does not implement, stop ``harness.plan`` before any device
work. GraphSAGE's module reproduces bit for bit what the harness computed
before its model code moved there (``data/graphsage_pin.json``): the
weights, the reference's steps, the work counts and the readers over one
run record. Its weights fit the program's parameter tree.
"""
from __future__ import annotations

import copy
import hashlib
import json
import os
import sys
from types import SimpleNamespace

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

OWED = ("init_params", "forward", "program_options", "model_flops",
        "kernel_calls", "validate")
CONFIG_FILES = sorted(
    [c["file"] for c in harness.load_spec()["configs"]]
    + [os.path.relpath(os.path.join(DATA, "tiny.json"), ROOT)])


def _json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


PIN = _json(DATA, "graphsage_pin.json")
TINY = _json(DATA, "tiny.json")
REDDIT = _json(BENCH, "configs", "sage-reddit.json")


def _sha(tree) -> dict:
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): hashlib.sha256(
        np.ascontiguousarray(np.asarray(v)).tobytes()).hexdigest()
        for k, v in flat}


# ------------------------------------------------------------ contract
@pytest.mark.parametrize("arch", harness.known_models())
def test_module_owes_every_function(arch):
    mod = harness._load("models", arch)
    missing = [f for f in OWED if not callable(getattr(mod, f, None))]
    assert not missing, missing


@pytest.mark.parametrize("path", CONFIG_FILES)
def test_configuration_names_a_model(path):
    config = _json(ROOT, path)
    assert config["model"]["arch"] in harness.known_models()
    mod = harness.model(config)
    assert isinstance(mod.program_options(config), dict)


def _spec_with(tmp_path, config: dict) -> dict:
    """``BENCHMARK.json`` with its first configuration's file replaced by
    ``config``, written under ``tmp_path``."""
    spec = copy.deepcopy(harness.load_spec())
    path = tmp_path / "config.json"
    path.write_text(json.dumps(config))
    cell = spec["workloads"][0]
    for c in spec["configs"]:
        if c["name"] == cell["config"]:
            c["file"] = str(path)
    return spec


def _no_device(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("the plan touched the device")

    monkeypatch.setattr(harness, "device_info", refuse)


def test_unknown_model_stops_the_plan(tmp_path, monkeypatch):
    _no_device(monkeypatch)
    config = copy.deepcopy(REDDIT)
    config["model"]["arch"] = "no-such-model"
    spec = _spec_with(tmp_path, config)
    with pytest.raises(SystemExit) as e:
        harness.plan(spec, spec["workloads"][0]["name"], False)
    msg = str(e.value)
    assert "no-such-model" in msg
    assert all(arch in msg for arch in harness.known_models())


@pytest.mark.parametrize("key, value", [
    ("aggregator", "max"), ("dtype", "bfloat16"),
    ("matmul_precision", "default"), ("n_layers", 3)])
def test_unimplemented_settings_stop_the_plan(tmp_path, monkeypatch, key,
                                              value):
    _no_device(monkeypatch)
    config = copy.deepcopy(REDDIT)
    config["model"][key] = value
    spec = _spec_with(tmp_path, config)
    with pytest.raises(SystemExit) as e:
        harness.plan(spec, spec["workloads"][0]["name"], True)
    assert key in str(e.value)


# ------------------------------------------------------------ pin
SAGE = harness.model(TINY)


def synthetic_batches(features: int, classes: int) -> list[dict]:
    """Two batches as the reference reads them, drawn from a fixed seed
    and independent of the program's sampler: 40 input rows of 200
    nodes, 20 and then 6 destinations drawing 4 and 3 in-edges each."""
    rng = np.random.default_rng(0)
    n_nodes = 200
    x = rng.standard_normal((n_nodes, features)).astype(np.float32)
    labels = rng.integers(0, classes, n_nodes).astype(np.int32)
    out = []
    for _ in range(2):
        ids = rng.choice(n_nodes, 40, replace=False)
        blocks, n_src = [], len(ids)
        for nd, fan in ((20, 4), (6, 3)):
            blocks.append(SimpleNamespace(
                edge_src=rng.integers(0, n_src, nd * fan).astype(np.int64),
                edge_dst=np.repeat(np.arange(nd), fan),
                edge_mask=np.ones(nd * fan, bool),
                dst_nodes=ids[:nd], dst_pos=np.arange(nd),
                dst_mask=np.ones(nd, bool)))
            n_src = nd
        mb = SimpleNamespace(blocks=blocks, input_nodes=ids)
        out.append(reference.batch_arrays(mb, x, labels))
    return out


@pytest.mark.parametrize("seed", sorted(PIN["init_params"]))
def test_init_params_pinned(seed):
    assert _sha(SAGE.init_params(int(seed), TINY)) == PIN["init_params"][seed]


@pytest.mark.parametrize("control", [False, True])
def test_reference_steps_pinned(control):
    g = TINY["graph"]
    batches = synthetic_batches(g["n_feat"], g["n_classes"])
    seed = min(int(s) for s in PIN["init_params"])
    p0 = SAGE.init_params(seed, TINY)
    out = reference.train(SAGE.forward, p0, batches,
                          TINY["training"]["optimizer"], control=control)
    pin = PIN["train"]
    key = "control_losses" if control else "losses"
    assert [float(x).hex() for x in out["losses"]] == pin[key]
    if not control:
        assert _sha(out["grad"]) == pin["grad_sha256"]
        assert _sha(out["params"]) == pin["params_sha256"]


def test_work_counts_pinned():
    hand = PIN["hand"]
    d_in, d_hidden, n_classes = hand["dims"]
    config = dict(TINY, model=dict(TINY["model"], d_hidden=d_hidden),
                  graph=dict(TINY["graph"], n_feat=d_in,
                             n_classes=n_classes))
    layers = hand["layers"]
    assert SAGE.model_flops(layers, config) == hand["model_flops"]
    calls = SAGE.kernel_calls(layers, config)
    assert list(calls) == ["block_spmm_kernel"]
    assert calls["block_spmm_kernel"] == hand["spmm_calls"]


def _reddit_run(kernel_calls: bool = True) -> dict:
    """The pinned steps at sage-reddit's widths over the recorded window,
    as ``harness.run`` records them."""
    mod = harness.model(REDDIT)
    steps = []
    for r in PIN["readers"]["steps"]:
        steps.append(dict(
            r, model_flops=mod.model_flops(r["layers"], REDDIT),
            kernel_calls=(mod.kernel_calls(r["layers"], REDDIT)
                          if kernel_calls else {})))
    tr = xtrace.reduce(xtrace.read_events(
        os.path.join(DATA, "reddit_window.xplane.pb")))
    return {"steps": steps, "n_feat": REDDIT["graph"]["n_feat"],
            "peaks": work.PEAKS["TPU v5 lite"], "window_s": tr["window_s"],
            "trace": tr}


@pytest.mark.parametrize("name", sorted(PIN["readers"]["values"]))
def test_readers_pinned(name):
    value = harness.reader(name)(_reddit_run())
    assert float(value).hex() == PIN["readers"]["values"][name]


def test_spmm_roofline_silent_without_its_kernel():
    run = _reddit_run(kernel_calls=False)
    assert xtrace.kernel_seconds(run["trace"], "block_spmm_kernel")[1] > 0
    assert harness.reader("spmm_roofline")(run) is None
    assert harness.reader("mfu")(run) is not None


# ------------------------------------------------------------ layout
@pytest.fixture(scope="module")
def tiny_engine():
    """The program's ``ComputeEngine`` for the tiny configuration, over a
    graph at its widths (no partition or policy is needed)."""
    import fixtures
    from repro.core.cost_model import CostModelParams
    from repro.train.compute import ComputeEngine

    g = TINY["graph"]
    arr = fixtures.power_law_graph(
        n_nodes=g["n_nodes"], n_edges=g["n_edges"], n_feat=g["n_feat"],
        n_classes=g["n_classes"], n_communities=g["n_communities"],
        zipf_a=g["zipf_a"], intra_frac=g["intra_frac"], seed=0)
    indptr, indices = fixtures.to_csr(arr["src"], arr["dst"], g["n_nodes"])
    graph = fixtures.program_graph({
        "indptr": indptr, "indices": indices,
        "features": arr["features"], "labels": arr["labels"]})
    traffic = _json(DATA, "tiny-traffic.json")
    cfg = harness.program_config(TINY, traffic, 7, None,
                                 CostModelParams(**TINY["cost_model"]))
    return ComputeEngine(graph, cfg)


def test_inject_weights_takes_the_module_weights(tiny_engine):
    params0 = SAGE.init_params(7, TINY)
    harness.inject_weights(tiny_engine, params0, "graphsage")
    assert tiny_engine.params is params0


def test_inject_weights_refuses_another_layout(tiny_engine):
    wide = dict(TINY, model=dict(TINY["model"], d_hidden=32))
    with pytest.raises(RuntimeError, match="graphsage"):
        harness.inject_weights(tiny_engine, SAGE.init_params(7, wide),
                               "graphsage")
