"""Tests of the benchmark's PNA module (``benchmarks/chip/models/pna.py``)
and of the two readers the ``pna-reddit`` cell adds (``fanout_agg_roofline``,
``pad_msg_share``), on the CPU at small sizes.

    PYTHONPATH=src python -m pytest -q tests/benchmark/test_pna.py

``validate`` refuses every setting its reference does not implement; the
weights have the program's parameter tree; the work counts equal a hand
count; the plain forward equals the program's own reference
(``repro.models.gnn.pna.apply_blocks``) on a synthetic batch; the readers
read their numbers and stay silent on a GraphSAGE run; and a tiny run of
the harness checks the program's PNA step, whose ``delta`` has to be the
configuration's.
"""
from __future__ import annotations

import copy
import json
import os
import sys
import time
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
import hostspans  # noqa: E402
import reference  # noqa: E402
import work  # noqa: E402
import xtrace  # noqa: E402


def _json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


TINY = _json(DATA, "tiny-pna.json")
REDDIT = _json(BENCH, "configs", "pna-reddit.json")
PNA = harness.model(REDDIT)
KERNEL = "fanout_aggregate_kernel"


# ------------------------------------------------------------ validate
@pytest.mark.parametrize("config", [TINY, REDDIT], ids=["tiny", "reddit"])
def test_validate_accepts_the_configurations(config):
    PNA.validate(config)
    assert PNA.program_options(config) == {"model": "pna"}


@pytest.mark.parametrize("key, value", [
    ("aggregators", ["mean", "max", "min"]),
    ("aggregators", ["max", "mean", "min", "std"]),
    ("scalers", ["identity", "amplification"]),
    ("delta", 2.5), ("dtype", "bfloat16"), ("matmul_precision", "high"),
    ("n_layers", 3)])
def test_validate_refuses_each_wrong_setting(key, value):
    config = copy.deepcopy(REDDIT)
    config["model"][key] = value
    with pytest.raises(ValueError, match=key):
        PNA.validate(config)
    with pytest.raises(SystemExit, match=key):
        harness.model(config)


def test_delta_is_the_fixture_graphs():
    """The pinned ``delta`` is the mean ``log(d+1)`` of the fixture
    graph's in-degrees, stated as the configuration states it."""
    assert REDDIT["model"]["delta"] == PNA.DELTA
    assert repr(PNA.DELTA) in REDDIT["assumed"]["delta"]


# ------------------------------------------------------------ weights
def _program_cfg(config: dict, delta: float = PNA.DELTA):
    from repro.models.gnn import pna

    m, g = config["model"], config["graph"]
    return pna.PNAConfig(d_in=g["n_feat"], d_hidden=m["d_hidden"],
                         n_classes=g["n_classes"], n_layers=m["n_layers"],
                         delta=delta)


@pytest.mark.parametrize("config", [TINY, REDDIT], ids=["tiny", "reddit"])
def test_init_params_has_the_programs_tree(config):
    import jax

    from repro.models.gnn import pna

    own, _ = pna.init(jax.random.PRNGKey(0), _program_cfg(config),
                      abstract=True)
    ours = jax.eval_shape(lambda: PNA.init_params(7, config))
    assert jax.tree.structure(own) == jax.tree.structure(ours)
    for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(ours)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_init_params_values():
    """Glorot-uniform weights, zero biases, LayerNorm gain 1 and bias 0,
    the same for the same seed and not for another."""
    import jax

    p = PNA.init_params(7, TINY)
    flat = {jax.tree_util.keystr(k): np.asarray(v) for k, v in
            jax.tree_util.tree_flatten_with_path(p)[0]}
    for name, v in flat.items():
        if name.endswith("['ln_g']"):
            assert (v == 1).all()
        elif v.ndim == 1:
            assert not v.any(), name
        else:
            bound = np.sqrt(6.0 / sum(v.shape))
            assert np.abs(v).max() <= bound and v.std() > bound / 3, name
    again = PNA.init_params(7, TINY)
    other = PNA.init_params(8, TINY)
    assert all((np.asarray(a) == np.asarray(b)).all() for a, b in
               zip(jax.tree.leaves(p), jax.tree.leaves(again)))
    assert (np.asarray(p["w_in"]) != np.asarray(other["w_in"])).any()


# ------------------------------------------------------------ work
HAND_CONFIG = {"model": {"d_hidden": 2}, "graph": {"n_feat": 3,
                                                   "n_classes": 2}}
HAND_LAYERS = [{"n_src": 5, "n_dst": 3, "n_edges": 6},
               {"n_src": 3, "n_dst": 1, "n_edges": 2}]


def test_model_flops_hand_count():
    """d 2, features 3, classes 2, so [h ‖ 12 blocks] is 26 wide. Input
    projection, forward and weight gradient: 2·2·5·3·2 = 120. Layer 0:
    projections 2·(5+3)·2·2 = 64 and update 2·3·26·2 = 312, three times
    (forward, weight and input gradients) = 1128, messages 2·6·6·2 = 144.
    Layer 1: 3·(32 + 104) = 408 and 2·6·2·2 = 48. Head: 3·2·1·2·2 = 24."""
    assert PNA.model_flops(HAND_LAYERS, HAND_CONFIG) == (
        120 + 1128 + 144 + 408 + 48 + 24)


def test_kernel_calls_hand_count():
    """One forward call a layer: 6 operations per edge and channel; bytes
    of the sources' and destinations' projections read once, the four
    aggregates written once, one index an edge."""
    calls = PNA.kernel_calls(HAND_LAYERS, HAND_CONFIG)
    assert list(calls) == [KERNEL]
    assert calls[KERNEL] == [
        {"layer": 0, "pass": "forward", "flops": 72.0,
         "bytes": float((5 + 5 * 3) * 2 * 4 + 6 * 4)},
        {"layer": 1, "pass": "forward", "flops": 24.0,
         "bytes": float((3 + 5 * 1) * 2 * 4 + 2 * 4)}]


# ------------------------------------------------------------ forward
def synthetic_batch(features: int, classes: int):
    """A batch as the sampler lays it out, from a fixed seed and
    independent of the program's sampler: 40 input rows of 200 nodes,
    then 20, 12 and 6 destinations drawing 4, 3 and 3 in-edges each.
    Destination 1 of every layer draws one source in all its slots
    (exactly tied messages); destination 2 of every layer has none (its
    edges masked out)."""
    rng = np.random.default_rng(0)
    n_nodes = 200
    x = rng.standard_normal((n_nodes, features)).astype(np.float32)
    labels = rng.integers(0, classes, n_nodes).astype(np.int32)
    ids = rng.choice(n_nodes, 40, replace=False)
    blocks, n_src = [], len(ids)
    for nd, fan in ((20, 4), (12, 3), (6, 3)):
        src = rng.integers(0, n_src, nd * fan).astype(np.int64)
        src[fan:2 * fan] = src[fan]
        mask = np.ones(nd * fan, bool)
        mask[2 * fan:3 * fan] = False
        blocks.append(SimpleNamespace(
            edge_src=src, edge_dst=np.repeat(np.arange(nd), fan),
            edge_mask=mask, dst_nodes=ids[:nd], dst_pos=np.arange(nd),
            dst_mask=np.ones(nd, bool)))
        n_src = nd
    return SimpleNamespace(blocks=blocks, input_nodes=ids), x, labels


def _three_layers(config: dict) -> dict:
    return dict(config, model=dict(config["model"], n_layers=3),
                training=dict(config["training"], fanouts=[3, 3, 4]))


def test_forward_equals_the_programs_reference():
    import jax
    import jax.numpy as jnp

    from repro.models.gnn import pna

    config = _three_layers(TINY)
    mb, x, labels = synthetic_batch(config["graph"]["n_feat"],
                                    config["graph"]["n_classes"])
    batch = reference.batch_arrays(mb, x, labels)
    params = PNA.init_params(11, config)
    dev = reference.on_device(batch)
    got = np.asarray(PNA.forward(params, dev["x"], dev["blocks"]))
    blocks = [{"edge_src": jnp.asarray(b.edge_src),
               "edge_dst": jnp.asarray(b.edge_dst),
               "edge_mask": jnp.asarray(b.edge_mask),
               "dst_pos": jnp.asarray(b.dst_pos)} for b in mb.blocks]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(pna.apply_blocks(
            params, _program_cfg(config), jnp.asarray(x[mb.input_nodes]),
            blocks))
    n = len(mb.blocks[-1].dst_nodes)
    # the same float32 arithmetic in another order: at the tied
    # destination std's variance is a rounding error, amplified by the
    # square root, so the logits (up to ~10) agree to ~1e-5, not bitwise
    np.testing.assert_allclose(got[:n], want, rtol=0, atol=1e-4)
    control = np.asarray(PNA.forward(params, dev["x"], dev["blocks"],
                                     control=True))
    assert 0 < np.abs(control[:n] - want).max() < 0.1


def test_reference_trains_and_the_control_differs():
    config = _three_layers(TINY)
    mb, x, labels = synthetic_batch(config["graph"]["n_feat"],
                                    config["graph"]["n_classes"])
    batches = [reference.batch_arrays(mb, x, labels)] * 2
    p0 = PNA.init_params(11, config)
    opt = config["training"]["optimizer"]
    ref = reference.train(PNA.forward, p0, batches, opt)
    ctl = reference.train(PNA.forward, p0, batches, opt, control=True)
    assert all(np.isfinite(ref["losses"]))
    assert ref["losses"][1] < ref["losses"][0]     # the same batch again
    assert ctl["losses"] != ref["losses"]


# ------------------------------------------------------------ readers
def _upload_spans(counters: list[dict]) -> list[tuple]:
    """A 10 s window of one step per entry of ``counters``, each with an
    ``engine.upload`` span carrying them."""
    out = [(0.0, 10.0, "bench.window", {})]
    for i, c in enumerate(counters):
        out += [(i + 0.5, i + 0.9, "bench.step", {}),
                (i + 0.6, i + 0.7, "engine.upload", c)]
    return out


def test_pad_msg_share_reads_the_upload_counters(monkeypatch):
    spans = _upload_spans([
        {"msg_slots": 1000, "pad_msg_slots": 150, "tiles": 0,
         "pad_tiles": 0},
        {"msg_slots": 3000, "pad_msg_slots": 250, "tiles": 0,
         "pad_tiles": 0}])
    monkeypatch.setattr(hostspans, "load_events", lambda: spans)
    run = {"window_s": 10.0, "steps": [{}, {}]}
    assert harness.reader("pad_msg_share")(run) == pytest.approx(10.0)
    assert harness.reader("pad_tile_share")(run) is None


def test_fanout_agg_roofline_reads_the_kernels_time():
    peaks = work.PEAKS["TPU v5 lite"]
    calls = PNA.kernel_calls(HAND_LAYERS, HAND_CONFIG)
    least = sum(work.least_time(c["flops"], c["bytes"], peaks)
                for c in calls[KERNEL])
    trace = {"n_devices": 1, "ops": {
        "%fanout_aggregate_kernel.1": (least * 2, 2),
        "%fanout_aggregate_kernel.3": (least * 2, 2),
        "%fusion.7": (1.0, 4)}}
    run = {"trace": trace, "peaks": peaks,
           "steps": [{"kernel_calls": calls}] * 2}
    assert harness.reader("fanout_agg_roofline")(run) == pytest.approx(50.0)
    assert harness.reader("fanout_agg_roofline")(
        dict(run, peaks=None)) is None


def _sage_run() -> dict:
    """A GraphSAGE run record: the steps pinned from sage-reddit with its
    work counts, over the recorded chip trace of its window."""
    pin = _json(DATA, "graphsage_pin.json")
    sage_cfg = _json(BENCH, "configs", "sage-reddit.json")
    sage = harness.model(sage_cfg)
    steps = [dict(r, model_flops=sage.model_flops(r["layers"], sage_cfg),
                  kernel_calls=sage.kernel_calls(r["layers"], sage_cfg))
             for r in pin["readers"]["steps"]]
    tr = xtrace.reduce(xtrace.read_events(
        os.path.join(DATA, "reddit_window.xplane.pb")))
    return {"steps": steps, "n_feat": sage_cfg["graph"]["n_feat"],
            "peaks": work.PEAKS["TPU v5 lite"], "window_s": tr["window_s"],
            "trace": tr}


def test_new_readers_are_silent_on_a_sage_run(monkeypatch):
    run = _sage_run()
    assert harness.reader("spmm_roofline")(run) is not None
    assert harness.reader("fanout_agg_roofline")(run) is None
    sage_uploads = _upload_spans([{"tiles": 100, "pad_tiles": 25}] * 2)
    monkeypatch.setattr(hostspans, "load_events", lambda: sage_uploads)
    spans_run = {"window_s": 10.0, "steps": [{}, {}]}
    assert harness.reader("pad_tile_share")(spans_run) == 25.0
    assert harness.reader("pad_msg_share")(spans_run) is None


def test_new_metrics_are_declared_for_the_new_cell():
    spec = harness.load_spec()
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    cells = {c["name"]: c for c in spec["workloads"]}
    for name in ("fanout_agg_roofline", "pad_msg_share"):
        assert per_layer[name]["workloads"] == ["pna-reddit.paper-schedule"]
        assert per_layer[name]["moves"] == "seeds_per_s"
    cell = cells["pna-reddit.paper-schedule"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "pna-reddit", "paper-schedule", 1)


# ------------------------------------------------------------ tiny run
@pytest.fixture(scope="module")
def tiny_cache(tmp_path_factory):
    """The tiny configuration's fixtures, made once for the module in a
    temporary directory; the policy artifacts, ``REPRO_ARTIFACTS`` and
    the compile cache that a run sets are restored afterwards."""
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


def _tiny_run() -> dict:
    spec = harness.load_spec()
    plan = {"cell": {"name": "tiny-pna", "chips": 1}, "config": TINY,
            "traffic": _json(DATA, "tiny-traffic.json"),
            "metrics": spec["end_to_end"]}
    return harness.run(plan, 7, 0.0, False, time.perf_counter(),
                       require_chip=False)


@pytest.mark.parametrize("pinned", [True, False], ids=["delta", "own"])
def test_tiny_run_checks_the_programs_pna(tiny_cache, monkeypatch, pinned):
    """The program's PNA step follows the reference through the checked
    steps when its ``delta`` is the configuration's; with the tiny
    graph's own ``delta`` the ``loss`` check sees it."""
    from repro.models.gnn import pna

    if pinned:
        monkeypatch.setattr(pna, "graph_delta", lambda indptr: PNA.DELTA)
    checks = _tiny_run()["checks"]
    assert checks["blocks"]["value"] == 0 and checks["x_rows"]["value"] == 0
    if pinned:
        # CPU float32 both sides; in the checked steps the sums run in
        # another order (readings on the CPU: loss 2.8e-6, grad_norm
        # 3.5e-6, update_norm 2.0e-4)
        assert checks["loss"]["value"] < 3e-5
        assert checks["grad_norm"]["value"] < 5e-5
        assert checks["update_norm"]["value"] < 2e-3
    else:
        assert checks["loss"]["value"] > 1e-2
