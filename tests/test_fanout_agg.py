"""PNA on the measured lane: the fixed-fan-out aggregation
(``kernels/fanout_agg``) and ``ComputeEngine`` with ``model="pna"``,
against the plain references, on the CPU at small sizes.

    PYTHONPATH=src python -m pytest -q tests/test_fanout_agg.py

The op (its XLA twin and the Pallas kernel in interpret mode) against
per-edge segment ops, gradients included; the engine's forward, loss and
gradients against ``models/gnn/pna.apply_blocks`` on seeded weights, over
a graph with destinations that have no in-neighbour (their aggregates
are 0) and destinations with one in-neighbour drawn ``fan`` times
(exactly tied messages, whose max and min gradient is split equally);
padded rows that leak into no real one; ``delta`` from the graph; the
message-slot counters; and a measured run through ``TrainerWorker``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph.sampling import sample_blocks
from repro.graph.structure import Graph
from repro.kernels.fanout_agg import fanout_aggregate, neighbour_table
from repro.kernels.fanout_agg.ref import fanout_aggregate_ref
from repro.models.gnn import pna
from repro.models.gnn.common import cross_entropy

IMPLS = ["xla", "pallas"]
FANOUTS = (3, 4, 3, 5)          # output layer first, as RunConfig states
NO_IN, ONE_IN = range(0, 4), range(4, 10)


def _graph() -> Graph:
    """60 nodes, 12 features, 5 classes; nodes 0-3 have no in-neighbour,
    nodes 4-9 exactly one, the rest 1-6."""
    rng = np.random.default_rng(0)
    n = 60
    src, dst = [], []
    for v in range(n):
        k = 0 if v in NO_IN else 1 if v in ONE_IN else rng.integers(1, 7)
        for u in rng.choice(np.delete(np.arange(n), v), k, replace=False):
            src.append(u)
            dst.append(v)
    return Graph(
        n_nodes=n, edge_index=np.array([src, dst], np.int64),
        features=rng.standard_normal((n, 12)).astype(np.float32),
        labels=rng.integers(0, 5, n).astype(np.int32),
    )


GRAPH = _graph()


def _batch(graph=GRAPH, seeds=np.arange(20), fanouts=FANOUTS):
    return sample_blocks(graph, seeds, list(fanouts),
                         np.random.default_rng(1), pad=False)


def _engine(agg_impl="xla", fanouts=FANOUTS, graph=GRAPH):
    from repro.train import gnn_trainer as gt
    from repro.train.compute import ComputeEngine

    cfg = gt.RunConfig(model="pna", fanouts=fanouts, seed=3)
    return ComputeEngine(graph, cfg, agg_impl=agg_impl)


# ------------------------------------------------------------------ op
def _op_case():
    """40 sources, 16 destinations of which 3 draw no slot; the sources
    come from 5 rows, so tied messages abound."""
    rng = np.random.default_rng(0)
    n_src, n_dst, fan, d = 40, 16, 4, 75
    deg = np.full(n_dst, fan)
    deg[[3, 7, 12]] = 0
    dst = np.repeat(np.arange(n_dst), deg)
    src = rng.integers(0, 5, len(dst))
    p_src = jnp.asarray(rng.standard_normal((n_src, d)), jnp.float32)
    p_dst = jnp.asarray(rng.standard_normal((n_dst, d)), jnp.float32)
    cts = [jnp.asarray(rng.standard_normal((n_dst, d)), jnp.float32)
           for _ in range(4)]
    return src, dst, n_dst, p_src, p_dst, cts


def test_neighbour_table_layout():
    src = np.array([5, 1, 2, 7, 9, 4, 3])
    dst = np.array([2, 0, 2, 0, 2, 3, 1])
    keep = np.array([1, 1, 1, 1, 1, 1, 0], bool)
    nbr, deg = neighbour_table(src, dst, keep, 6)
    assert nbr.dtype == np.int32 and deg.dtype == np.float32
    assert nbr.shape == (6, 3)
    np.testing.assert_array_equal(deg, [2, 0, 3, 1, 0, 0])
    # real slots first, in edge order; padding slots point at row 0
    np.testing.assert_array_equal(
        nbr, [[1, 7, 0], [0, 0, 0], [5, 2, 9], [4, 0, 0], [0, 0, 0],
              [0, 0, 0]])


@pytest.mark.parametrize("impl", IMPLS)
def test_aggregate_matches_segment_reference(impl):
    src, dst, n_dst, p_src, p_dst, cts = _op_case()
    nbr, deg = neighbour_table(src, dst, np.ones(len(src), bool), n_dst)

    def ours(ps, pd):
        return fanout_aggregate(ps, pd, jnp.asarray(nbr), jnp.asarray(deg),
                                impl=impl)

    def ref(ps, pd):
        return fanout_aggregate_ref(ps, pd, jnp.asarray(src),
                                    jnp.asarray(dst), n_dst)

    got, want = ours(p_src, p_dst), ref(p_src, p_dst)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    for a in got:       # no in-neighbour: all four aggregates are 0
        assert not np.asarray(a)[deg == 0].any()

    def pull(f):
        return jax.grad(lambda ps, pd: sum(
            jnp.sum(o * c) for o, c in zip(f(ps, pd), cts)),
            argnums=(0, 1))(p_src, p_dst)

    for a, b in zip(pull(ours), pull(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("impl", IMPLS)
def test_tied_slots_split_the_max_and_min_gradient(impl):
    """One destination draws source 2 in all its slots: its max and min
    pass their gradient once in all, split equally over the slots, not
    once a slot."""
    fan, d = 5, 8
    nbr = jnp.full((1, fan), 2, jnp.int32)
    deg = jnp.full((1,), float(fan), jnp.float32)
    p_src = jnp.ones((4, d), jnp.float32)
    p_dst = jnp.full((1, d), 0.5, jnp.float32)
    for k in (1, 2):    # max, then min
        g_src, g_dst = jax.grad(
            lambda ps, pd: jnp.sum(fanout_aggregate(
                ps, pd, nbr, deg, impl=impl)[k]), argnums=(0, 1))(
            p_src, p_dst)
        g_src = np.asarray(g_src)
        np.testing.assert_allclose(g_src[2], np.ones(d))
        assert not g_src[[0, 1, 3]].any()
        np.testing.assert_allclose(np.asarray(g_dst[0]), np.ones(d))


# -------------------------------------------------------------- engine
def _ref_blocks(mb):
    return [{"edge_src": jnp.asarray(b.edge_src),
             "edge_dst": jnp.asarray(b.edge_dst),
             "edge_mask": jnp.asarray(b.edge_mask),
             "dst_pos": jnp.asarray(b.dst_pos)} for b in mb.blocks]


def test_batch_has_the_edge_cases():
    mb = _batch()
    seeds = set(mb.blocks[-1].dst_nodes)
    assert seeds & set(NO_IN) and seeds & set(ONE_IN)


def test_delta_is_the_graphs_mean_log_degree():
    eng = _engine()
    deg = np.bincount(GRAPH.edge_index[1], minlength=GRAPH.n_nodes)
    assert eng.mcfg.delta == pytest.approx(np.mean(np.log(deg + 1.0)),
                                           rel=1e-12)
    assert (eng.mcfg.d_hidden, eng.mcfg.n_layers) == (75, 4)
    assert (eng.mcfg.d_in, eng.mcfg.n_classes) == (12, 5)


def test_layers_must_match_fanouts():
    with pytest.raises(ValueError, match="one fan-out per layer"):
        _engine(fanouts=(3, 3))


@pytest.mark.parametrize("impl", IMPLS)
def test_loss_and_gradients_match_apply_blocks(impl):
    eng = _engine(impl)
    mb = _batch()
    x_in = GRAPH.features[mb.input_nodes].astype(np.float32)
    layers, x_rows, _ = eng.prepare(mb)
    x_pad = eng.pad_input(x_in, x_rows)
    last = layers[-1]
    seeds = mb.blocks[-1].dst_nodes

    def ours(p):
        return cross_entropy(eng._forward(p, x_pad, layers), last["labels"],
                             last["lmask"])

    def ref(p):
        logits = pna.apply_blocks(p, eng.mcfg, jnp.asarray(x_in),
                                  _ref_blocks(mb))
        return cross_entropy(logits, jnp.asarray(GRAPH.labels[seeds]))

    # both sides compute std as sqrt(E[m²] − mean² + 1e-5), summing in
    # another order; at exactly tied messages the variance is a rounding
    # error, which the square root amplifies 1/(2·sqrt(1e-5)) ≈ 158
    # times: logits (up to ~5) differ by ~1e-4, gradients by ~3e-4 of
    # their norm, where a wrong aggregate or scaler moves them by O(1)
    assert eng.check_parity(mb, x_in) < 5e-4
    (l1, g1), (l2, g2) = (jax.value_and_grad(f)(eng.params)
                          for f in (ours, ref))
    assert float(l1) == pytest.approx(float(l2), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.linalg.norm(a - b) <= 1e-3 * np.linalg.norm(b) + 1e-6


def test_padded_rows_do_not_leak():
    """Garbage in the padded input rows and in the padded destinations'
    tables changes no real logit."""
    eng = _engine()
    mb = _batch()
    x_in = GRAPH.features[mb.input_nodes].astype(np.float32)
    host, x_rows, _, _ = eng._prepare(mb)
    x_pad = eng.pad_input(x_in, x_rows)
    n_seeds = len(mb.blocks[-1].dst_nodes)
    clean = np.asarray(eng._fwd_jit(eng.params, x_pad, host))[:n_seeds]
    x_bad = x_pad.copy()
    x_bad[len(x_in):] = 1e3
    bad = []
    for layer, blk in zip(host, mb.blocks):
        n_dst = len(blk.dst_nodes)
        assert len(layer["deg"]) > n_dst
        layer = dict(layer, nbr=layer["nbr"].copy(), deg=layer["deg"].copy(),
                     dst_pos=layer["dst_pos"].copy())
        layer["nbr"][n_dst:] = len(x_pad) // 2
        layer["deg"][n_dst:] = layer["nbr"].shape[1]
        layer["dst_pos"][n_dst:] = len(x_in)
        bad.append(layer)
    dirty = np.asarray(eng._fwd_jit(eng.params, x_bad, tuple(bad)))
    np.testing.assert_array_equal(dirty[:n_seeds], clean)


def test_step_counts_message_slots():
    eng = _engine()
    mb = _batch()
    x_in = GRAPH.features[mb.input_nodes].astype(np.float32)
    host, _, _, counts = eng._prepare(mb)
    want = sum(layer["nbr"].size for layer in host)
    pad = sum(int((layer["deg"] == 0).sum()) * layer["nbr"].shape[1]
              for layer in host)
    assert counts == {"tiles": 0, "pad_tiles": 0, "msg_slots": want,
                      "pad_msg_slots": pad}
    assert 0 < pad < want
    eng.parity_max_diff = float("nan")
    eng.step(mb, x_in)
    eng.step(mb, x_in)
    assert (eng.msg_slots, eng.pad_msg_slots) == (2 * want, 2 * pad)
    assert (eng.tiles, eng.pad_tiles) == (0, 0)
    assert eng.n_compiles == 1 and all(np.isfinite(eng.losses))


def test_wire_bytes_follow_the_model():
    from repro.train.cluster import default_grad_bytes
    from repro.train.compute import model_wire_bytes, pna_config

    params, _ = pna.init(jax.random.PRNGKey(0), pna_config(GRAPH))
    n = sum(p.size for p in jax.tree.leaves(params))
    assert default_grad_bytes(GRAPH, "pna") == 4.0 * n
    assert model_wire_bytes(GRAPH, "int8", model="pna") < 4.0 * n
    # SAGE keeps its closed form: d_in -> 16 -> classes, two weights each
    assert default_grad_bytes(GRAPH) == 4.0 * (2 * 12 * 16 + 16
                                               + 2 * 16 * 5 + 5)


def test_measured_run_through_the_worker():
    """``RunConfig(model="pna")`` on the measured lane trains PNA through
    ``TrainerWorker``: no side script."""
    from repro.train import gnn_trainer as gt

    cfg = gt.RunConfig(method="static_w", dataset="reddit", batch_size=600,
                       n_epochs=1, steps_per_epoch=2, scenario="clean",
                       fanouts=(2, 2, 2, 2), compute="measured",
                       model="pna", run_model=True, seed=0)
    res = gt.run(cfg)
    rep = res.compute_report
    assert rep["n_steps"] == 2 and all(np.isfinite(rep["losses"]))
    assert rep["parity_max_diff"] < 2e-3
    assert len(res.accuracy_per_epoch) == 1
