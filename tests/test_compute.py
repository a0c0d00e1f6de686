"""Measured compute lane: block aggregation parity, compression, engine.

Fast tier covers the numerics (block-sparse aggregation vs the
``scatter_sum`` oracle on ragged graphs, error-feedback compression on
nested pytrees, ``calibrate_compute`` law recovery, the wire-bytes
identity) plus the modeled-lane digest pins this PR must not move. The
slow lane runs the jitted engine end to end: measured-lane determinism,
and a P=2 cluster smoke with int8 gradient sync.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.segment_mm import (
    block_spmm, block_spmm_xla, default_interpret, to_block_sparse,
)
from repro.models.gnn.common import scatter_sum
from repro.train import grad_compression as gc


# ---------------------------------------------------------------------------
# block-sparse aggregation vs the scatter_sum oracle
# ---------------------------------------------------------------------------

def _block_agg(src, dst, x, n_dst, w=None, tile=128):
    """to_block_sparse + compiled block path, cropped to the true rows."""
    n_src = x.shape[0]
    rows, cols, blocks, ndb, n_src_pad = to_block_sparse(
        src, dst, n_dst, n_src, tile, tile, edge_weight=w
    )
    x_pad = np.zeros((n_src_pad, x.shape[1]), np.float32)
    x_pad[:n_src] = x
    y = block_spmm_xla(
        jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(blocks),
        jnp.asarray(x_pad), ndb, tile, tile,
    )
    return np.asarray(y)[:n_dst]


class TestBlockAggregation:
    @pytest.mark.parametrize("n_src,n_dst,n_edges,f,seed", [
        (300, 260, 2000, 70, 0),     # non-multiple-of-128 everywhere
        (1000, 50, 4000, 32, 1),     # many-to-few (the SAGE regime)
        (64, 700, 300, 16, 2),       # sparse: most dst blocks empty
        (128, 128, 0, 8, 3),         # no edges at all
    ])
    def test_matches_scatter_sum(self, n_src, n_dst, n_edges, f, seed):
        rng = np.random.default_rng(seed)
        src = rng.integers(0, n_src, n_edges).astype(np.int64)
        dst = rng.integers(0, n_dst, n_edges).astype(np.int64)
        x = rng.standard_normal((n_src, f)).astype(np.float32)
        got = _block_agg(src, dst, x, n_dst)
        want = np.asarray(scatter_sum(
            jnp.asarray(x)[jnp.asarray(src)], jnp.asarray(dst), n_dst
        )) if n_edges else np.zeros((n_dst, f), np.float32)
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)

    def test_edge_weights(self):
        rng = np.random.default_rng(7)
        src = rng.integers(0, 90, 500).astype(np.int64)
        dst = rng.integers(0, 70, 500).astype(np.int64)
        w = rng.standard_normal(500).astype(np.float32)
        x = rng.standard_normal((90, 24)).astype(np.float32)
        got = _block_agg(src, dst, x, 70, w=w)
        msgs = jnp.asarray(x)[jnp.asarray(src)] * jnp.asarray(w)[:, None]
        want = np.asarray(scatter_sum(msgs, jnp.asarray(dst), 70))
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)

    def test_format_covers_every_dst_block(self):
        """Missing row-blocks are materialized as zero blocks (col 0) and
        the row index stays sorted — the executor contract."""
        src = np.array([0, 5], np.int64)
        dst = np.array([0, 300], np.int64)   # dst blocks 0 and 2 touched
        rows, cols, blocks, ndb, _ = to_block_sparse(src, dst, 384, 64)
        assert ndb == 3
        assert sorted(set(rows.tolist())) == [0, 1, 2]
        assert np.all(np.diff(rows) >= 0)
        filler = np.flatnonzero(rows == 1)
        assert cols[filler].tolist() == [0]
        assert not blocks[filler].any()

    def test_interpret_autodetects_cpu(self):
        assert default_interpret() is (
            jax.default_backend() not in ("tpu", "gpu", "cuda", "rocm")
        )
        # interpret=None resolves without error and matches the XLA path
        rng = np.random.default_rng(4)
        src = rng.integers(0, 128, 200).astype(np.int64)
        dst = rng.integers(0, 128, 200).astype(np.int64)
        x = rng.standard_normal((128, 16)).astype(np.float32)
        rows, cols, blocks, ndb, n_src_pad = to_block_sparse(
            src, dst, 128, 128
        )
        a = block_spmm(rows, cols, blocks, jnp.asarray(x), ndb, tf=16)
        b = block_spmm_xla(
            jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(blocks),
            jnp.asarray(x), ndb,
        )
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------------------
# device plumbing: peak table, prepared batches, step spans, compile cache
# ---------------------------------------------------------------------------

class TestDevicePlumbing:
    def test_peak_table_known_and_unknown_kinds(self):
        from repro.launch.roofline import device_peaks

        v5e = device_peaks("TPU v5 lite")
        assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
        assert "TPU v5e" in v5e.source
        with pytest.raises(ValueError, match="no published peaks"):
            device_peaks("TPU v99")

    def test_cpu_engine_prices_nothing(self):
        """On the CPU the engine takes the XLA block path; it holds no
        roofline peaks, and its transfer counters start at zero."""
        from repro.train import gnn_trainer as gt
        from repro.train.compute import ComputeEngine

        cfg = gt.RunConfig(**_PIN_CFG)
        graph = gt.datasets.materialize(cfg.dataset, seed=0)
        eng = ComputeEngine(graph, cfg)
        assert not hasattr(eng, "peaks") and eng.agg_impl == "xla"
        assert (eng.h2d_bytes, eng.tiles, eng.pad_tiles) == (0, 0, 0)

    def test_prepare_uploads_canonical_layers(self):
        from repro.train import gnn_trainer as gt
        from repro.train.compute import ComputeEngine

        cfg = gt.RunConfig(**dict(_PIN_CFG, n_epochs=1, steps_per_epoch=1))
        graph, _owner, _traces, mbs = gt.build_trace(cfg)
        mb = mbs[0][0]
        layers, x_rows, n_edges = ComputeEngine(graph, cfg).prepare(mb)
        assert x_rows % 128 == 0 and n_edges > 0
        scattered = 0
        # blocks are input layer first; fan-outs are listed output first
        for layer, fanout in zip(layers, cfg.fanouts[::-1]):
            assert all(isinstance(a, jax.Array) for a in layer.values())
            assert "blocks" not in layer
            for key in ("rows", "cols", "slot", "off", "dst_pos"):
                assert layer[key].dtype == jnp.int32
            assert layer["counts"].dtype == jnp.float32
            # one slot per sampled edge of every padded dst row; the real
            # edges first, sorted, then padding (slot = tile count)
            n_tiles = layer["rows"].shape[0]
            slot = np.asarray(layer["slot"])
            assert len(slot) == layer["counts"].shape[0] * fanout
            real = slot < n_tiles
            scattered += int(real.sum())
            assert not real[real.sum():].any()
            key = slot[real].astype(np.int64) * 128**2 + np.asarray(
                layer["off"])[real]
            assert (np.diff(key) >= 0).all() and key.max() < n_tiles * 128**2
        assert scattered == n_edges
        assert layers[-1]["labels"].dtype == jnp.int32

    @pytest.mark.parametrize("n_steps", [1, 3])
    def test_step_times_prep_and_compiled_step_apart(self, n_steps):
        """Under a clock that advances 1 ms per read, the compiled step
        spans one read pair, host preparation two (before the step and the
        free after it), and ``step`` returns their sum, the charged time."""
        from repro.train import gnn_trainer as gt
        from repro.train.compute import ComputeEngine

        class _VClock:
            t = 0.0

            def __call__(self):
                self.t += 1e-3
                return self.t

        cfg = gt.RunConfig(**dict(_PIN_CFG, n_epochs=1,
                                  steps_per_epoch=n_steps))
        graph, _owner, _traces, mbs = gt.build_trace(cfg)
        eng = ComputeEngine(graph, cfg, clock=_VClock())
        charged = [
            eng.step(mb, np.asarray(graph.features[mb.input_nodes],
                                    np.float32))
            for mb in mbs[0]
        ]
        rep = eng.report()
        assert rep["step_s"] == pytest.approx([1e-3] * n_steps)
        assert rep["prep_s"] == pytest.approx([2e-3] * n_steps)
        assert charged == pytest.approx([3e-3] * n_steps)
        assert rep["n_compiles"] == 1

    def test_step_counts_its_upload_exactly(self, monkeypatch):
        """``h2d_bytes`` is the summed ``nbytes`` of the layers' plans
        ``_prepare`` returns and of ``pad_input``'s padded input; ``tiles``
        the layers' ``rows`` lengths after padding, ``pad_tiles`` the
        difference from the plan's lengths before."""
        import jax

        from repro.kernels import segment_mm
        from repro.train import gnn_trainer as gt
        from repro.train.compute import ComputeEngine

        cfg = gt.RunConfig(**dict(_PIN_CFG, n_epochs=1, steps_per_epoch=2))
        graph, _owner, _traces, mbs = gt.build_trace(cfg)
        eng = ComputeEngine(graph, cfg)
        eng.parity_max_diff = float("nan")
        real = segment_mm.block_sparse_plan
        unpadded = []

        def spy(*a, **k):
            out = real(*a, **k)
            unpadded.append(len(out[0]))
            return out

        monkeypatch.setattr(segment_mm, "block_sparse_plan", spy)
        want_bytes = want_tiles = 0
        for mb in mbs[0]:
            x_in = np.asarray(graph.features[mb.input_nodes], np.float32)
            host, x_rows, _, _ = eng._prepare(mb)
            assert all("blocks" not in layer for layer in host)
            x_pad = eng.pad_input(x_in, x_rows)
            want_bytes += sum(a.nbytes for a in
                              jax.tree.leaves((host, x_pad)))
            want_tiles += sum(len(layer["rows"]) for layer in host)
            eng.step(mb, x_in)
        # _prepare ran twice per batch: once here, once inside step
        n_before = sum(unpadded) // 2
        assert eng.h2d_bytes == want_bytes
        assert eng.tiles == want_tiles
        assert eng.pad_tiles == want_tiles - n_before
        assert eng.pad_tiles > 0

    def test_device_tiles_train_as_host_tiles(self):
        """Over three steps the engine, which scatters its tiles on the
        device, gives bit for bit the losses and parameters of the same
        step fed ``to_block_sparse``'s host tiles, and compiles once per
        (input rows, tile bucket, dst bucket) signature, as host tiles
        did."""
        from repro.train import gnn_trainer as gt
        from repro.train.compute import ComputeEngine

        class HostTiles(ComputeEngine):
            def _prepare(self, mb):
                layers, x_rows, n_edges, pad_tiles = super()._prepare(mb)
                src_rows = x_rows
                for layer, blk in zip(layers, mb.blocks):
                    n_dst_pad = len(layer["counts"])
                    _, _, blocks, _, _ = to_block_sparse(
                        blk.edge_src, blk.edge_dst, n_dst_pad, src_rows,
                        edge_weight=blk.edge_mask.astype(np.float32),
                    )
                    layer["blocks"] = np.zeros(
                        (len(layer["rows"]), 128, 128), np.float32)
                    layer["blocks"][: len(blocks)] = blocks
                    src_rows = n_dst_pad
                return layers, x_rows, n_edges, pad_tiles

            def _tiles(self, layer):
                return layer["blocks"]

        cfg = gt.RunConfig(**dict(_PIN_CFG, n_epochs=1, steps_per_epoch=3))
        graph, _owner, _traces, mbs = gt.build_trace(cfg)
        device, host = ComputeEngine(graph, cfg), HostTiles(graph, cfg)
        sigs = set()
        for mb in mbs[0]:
            x_in = np.asarray(graph.features[mb.input_nodes], np.float32)
            layers, x_rows, _, _ = device._prepare(mb)
            sigs.add((x_rows,) + tuple(
                (len(l["rows"]), len(l["counts"])) for l in layers))
            device.step(mb, x_in)
            host.step(mb, x_in)
        assert device.losses == host.losses
        for a, b in zip(jax.tree.leaves(device.params),
                        jax.tree.leaves(host.params)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert device.n_compiles == host.n_compiles == len(sigs)

    @pytest.mark.parametrize("agg_impl", ["xla", "pallas"])
    def test_step_lowers_every_dot_at_model_precision(self, agg_impl):
        """The trained step, backward pass included, runs its matmuls at
        the model's float32 precision, not the backend's default."""
        import re

        from repro.train import gnn_trainer as gt
        from repro.train.compute import ComputeEngine

        cfg = gt.RunConfig(**dict(_PIN_CFG, n_epochs=1, steps_per_epoch=1))
        graph, _owner, _traces, mbs = gt.build_trace(cfg)
        eng = ComputeEngine(graph, cfg, agg_impl=agg_impl)
        mb = mbs[0][0]
        layers, x_rows, _ = eng.prepare(mb)
        x = eng.pad_input(
            np.asarray(graph.features[mb.input_nodes], np.float32), x_rows
        )
        text = eng._jit.lower(eng.params, eng.opt_state, eng.error, x,
                              layers).as_text()
        dots = re.findall(r"stablehlo\.dot_general.*", text)
        assert dots
        assert all("precision = [HIGHEST, HIGHEST]" in d for d in dots)

    def test_compile_cache_env_wins_else_fixed_path(self, monkeypatch):
        from repro.launch import compile_cache as cc

        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.append((k, v)))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert cc.enable_compile_cache() == "/elsewhere"
        assert updates == []
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cc.enable_compile_cache() == cc.CACHE_DIR
        assert updates == [("jax_compilation_cache_dir", cc.CACHE_DIR)]
        assert cc.CACHE_DIR.endswith(".jax_cache")


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

class TestGradCompression:
    def _nested(self):
        rng = np.random.default_rng(0)
        return {
            "layer_0": (jnp.asarray(rng.standard_normal((8, 4)), jnp.float32),
                        jnp.asarray(rng.standard_normal(4), jnp.float32)),
            "scale": jnp.asarray(rng.standard_normal(()), jnp.float32),
        }

    @pytest.mark.parametrize("scheme", ["int8", "topk"])
    def test_nested_tuple_pytree_survives(self, scheme):
        """Regression: tuple-sniffing is_leaf mangled (w, b) layer params;
        the explicit unzip must preserve the treedef on both outputs."""
        grads = self._nested()
        error = gc.init_error_feedback(grads)
        fn = (gc.compress_int8 if scheme == "int8"
              else lambda g, e: gc.compress_topk(g, e, 0.25))
        deq, new_err = fn(grads, error)
        want = jax.tree.structure(grads)
        assert jax.tree.structure(deq) == want
        assert jax.tree.structure(new_err) == want
        for g, d, e in zip(jax.tree.leaves(grads), jax.tree.leaves(deq),
                           jax.tree.leaves(new_err)):
            assert d.shape == g.shape
            # exact identity: decompressed + error == grad + old error (0)
            np.testing.assert_allclose(
                np.asarray(d + e), np.asarray(g), atol=1e-5, rtol=1e-5
            )

    def test_error_feedback_converges(self):
        """int8-compressed SGD on a quadratic reaches the uncompressed
        optimum: the residual is re-injected, not dropped."""
        target = jnp.asarray(np.linspace(-2.0, 2.0, 16), jnp.float32)
        x = jnp.zeros(16, jnp.float32)
        err = jnp.zeros(16, jnp.float32)
        for _ in range(300):
            g = x - target
            deq, err = gc.compress_int8(g, err)
            x = x - 0.1 * deq
        assert float(jnp.max(jnp.abs(x - target))) < 1e-2

    def test_wire_bytes_schemes(self):
        grads = self._nested()
        n = sum(g.size for g in jax.tree.leaves(grads))
        assert gc.wire_bytes(grads, "none") == 4 * n
        assert gc.wire_bytes(grads, "int8") == n + 4 * 3  # one scale/leaf
        k = sum(max(int(0.25 * g.size), 1)
                for g in jax.tree.leaves(grads))
        assert gc.wire_bytes(grads, "topk", 0.25) == 8 * k
        with pytest.raises(ValueError):
            gc.wire_bytes(grads, "zfp")

    def test_model_wire_bytes_matches_default_grad_bytes(self):
        """Acceptance identity: grad_compression="none" charges exactly
        the constant the modeled collective has always used."""
        from repro.graph import datasets
        from repro.train.cluster import default_grad_bytes
        from repro.train.compute import model_wire_bytes

        graph = datasets.materialize("reddit", seed=0)
        assert model_wire_bytes(graph, "none") == default_grad_bytes(graph)


# ---------------------------------------------------------------------------
# calibration law recovery
# ---------------------------------------------------------------------------

class TestCalibrateCompute:
    def test_recovers_law(self):
        from repro.core import calibration as cal
        from repro.core import cost_model as cm

        t0, per_edge = 1.5e-3, 4.0e-8
        edges = np.array([2e3, 8e3, 3e4, 9e4])
        times = np.asarray([cm.compute_step_s(t0, per_edge, float(e))
                            for e in edges])
        params, fit = cal.calibrate_compute(edges, times)
        assert fit.t0 == pytest.approx(t0, rel=1e-9)
        assert fit.per_edge == pytest.approx(per_edge, rel=1e-9)
        assert fit.r2 == pytest.approx(1.0, abs=1e-12)
        want = cm.compute_step_s(t0, per_edge, float(edges.mean()))
        assert float(params.t_base) == pytest.approx(want, rel=1e-9)

    def test_ref_edges_override_and_errors(self):
        from repro.core import calibration as cal

        edges = np.array([1e3, 2e3, 3e3])
        times = 1e-3 + 1e-8 * edges
        params, _ = cal.calibrate_compute(edges, times, ref_edges=2e3)
        assert float(params.t_base) == pytest.approx(
            1e-3 + 1e-8 * 2e3, rel=1e-9
        )
        with pytest.raises(ValueError):
            cal.calibrate_compute(np.array([]), np.array([]))
        with pytest.raises(ValueError):
            cal.calibrate_compute(edges, times[:2])


# ---------------------------------------------------------------------------
# modeled-lane digest pins (this PR must not move the modeled lane)
# ---------------------------------------------------------------------------

_PIN_CFG = dict(
    method="static_w", dataset="reddit", batch_size=600, n_epochs=2,
    steps_per_epoch=8, scenario="clean", seed=0,
)
_P1_DIGEST = "04bf2d292b6290a0ada5117655575d508b78d3f2dee64ea93de3c24b15157ac4"
_P4_DIGEST = "41d1a2d4d2a3e26dac2bfcd3618cab19fa12ffb53b1db759670fece305fbce28"


class TestModeledLanePins:
    def test_p1_digest_unchanged(self):
        from repro.analysis import digest as dg
        from repro.train import gnn_trainer as gt

        cfg = gt.RunConfig(**_PIN_CFG)
        assert dg.result_digest(gt.run(cfg, gt.build_trace(cfg))) \
            == _P1_DIGEST

    @pytest.mark.slow
    def test_p4_cluster_digest_unchanged(self):
        from repro.analysis import digest as dg
        from repro.train import gnn_trainer as gt
        from repro.train.cluster import ClusterConfig, run_cluster

        cfg = gt.RunConfig(**_PIN_CFG)
        report = run_cluster(cfg, ClusterConfig(n_workers=4))
        assert dg.report_digest(report) == _P4_DIGEST


# ---------------------------------------------------------------------------
# the measured engine end to end (slow: real jit compiles)
# ---------------------------------------------------------------------------

def _measured_cfg(**kw):
    from repro.train import gnn_trainer as gt

    base = dict(_PIN_CFG, n_epochs=1, steps_per_epoch=4, compute="measured")
    base.update(kw)
    return gt.RunConfig(**base)


@pytest.mark.slow
class TestComputeEngine:
    def test_engine_step_parity_and_report(self):
        from repro.train import gnn_trainer as gt
        from repro.train.compute import ComputeEngine

        cfg = _measured_cfg()
        graph, _owner, _traces, mbs = gt.build_trace(cfg)
        eng = ComputeEngine(graph, cfg)
        for s in range(cfg.steps_per_epoch):
            mb = mbs[0][s]
            dt = eng.step(
                mb, np.asarray(graph.features[mb.input_nodes], np.float32)
            )
            assert dt > 0.0
        rep = eng.report()
        assert rep["n_steps"] == cfg.steps_per_epoch
        assert rep["parity_max_diff"] < 2e-3    # block path vs reference
        assert rep["n_compiles"] == 1           # pow2 bucketing held
        assert np.all(np.isfinite(rep["losses"]))
        acc = eng.model_eval(graph)
        assert 0.0 <= acc <= 1.0

    def test_measured_lane_deterministic(self):
        from repro.analysis import digest as dg
        from repro.train import gnn_trainer as gt

        cfg = _measured_cfg()
        runs = [gt.run(cfg, gt.build_trace(cfg)) for _ in range(2)]
        assert (dg.measured_result_digest(runs[0])
                == dg.measured_result_digest(runs[1]))
        rep = runs[0].compute_report
        total = cfg.n_epochs * cfg.steps_per_epoch
        assert rep["n_steps"] == total
        assert len(rep["step_s"]) == total
        # the measured lane must not perturb the sim's discrete surface
        r_mod = gt.run(
            dataclasses.replace(cfg, compute="modeled"), gt.build_trace(cfg)
        )
        fa, fb = dg.result_fields(runs[0]), dg.result_fields(r_mod)
        for name in dg._ENERGY_FIELDS:
            fa.pop(name)
            fb.pop(name)
        assert dg.digest(fa) == dg.digest(fb)

    def test_cluster_int8_smoke(self):
        from repro.train import gnn_trainer as gt
        from repro.train.cluster import (
            ClusterConfig, default_grad_bytes, run_cluster,
        )

        cfg = _measured_cfg()
        graph = gt.datasets.materialize(cfg.dataset, seed=0)
        report = run_cluster(
            cfg, ClusterConfig(n_workers=2, grad_compression="int8")
        )
        assert report.grad_compression == "int8"
        assert 0 < report.grad_wire_bytes < default_grad_bytes(graph)
        rows = report.per_worker()
        assert all(r["grad_compression"] == "int8" for r in rows)
        assert all(r["measured_step_s"] > 0.0 for r in rows)

    def test_invalid_schemes_rejected(self):
        from repro.train import gnn_trainer as gt
        from repro.train.compute import ComputeEngine

        cfg = _measured_cfg(grad_compression="zfp")
        graph = gt.datasets.materialize(cfg.dataset, seed=0)
        with pytest.raises(ValueError):
            ComputeEngine(graph, cfg)
        with pytest.raises(ValueError):
            gt.run(dataclasses.replace(cfg, compute="sampled"))
