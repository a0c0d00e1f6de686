"""Measured-compute lane: a real jitted GraphSAGE step on the hot path.

Modeled mode charges ``CostModelParams.t_base`` for every trainer step;
this module replaces that constant with the wall time of an actual
forward/backward/optimizer step over the feature payloads the step
resolved, with neighborhood aggregation dispatched through the
``kernels.segment_mm`` block-sparse format:

  * on an accelerator backend the Pallas kernel (``block_spmm``) runs
    compiled;
  * on CPU — where Pallas can only interpret — the same block-sparse
    format executes through the compiled XLA twin (``block_spmm_xla``),
    so the measured numbers are real compiled-step times everywhere.

The edge-list -> block conversion is numpy preprocessing, run and
uploaded afresh for every step and dropped after it: nothing keeps a batch
on the device between steps (at reddit width one batch's layer-0 tiles
alone take ~4.3 GB). That host work is timed as its own span, ``prep_s``,
apart from the compiled step, ``step_s``; the meter is charged both.
Dynamic block/src/dst counts are bucketed to powers of two so the jitted
step compiles once per size bucket; compilation happens ahead-of-time
(``.lower().compile()``) and is excluded from the measured step time.

The block path is parity-asserted against the ``models/gnn/common``
scatter reference (``check_parity``, run automatically on the first
step). On an accelerator the engine prices its steps at the chip's
published peaks (``launch.roofline.PEAKS``); an accelerator missing from
that table is an error. Gradient sync flows through ``grad_compression``
with error feedback; ``sync_wire_bytes`` is what the cluster driver feeds
into ``ring_collective_cost`` in place of the uncompressed payload.
"""
from __future__ import annotations

import time
from typing import Callable

import numpy as np

from repro.train import grad_compression as gc

_SCHEMES = ("none", "int8", "topk")
# The model is stated in float32. On TPU the backend's default runs a
# float32 dot as one bf16 pass, which is a lower precision than that, so
# the step (and the forward ``check_parity`` reads) pins its matmuls here.
MATMUL_PRECISION = "highest"


def _bucket(n: int) -> int:
    """Next power of two >= n (min 1): bounds distinct jit signatures."""
    return 1 << max(int(n) - 1, 0).bit_length()


def sage_config(graph, d_hidden: int = 16):
    """The paper's training model (Section VI-A) sized for ``graph`` —
    the single config shared by the modeled-mode model runner
    (``gnn_trainer._init_model``) and the measured lane."""
    from repro.models.gnn import sage

    d_in = (
        graph.features.shape[1]
        if graph.features is not None
        else graph.feature_source.n_feat
    )
    return sage.SageConfig(
        d_in=d_in, d_hidden=d_hidden,
        n_classes=int(graph.labels.max()) + 1, n_layers=2, dropout=0.0,
    )


def model_wire_bytes(graph, scheme: str = "none", frac: float = 0.05) -> float:
    """Per-sync gradient payload bytes for the SAGE model on ``graph``
    under a compression scheme (abstract param shapes; nothing is
    materialized). ``scheme="none"`` equals the float32 gradient payload
    of ``cluster.default_grad_bytes`` bit-for-bit."""
    import jax

    from repro.models.gnn import sage

    params, _ = sage.init(jax.random.PRNGKey(0), sage_config(graph),
                          abstract=True)
    return float(gc.wire_bytes(params, scheme, frac))


class ComputeEngine:
    """Real jitted SAGE step + timing + compression for one worker.

    ``clock`` is injectable (monotonic, ``time.perf_counter`` by default)
    so the determinism harness can drive the measured lane with a virtual
    clock and pin the timing -> calibration plumbing numerically.
    """

    def __init__(self, graph, cfg, agg_impl: str = "auto",
                 clock: Callable[[], float] | None = None,
                 tile: int = 128):
        import jax

        from repro import optim
        from repro.kernels.segment_mm import default_interpret
        from repro.launch.roofline import device_peaks

        scheme = getattr(cfg, "grad_compression", "none")
        if scheme not in _SCHEMES:
            raise ValueError(
                f"grad_compression must be one of {_SCHEMES}, got {scheme!r}"
            )
        if agg_impl == "auto":
            agg_impl = "xla" if default_interpret() else "pallas"
        if agg_impl not in ("pallas", "xla"):
            raise ValueError(f"unknown agg_impl {agg_impl!r}")

        from repro.models.gnn import sage

        device = jax.devices()[0]
        self.device_kind = device.device_kind
        # per-chip peaks for the roofline terms; None on CPU, where a wall
        # time prices nothing
        self.peaks = (
            None if device.platform == "cpu"
            else device_peaks(device.device_kind)
        )
        self.graph = graph
        self.mcfg = sage_config(graph)
        self.tile = int(tile)
        self.agg_impl = agg_impl
        self.scheme = scheme
        self.topk_frac = float(getattr(cfg, "topk_frac", 0.05))
        self.clock = clock or time.perf_counter
        self.params, _ = sage.init(jax.random.PRNGKey(cfg.seed), self.mcfg)
        self.opt = optim.adamw(3e-3)  # greenlint: literal-ok — must match
        # the modeled lane's _init_model lr exactly; plumbing a config
        # field only one lane reads would let the twins drift
        self.opt_state = self.opt.init(self.params)
        self.error = gc.init_error_feedback(self.params)
        self.sync_wire_bytes = float(
            gc.wire_bytes(self.params, scheme, self.topk_frac)
        )
        self.labels_np = np.asarray(graph.labels)

        self._jit = jax.jit(self._step_fn)
        self._fwd_jit = jax.jit(self._forward)
        self._exec: dict = {}            # shape signature -> AOT executable

        self.losses: list[float] = []
        self.prep_s: list[float] = []
        self.step_s: list[float] = []
        self.step_edges: list[int] = []
        self.compile_s = 0.0
        self.n_compiles = 0
        self.parity_max_diff: float | None = None
        self._parity_tol = 2e-3

    # ------------------------------------------------------------ prepare
    def prepare(self, mb):
        """Block-sparse conversion + pow2 bucketing for one mini-batch,
        uploaded: ``(layers, x_rows, n_edges)`` with ``layers`` on the
        device."""
        import jax

        host, x_rows, n_edges = self._prepare(mb)
        return jax.device_put(host), x_rows, n_edges

    def _prepare(self, mb):
        """``prepare`` on the host: the layers as numpy arrays."""
        from repro.kernels.segment_mm import to_block_sparse

        t = self.tile
        layers = []
        n_edges = 0
        n_src_rows = _bucket(-(-len(mb.blocks[0].src_nodes) // t)) * t
        src_rows = n_src_rows
        for i, blk in enumerate(mb.blocks):
            n_dst_true = len(blk.dst_nodes)
            n_dst_blocks = _bucket(-(-n_dst_true // t))
            n_dst_pad = n_dst_blocks * t
            w = blk.edge_mask.astype(np.float32)
            rows, cols, blocks, ndb, n_src_pad = to_block_sparse(
                blk.edge_src, blk.edge_dst, n_dst_pad, src_rows, t, t, w
            )
            assert n_src_pad == src_rows and ndb == n_dst_blocks
            nbp = _bucket(len(rows))
            if nbp > len(rows):
                pad = nbp - len(rows)
                # padding blocks stay zero and point at the last row-block
                # (rows stay sorted; they accumulate nothing)
                rows = np.concatenate(
                    [rows, np.full(pad, ndb - 1, np.int32)]
                )
                cols = np.concatenate([cols, np.zeros(pad, np.int32)])
                blocks = np.concatenate(
                    [blocks, np.zeros((pad, t, t), np.float32)]
                )
            indeg = np.bincount(
                blk.edge_dst[blk.edge_mask], minlength=n_dst_pad
            ).astype(np.float32)
            dst_pos = np.zeros(n_dst_pad, np.int32)
            dst_pos[:n_dst_true] = blk.dst_pos
            layer = {
                "rows": rows,
                "cols": cols,
                "blocks": blocks,
                "counts": np.maximum(indeg, 1.0)[:, None],
                "dst_pos": dst_pos,
            }
            if i == len(mb.blocks) - 1:
                labels = np.zeros(n_dst_pad, self.labels_np.dtype)
                labels[:n_dst_true] = self.labels_np[blk.dst_nodes]
                lmask = np.zeros(n_dst_pad, np.float32)
                lmask[:n_dst_true] = blk.dst_mask.astype(np.float32)
                layer["labels"] = labels
                layer["lmask"] = lmask
            layers.append(layer)
            n_edges += int(blk.edge_mask.sum())
            src_rows = n_dst_pad
        return tuple(layers), n_src_rows, n_edges

    def pad_input(self, x_in: np.ndarray, x_rows: int) -> np.ndarray:
        x = np.zeros((x_rows, self.mcfg.d_in), np.float32)
        x[: len(x_in)] = x_in
        return x

    # ------------------------------------------------------------ forward
    def _aggregate(self, layer, h):
        import jax.numpy as jnp

        from repro.kernels.segment_mm import block_spmm_xla
        from repro.kernels.segment_mm.kernel import block_spmm_kernel

        t = self.tile
        n_dst_blocks = layer["counts"].shape[0] // t
        f = h.shape[1]
        if self.agg_impl == "pallas":
            f_pad = -(-f // t) * t
            hp = h
            if f_pad != f:
                hp = jnp.zeros((h.shape[0], f_pad), h.dtype).at[:, :f].set(h)
            y = block_spmm_kernel(
                layer["rows"], layer["cols"], layer["blocks"], hp,
                n_dst_blocks, tn=t, tm=t, tf=t,
            )[:, :f]
        else:
            y = block_spmm_xla(
                layer["rows"], layer["cols"], layer["blocks"], h,
                n_dst_blocks, tn=t, tm=t,
            )
        return y / layer["counts"]

    def _forward(self, params, x_pad, layers):
        """Block-path SAGE forward over prepared layers (padded rows), at
        ``MATMUL_PRECISION``."""
        import jax

        h = x_pad
        with jax.default_matmul_precision(MATMUL_PRECISION):
            for i, layer in enumerate(layers):
                lp = params[f"layer_{i}"]
                agg = self._aggregate(layer, h)
                h_dst_self = h[layer["dst_pos"]]
                h_new = (h_dst_self @ lp["w_self"] + agg @ lp["w_neigh"]
                         + lp["b"])
                if i < len(layers) - 1:
                    h_new = jax.nn.relu(h_new)
                h = h_new
        return h

    def _step_fn(self, params, opt_state, error, x_pad, layers):
        import jax

        from repro import optim
        from repro.models.gnn.common import cross_entropy

        last = layers[-1]

        def loss_fn(p):
            logits = self._forward(p, x_pad, layers)
            return cross_entropy(logits, last["labels"], last["lmask"])

        # the backward pass (the kernel's custom VJP included) is traced
        # here, so it runs at the forward's precision too
        with jax.default_matmul_precision(MATMUL_PRECISION):
            loss, grads = jax.value_and_grad(loss_fn)(params)
        if self.scheme == "int8":
            grads, error = gc.compress_int8(grads, error)
        elif self.scheme == "topk":
            grads, error = gc.compress_topk(grads, error, self.topk_frac)
        upd, opt_state = self.opt.update(grads, opt_state, params)
        return optim.apply_updates(params, upd), opt_state, error, loss

    # --------------------------------------------------------------- step
    def step(self, mb, x_in: np.ndarray) -> float:
        """One measured forward/backward/optimizer step.

        ``x_in`` are the resolved feature rows for ``mb.input_nodes``.
        Two spans are timed: ``prep_s``, the host build of the block
        layers and padded input, their upload (waited for) and the free of
        the host copies; and ``step_s``, the compiled step alone, which is
        what ``calibration_samples`` fits. AOT compilation on a new shape
        bucket and the first step's parity check are in neither
        (compilation is accounted in ``compile_s``). Returns the sum of
        the two spans, the step's compute time as the meter charges it.
        """
        import jax

        t = self.clock()
        host, x_rows, n_edges = self._prepare(mb)
        x_pad = self.pad_input(np.asarray(x_in, np.float32), x_rows)
        layers, x_dev = jax.block_until_ready(jax.device_put((host, x_pad)))
        prep = self.clock() - t
        if self.parity_max_diff is None:
            self.check_parity(mb, x_in, _prep=(layers, x_rows))
        args = (self.params, self.opt_state, self.error, x_dev, layers)
        sig = (x_pad.shape,) + tuple(
            (l["rows"].shape[0], l["counts"].shape[0]) for l in layers
        )
        if sig not in self._exec:
            t0 = self.clock()
            self._exec[sig] = self._jit.lower(*args).compile()
            self.compile_s += self.clock() - t0
            self.n_compiles += 1
        t0 = self.clock()
        out = self._exec[sig](*args)
        jax.block_until_ready(out)
        dt = self.clock() - t0
        # the host copies the upload read are held past the timed step and
        # dropped here, so their free (~4.4 GB at reddit width) is charged
        # to prep_s and not to the compiled step
        t = self.clock()
        del host, x_pad
        prep += self.clock() - t
        self.params, self.opt_state, self.error, loss = out
        self.losses.append(float(loss))
        self.prep_s.append(float(prep))
        self.step_s.append(float(dt))
        self.step_edges.append(int(n_edges))
        return float(prep + dt)

    # ------------------------------------------------------------- parity
    def check_parity(self, mb, x_in: np.ndarray, tol: float | None = None,
                     _prep=None):
        """Assert block-path forward == scatter reference on this batch.

        The reference is ``sage.apply_blocks`` (per-edge gather +
        ``common.scatter_sum``/mean) on the UNPADDED blocks; the block
        path must agree on every valid dst row within float-accumulation
        tolerance (summation order differs between the two). The block
        path runs at ``MATMUL_PRECISION``, as the trained step does; the
        reference runs at full float32.
        """
        import jax
        import jax.numpy as jnp

        from repro.models.gnn import sage

        tol = self._parity_tol if tol is None else tol
        if _prep is None:
            layers, x_rows, _ = self.prepare(mb)
        else:
            layers, x_rows = _prep
        x_pad = self.pad_input(np.asarray(x_in, np.float32), x_rows)
        ref_blocks = [
            {
                "edge_src": jnp.asarray(b.edge_src),
                "edge_dst": jnp.asarray(b.edge_dst),
                "edge_mask": jnp.asarray(b.edge_mask),
                "dst_pos": jnp.asarray(b.dst_pos),
            }
            for b in mb.blocks
        ]
        got = self._fwd_jit(self.params, jnp.asarray(x_pad), layers)
        with jax.default_matmul_precision("highest"):
            ref = sage.apply_blocks(
                self.params, self.mcfg,
                jnp.asarray(np.asarray(x_in, np.float32)), ref_blocks,
            )
        n = ref.shape[0]
        valid = np.asarray(mb.blocks[-1].dst_mask, bool)
        diff = np.abs(np.asarray(got)[:n] - np.asarray(ref))[valid]
        self.parity_max_diff = float(diff.max()) if diff.size else 0.0
        if self.parity_max_diff > tol:
            raise AssertionError(
                f"block-path/scatter parity violated: max |diff| "
                f"{self.parity_max_diff:.3e} > {tol:.0e} "
                f"(agg_impl={self.agg_impl})"
            )
        return self.parity_max_diff

    # ---------------------------------------------------------- reporting
    def model_eval(self, graph) -> float:
        from repro.train import gnn_trainer as gt

        return gt._model_eval({"params": self.params, "cfg": self.mcfg},
                              graph)

    def calibration_samples(self) -> tuple[np.ndarray, np.ndarray]:
        """(n_edges, step_s) pairs for ``calibration.calibrate_compute``."""
        return (
            np.asarray(self.step_edges, np.float64),
            np.asarray(self.step_s, np.float64),
        )

    def report(self) -> dict:
        return {
            "n_steps": len(self.step_s),
            "losses": list(self.losses),
            "prep_s": list(self.prep_s),
            "step_s": list(self.step_s),
            "step_edges": list(self.step_edges),
            "compile_s": self.compile_s,
            "n_compiles": self.n_compiles,
            "agg_impl": self.agg_impl,
            "device_kind": self.device_kind,
            "grad_compression": self.scheme,
            "sync_wire_bytes": self.sync_wire_bytes,
            "parity_max_diff": self.parity_max_diff,
        }
