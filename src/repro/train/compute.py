"""Measured-compute lane: a real jitted GNN step on the hot path.

Modeled mode charges ``CostModelParams.t_base`` for every trainer step;
this module replaces that constant with the wall time of an actual
forward/backward/optimizer step over the feature payloads the step
resolved. ``RunConfig.model`` selects the model (``MODELS``): its config,
init, forward, parity reference and wire bytes. GraphSAGE (``"sage"``,
the default) dispatches neighborhood aggregation through the
``kernels.segment_mm`` block-sparse format:

  * on an accelerator backend the Pallas kernel (``block_spmm``) runs
    compiled;
  * on CPU — where Pallas can only interpret — the same block-sparse
    format executes through the compiled XLA twin (``block_spmm_xla``),
    so the measured numbers are real compiled-step times everywhere.

The edge-list -> block conversion is split between host and device.
The host computes each layer's index plan (``segment_mm.block_sparse_plan``:
the tile ids, and each edge's tile slot and in-tile offset) and uploads
it with the padded input; the compiled step scatters the tiles from the
plan on the device (``segment_mm.tiles_from_plan``) before each layer's
aggregation. A plan is a few bytes per edge where the tiles are 64 KiB
each (at reddit width one batch's layer-0 tiles take ~4.3 GB, for ~10
nonzeros a tile). Nothing keeps a batch on the device between steps. The
host work and upload are timed as their own span, ``prep_s``, apart from
the compiled step, ``step_s``, which includes the tile scatter; the meter
is charged both. Dynamic tile/src/dst counts are bucketed to powers of
two, and each plan is padded to the dst bucket times the layer's
fan-out, so the jitted step compiles once per size bucket; compilation
happens ahead-of-time (``.lower().compile()``) and is excluded from the
measured step time.

PNA (``"pna"``) needs max, min and std of messages that depend on both
endpoints, which no 0/1 tile carries. The sampler draws exactly the
fan-out per destination, so the host uploads each layer's neighbour table
(``kernels.fanout_agg.neighbour_table``: ``nbr [n_dst_pad, fan]`` and the
real slots ``deg``) and the step aggregates with ``fanout_aggregate``
(the ``fanout_aggregate_kernel`` on an accelerator, its XLA twin on CPU);
``msg_slots`` counts the table's slots, of which ``pad_msg_slots`` belong
to padded or neighbourless destinations.

The step is parity-asserted against the model's scatter reference
(``apply_blocks``; ``check_parity``, run automatically on the first
step). The step's phases are host-clock spans of the worker's
``repro.obs.wall`` recorder (``engine.build``, ``engine.pad``,
``engine.upload``, ``engine.parity``, ``engine.compile``, ``engine.run``,
``engine.free`` inside ``engine.step``), and its uploads are counted exactly:
``h2d_bytes``, the 128x128 ``tiles`` built of which ``pad_tiles`` are
power-of-two padding, and the real ``edges`` scattered into them.
Gradient sync flows through ``grad_compression`` with error feedback;
``sync_wire_bytes`` is what the cluster driver feeds into
``ring_collective_cost`` in place of the uncompressed payload.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import numpy as np

from repro.obs.wall import NULL_SPANS
from repro.train import grad_compression as gc

_SCHEMES = ("none", "int8", "topk")
# The model is stated in float32. On TPU the backend's default runs a
# float32 dot as one bf16 pass, which is a lower precision than that, so
# the step (and the forward ``check_parity`` reads) pins its matmuls here.
MATMUL_PRECISION = "highest"


def _bucket(n: int) -> int:
    """Next power of two >= n (min 1): bounds distinct jit signatures."""
    return 1 << max(int(n) - 1, 0).bit_length()


MODELS = ("sage", "pna")


def _d_in(graph) -> int:
    if graph.features is not None:
        return int(graph.features.shape[1])
    return int(graph.feature_source.n_feat)


def sage_config(graph, d_hidden: int = 16):
    """The paper's training model (Section VI-A) sized for ``graph`` —
    the single config shared by the modeled-mode model runner
    (``gnn_trainer._init_model``) and the measured lane."""
    from repro.models.gnn import sage

    return sage.SageConfig(
        d_in=_d_in(graph), d_hidden=d_hidden,
        n_classes=int(graph.labels.max()) + 1, n_layers=2, dropout=0.0,
    )


def pna_config(graph):
    """PNA at its published widths (``repro.configs.pna``) sized for
    ``graph``, with ``delta`` the mean log in-degree of ``graph``."""
    from repro.configs import pna as pna_arch
    from repro.models.gnn import pna

    cfg = pna_arch.make_config(d_in=_d_in(graph),
                               n_classes=int(graph.labels.max()) + 1)
    return dataclasses.replace(cfg, delta=pna.graph_delta(graph.csr.indptr))


def model_config(graph, model: str = "sage"):
    """The config of ``model`` (one of ``MODELS``) sized for ``graph``."""
    if model == "sage":
        return sage_config(graph)
    if model == "pna":
        return pna_config(graph)
    raise ValueError(f"model must be one of {MODELS}, got {model!r}")


def model_module(model: str):
    """``repro.models.gnn.<model>``: its ``init``, ``apply_blocks`` and
    ``apply_full``."""
    from repro.models.gnn import pna, sage

    return {"sage": sage, "pna": pna}[model]


def model_wire_bytes(graph, scheme: str = "none", frac: float = 0.05,
                     model: str = "sage") -> float:
    """Per-sync gradient payload bytes for ``model`` on ``graph`` under a
    compression scheme (abstract param shapes; nothing is materialized).
    ``scheme="none"`` is ``cluster.default_grad_bytes``."""
    import jax

    params, _ = model_module(model).init(
        jax.random.PRNGKey(0), model_config(graph, model), abstract=True)
    return float(gc.wire_bytes(params, scheme, frac))


class ComputeEngine:
    """Real jitted GNN step (``cfg.model``) + timing + compression for one
    worker.

    ``clock`` is injectable (monotonic, ``time.perf_counter`` by default)
    so the determinism harness can drive the measured lane with a virtual
    clock and pin the timing -> calibration plumbing numerically. ``spans``
    is the owning worker's host-clock span recorder; it never reads
    ``clock``.
    """

    def __init__(self, graph, cfg, agg_impl: str = "auto",
                 clock: Callable[[], float] | None = None,
                 tile: int = 128, spans=NULL_SPANS):
        import jax

        from repro import optim
        from repro.kernels.segment_mm import default_interpret

        scheme = getattr(cfg, "grad_compression", "none")
        if scheme not in _SCHEMES:
            raise ValueError(
                f"grad_compression must be one of {_SCHEMES}, got {scheme!r}"
            )
        if agg_impl == "auto":
            agg_impl = "xla" if default_interpret() else "pallas"
        if agg_impl not in ("pallas", "xla"):
            raise ValueError(f"unknown agg_impl {agg_impl!r}")

        self.device_kind = jax.devices()[0].device_kind
        self.graph = graph
        self.model = cfg.model
        self.mcfg = model_config(graph, cfg.model)
        if self.mcfg.n_layers != len(cfg.fanouts):
            raise ValueError(
                f"model {cfg.model!r} has {self.mcfg.n_layers} layers but "
                f"fanouts {tuple(cfg.fanouts)} sample {len(cfg.fanouts)}: "
                f"give one fan-out per layer"
            )
        self.module = model_module(cfg.model)
        self.tile = int(tile)
        self.agg_impl = agg_impl
        self.scheme = scheme
        self.topk_frac = float(getattr(cfg, "topk_frac", 0.05))
        self.clock = clock or time.perf_counter
        self.spans = spans
        self.params, _ = self.module.init(jax.random.PRNGKey(cfg.seed),
                                          self.mcfg)
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
        self.h2d_bytes = 0       # everything step uploads
        self.tiles = 0           # 128x128 tiles built, all layers (sage)
        self.pad_tiles = 0       # of which power-of-two padding
        self.msg_slots = 0       # neighbour-table slots, all layers (pna)
        self.pad_msg_slots = 0   # of padded or neighbourless destinations
        self.parity_max_diff: float | None = None
        self._parity_tol = 2e-3

    # ------------------------------------------------------------ prepare
    def prepare(self, mb):
        """The model's layer plans + pow2 bucketing for one mini-batch,
        uploaded: ``(layers, x_rows, n_edges)`` with ``layers`` on the
        device."""
        import jax

        host, x_rows, n_edges, _ = self._prepare(mb)
        return jax.device_put(host), x_rows, n_edges

    def _prepare(self, mb):
        """``prepare`` on the host: the layers as numpy arrays, and the
        step's counters (``tiles``, ``pad_tiles``, ``msg_slots``,
        ``pad_msg_slots``).

        Each layer's destinations are padded to a power-of-two count of
        128-row blocks; it holds ``dst_pos`` (padded rows point at row 0),
        the model's plan (``_tile_plan`` or ``_neighbour_plan``) and, on
        the last layer, ``labels`` and the label mask ``lmask``."""
        t = self.tile
        layers = []
        n_edges = 0
        counts = dict.fromkeys(
            ("tiles", "pad_tiles", "msg_slots", "pad_msg_slots"), 0)
        n_src_rows = _bucket(-(-len(mb.blocks[0].src_nodes) // t)) * t
        src_rows = n_src_rows
        for i, blk in enumerate(mb.blocks):
            n_dst_true = len(blk.dst_nodes)
            n_dst_pad = _bucket(-(-n_dst_true // t)) * t
            if self.model == "pna":
                layer = self._neighbour_plan(blk, n_dst_pad, counts)
            else:
                layer = self._tile_plan(blk, n_dst_pad, src_rows, counts)
            dst_pos = np.zeros(n_dst_pad, np.int32)
            dst_pos[:n_dst_true] = blk.dst_pos
            layer["dst_pos"] = dst_pos
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
        return tuple(layers), n_src_rows, n_edges, counts

    def _tile_plan(self, blk, n_dst_pad: int, src_rows: int,
                   counts: dict) -> dict:
        """A SAGE layer's block-sparse plan: its tiles' ``rows``/``cols``
        (padded to a power of two with zero tiles on the last row-block)
        and its real edges' ``slot``/``off``
        (``segment_mm.sorted_edge_slots``), padded to the dst bucket times
        the layer's largest in-degree (its fan-out) with slots the device
        build scatters as zeros; masked edges count toward the tile set but
        are not scattered (their weight is 0). ``counts`` gains the tiles
        and their padding."""
        from repro.kernels.segment_mm import (
            block_sparse_plan, sorted_edge_slots,
        )

        t = self.tile
        n_dst_blocks = n_dst_pad // t
        rows, cols, slot, off, ndb, n_src_pad = block_sparse_plan(
            blk.edge_src, blk.edge_dst, n_dst_pad, src_rows, t, t
        )
        assert n_src_pad == src_rows and ndb == n_dst_blocks
        nbp = _bucket(len(rows))
        if nbp > len(rows):
            pad = nbp - len(rows)
            counts["pad_tiles"] += pad
            # padding tiles stay zero and point at the last row-block
            # (rows stay sorted; they accumulate nothing)
            rows = np.concatenate(
                [rows, np.full(pad, ndb - 1, np.int32)]
            )
            cols = np.concatenate([cols, np.zeros(pad, np.int32)])
        counts["tiles"] += nbp
        indeg = np.bincount(
            blk.edge_dst[blk.edge_mask], minlength=n_dst_pad
        )
        slot, off = sorted_edge_slots(
            slot, off, blk.edge_mask, n_dst_pad * int(indeg.max()),
            nbp, t * t,
        )
        return {
            "rows": rows,
            "cols": cols,
            "slot": slot,
            "off": off,
            "counts": np.maximum(indeg, 1).astype(np.float32)[:, None],
        }

    def _neighbour_plan(self, blk, n_dst_pad: int, counts: dict) -> dict:
        """A PNA layer's neighbour table (``fanout_agg.neighbour_table``)
        over the padded destinations: ``nbr`` and the real slots ``deg``.
        ``counts`` gains its slots, and those of destinations with no real
        slot (padding, or no in-neighbour)."""
        from repro.kernels.fanout_agg import neighbour_table

        nbr, deg = neighbour_table(blk.edge_src, blk.edge_dst,
                                   blk.edge_mask, n_dst_pad)
        fan = nbr.shape[1]
        counts["msg_slots"] += n_dst_pad * fan
        counts["pad_msg_slots"] += int(np.count_nonzero(deg == 0)) * fan
        return {"nbr": nbr, "deg": deg}

    def pad_input(self, x_in: np.ndarray, x_rows: int) -> np.ndarray:
        x = np.zeros((x_rows, self.mcfg.d_in), np.float32)
        x[: len(x_in)] = x_in
        return x

    # ------------------------------------------------------------ forward
    def _tiles(self, layer):
        """The layer's adjacency tiles, scattered on the device from its
        plan."""
        from repro.kernels.segment_mm import tiles_from_plan

        return tiles_from_plan(layer["slot"], layer["off"],
                               layer["rows"].shape[0], self.tile, self.tile)

    def _aggregate(self, layer, h):
        import jax.numpy as jnp

        from repro.kernels.segment_mm import block_spmm_xla
        from repro.kernels.segment_mm.kernel import block_spmm_kernel

        t = self.tile
        n_dst_blocks = layer["counts"].shape[0] // t
        blocks = self._tiles(layer)
        f = h.shape[1]
        if self.agg_impl == "pallas":
            f_pad = -(-f // t) * t
            hp = h
            if f_pad != f:
                hp = jnp.zeros((h.shape[0], f_pad), h.dtype).at[:, :f].set(h)
            y = block_spmm_kernel(
                layer["rows"], layer["cols"], blocks, hp,
                n_dst_blocks, tn=t, tm=t, tf=t,
            )[:, :f]
        else:
            y = block_spmm_xla(
                layer["rows"], layer["cols"], blocks, h,
                n_dst_blocks, tn=t, tm=t,
            )
        return y / layer["counts"]

    def _forward(self, params, x_pad, layers):
        """The model's forward over prepared layers (padded rows), at
        ``MATMUL_PRECISION``. SAGE's block path builds each layer's tiles
        from its plan before its aggregation."""
        import jax

        if self.model == "pna":
            with jax.default_matmul_precision(MATMUL_PRECISION):
                return self._pna_forward(params, x_pad, layers)
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

    def _pna_forward(self, params, x_pad, layers):
        """PNA over the neighbour tables: each layer projects its sources
        and destinations once per node, aggregates the per-edge messages
        with ``fanout_aggregate`` and applies ``pna.update``."""
        from repro.kernels.fanout_agg import fanout_aggregate

        h = x_pad @ params["w_in"] + params["b_in"]
        for i, layer in enumerate(layers):
            lp = params[f"layer_{i}"]
            h_dst = h[layer["dst_pos"]]
            aggs = fanout_aggregate(
                h @ lp["w_msg_src"], h_dst @ lp["w_msg_dst"] + lp["b_msg"],
                layer["nbr"], layer["deg"], impl=self.agg_impl,
            )
            h = self.module.update(lp, self.mcfg, h_dst, aggs, layer["deg"])
        return h @ params["w_out"] + params["b_out"]

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
        Two spans are timed: ``prep_s``, the host build of the layers'
        plans and the padded input, their upload (waited for) and the free
        of the host copies; and ``step_s``, the compiled step alone (the
        tile scatter included), which is what ``calibration_samples``
        fits. AOT compilation on a new shape bucket and the first step's
        parity check are in neither (compilation is accounted in
        ``compile_s``). Returns the sum of the two spans, the step's
        compute time as the meter charges it. ``h2d_bytes`` counts the
        upload, ``tiles`` and ``pad_tiles`` the tiles the step builds,
        ``msg_slots`` and ``pad_msg_slots`` its neighbour tables' slots
        (the first step's parity check uploads its own copies, not
        counted).
        """
        import jax

        spans = self.spans
        with spans.span("engine.step"):
            t = self.clock()
            with spans.span("engine.build"):
                host, x_rows, n_edges, counts = self._prepare(mb)
            with spans.span("engine.pad"):
                x_pad = self.pad_input(np.asarray(x_in, np.float32), x_rows)
            nbytes = sum(a.nbytes for a in jax.tree.leaves((host, x_pad)))
            self.h2d_bytes += nbytes
            self.tiles += counts["tiles"]
            self.pad_tiles += counts["pad_tiles"]
            self.msg_slots += counts["msg_slots"]
            self.pad_msg_slots += counts["pad_msg_slots"]
            with spans.span("engine.upload", h2d_bytes=nbytes, edges=n_edges,
                            **counts):
                layers, x_dev = jax.block_until_ready(
                    jax.device_put((host, x_pad)))
            prep = self.clock() - t
            if self.parity_max_diff is None:
                with spans.span("engine.parity"):
                    self.check_parity(mb, x_in, _prep=(layers, x_rows))
            args = (self.params, self.opt_state, self.error, x_dev, layers)
            sig = (x_pad.shape,) + tuple(
                a.shape for a in jax.tree.leaves(layers))
            if sig not in self._exec:
                with spans.span("engine.compile"):
                    t0 = self.clock()
                    self._exec[sig] = self._jit.lower(*args).compile()
                    self.compile_s += self.clock() - t0
                self.n_compiles += 1
            with spans.span("engine.run"):
                t0 = self.clock()
                out = self._exec[sig](*args)
                jax.block_until_ready(out)
                dt = self.clock() - t0
            # the host copies the upload read are held past the timed step
            # and dropped here, so their free (~0.3 GB at reddit width, the
            # padded input) is charged to prep_s and not to the compiled step
            with spans.span("engine.free"):
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
        """Assert the step's forward == the scatter reference on this batch.

        The reference is the model's ``apply_blocks`` (per-edge gather +
        ``common`` scatter ops) on the UNPADDED blocks; the step's forward
        must agree on every valid dst row within float-accumulation
        tolerance (summation order differs between the two). The step
        runs at ``MATMUL_PRECISION``; the reference runs at full float32.
        """
        import jax
        import jax.numpy as jnp

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
            ref = self.module.apply_blocks(
                self.params, self.mcfg,
                jnp.asarray(np.asarray(x_in, np.float32)), ref_blocks,
            )
        n = ref.shape[0]
        valid = np.asarray(mb.blocks[-1].dst_mask, bool)
        diff = np.abs(np.asarray(got)[:n] - np.asarray(ref))[valid]
        self.parity_max_diff = float(diff.max()) if diff.size else 0.0
        if self.parity_max_diff > tol:
            raise AssertionError(
                f"step/scatter parity violated: max |diff| "
                f"{self.parity_max_diff:.3e} > {tol:.0e} "
                f"(agg_impl={self.agg_impl})"
            )
        return self.parity_max_diff

    # ---------------------------------------------------------- reporting
    def model_eval(self, graph) -> float:
        from repro.train import gnn_trainer as gt

        return gt._model_eval({"params": self.params, "cfg": self.mcfg},
                              graph, apply_full=self.module.apply_full)

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
