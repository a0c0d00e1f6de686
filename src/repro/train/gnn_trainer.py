"""Distributed GNN training loop with the GreenDyGNN pipeline (Section V).

This trainer reproduces the paper's evaluation harness end-to-end:

  real graph -> METIS-like partition -> presampled mini-batch trace ->
  per-step feature resolution (local / cache-hit / remote miss) ->
  calibrated network-time + energy accounting -> per-boundary control
  (static / heuristic / RL) -> Table-I style reports.

Everything *discrete* is real (sampled batches, hit/miss streams, per-owner
byte counts); wall-clock network time and power are modeled by the
calibrated Eq. (4) RPC law — see DESIGN.md "Measured vs modeled" — or, with
``RunConfig.scenario`` set, by the ``repro.net`` discrete-event congestion
fabric (per-owner link queues, background traffic, trace replay; DESIGN.md
"Fabric vs closed form"). With
``async_pipeline=True`` the double-buffered rebuild itself is also real: a
``repro.pipeline.CacheBuilder`` thread plans and bulk-fetches the next hot
set while this loop consumes the active buffer, and a depth-Q
``PrefetchQueue`` resolves upcoming batch payloads ahead of time; rebuild
overlap and exposed stalls are then *measured*, replacing the analytic
``alpha_crit`` leak term (DESIGN.md "Measured vs modeled, revisited"). The
same loop optionally runs the actual jitted GraphSAGE train step
(``run_model=True``) so examples train a real model under the same pipeline.

Methods (paper Section VI-A + ablations VI-H):
  dgl          on-demand per-layer fetching, no cache
  bgl          prefetch-overlap pipeline, no adaptive cache
  rapidgnn     epoch-level static cache (presample once per epoch)
  static_w     windowed cache at fixed W (w/o-RL ablation at W=16)
  heuristic    windowed cache + Eq. 7 threshold rule
  greendygnn   windowed cache + Double-DQN controller (full system)
  greendygnn_nocw   RL for W only, uniform allocation (w/o cost weights)
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

from repro.core import controller as ctl
from repro.core import cost_model as cm
from repro.core import domain_rand as dr
from repro.core.energy import EnergyMeter, StepSample
from repro.core.windowed_cache import CacheStats, DoubleBufferedCache
from repro.graph import datasets
from repro.graph.features import ShardedFeatureStore
from repro.graph.partition import partition_graph
from repro.graph.sampling import presample_epoch

METHODS = (
    "dgl", "bgl", "rapidgnn", "static_w", "heuristic",
    "greendygnn", "greendygnn_nocw",
)


@dataclasses.dataclass
class RunConfig:
    method: str = "greendygnn"
    dataset: str = "reddit"
    batch_size: int = 2000
    n_epochs: int = 30
    steps_per_epoch: int = 32
    fanouts: tuple = (10, 25)
    n_parts: int = 4
    cache_frac: float = 0.35        # RapidGNN-scale: ~100k / 233k on Reddit
    congested: bool = True           # paper schedule vs clean (closed form)
    fixed_delta_ms: float | tuple | None = None
                                     # override: constant injected delay [ms]
                                     # on EVERY owner link (scalar) or per
                                     # owner (length-(P-1) vector) —
                                     # calibration + Fig. 8 grids
    scenario: str | None = None      # net-fabric scenario (repro.net): e.g.
                                     # "clean", "paper_schedule",
                                     # "bursty_markov", "incast",
                                     # "trace:<path>". None/"closed_form"
                                     # keeps the analytic Eq. 4 law driven
                                     # by congested/fixed_delta_ms.
    static_window: int = 16
    warmup_epochs: int = 2
    batch_divisor: int = 10          # bench graphs are ~10x scaled: keep the
                                     # paper's batch/graph ratio
    locality_frac: float = 0.75      # fraction of each batch drawn from the
                                     # locality traversal (rest global)
    dgl_chunk: int = 512             # rows per fine-grained DistTensor RPC
    dgl_concurrency: int = 2         # in-flight RPCs (default DGL pipeline)
    prefetch_depth: int = 4          # Stage-3 async queue depth Q: cached
                                     # methods hide fetch latency behind up
                                     # to Q*t_base of lookahead (Section V-A)
    bgl_depth: int = 2               # BGL prefetches but shallower
    seed: int = 0
    params: cm.CostModelParams = dataclasses.field(
        default_factory=cm.CostModelParams
    )
    q_fn: Callable | None = None     # RL policy (greendygnn methods)
    run_model: bool = False          # also run the real jitted GNN step
    pad_blocks: bool = False         # static block shapes (jit-stable steps)
    bgl_overlap_frac: float = 0.75   # fraction of t_base usable to hide stall
    async_pipeline: bool = False     # run the REAL threaded builder/prefetch
                                     # pipeline (repro.pipeline) instead of
                                     # the analytic alpha_crit leak model;
                                     # windowed methods only
    mem_budget: object | None = None  # repro.store.MemoryBudget: tiered
                                     # out-of-core store with a host-tier
                                     # byte budget. None (or an unlimited
                                     # budget) keeps the legacy monolithic
                                     # in-RAM store bit-for-bit.
    compute: str = "modeled"         # "measured" runs the real jitted GNN
                                     # step (train/compute.ComputeEngine)
                                     # each trainer step and charges its
                                     # measured wall time where t_base is
                                     # charged today. "modeled" keeps the
                                     # constant-t_base lane bit-for-bit.
    model: str = "sage"              # the measured lane's model
                                     # (compute.MODELS): "sage" (2x16 mean)
                                     # or "pna" (4x75); one fan-out a layer
    grad_compression: str = "none"   # measured-lane gradient sync scheme:
                                     # "none" | "int8" | "topk" (error
                                     # feedback; wire bytes feed the ring
                                     # collective in cluster runs)
    topk_frac: float = 0.05          # kept fraction for "topk"
    trace: bool = False              # greentrace: record virtual-time span/
                                     # counter/charge events (repro.obs).
                                     # False keeps the modeled lane
                                     # bit-for-bit (null tracer, zero event
                                     # work on the hot path).


@dataclasses.dataclass
class RunResult:
    meter: EnergyMeter
    hit_rate_per_epoch: np.ndarray
    window_per_epoch: np.ndarray
    sigma_trace: np.ndarray
    accuracy_per_epoch: np.ndarray | None
    wall_time_per_epoch: np.ndarray
    # parity-harness observables: per-step hit/miss stream and cumulative
    # remotely-fetched rows by owner (cache rebuilds + per-step misses)
    step_hits: np.ndarray | None = None
    step_misses: np.ndarray | None = None
    fetched_rows_by_owner: np.ndarray | None = None
    pipeline: object | None = None   # PipelineReport when async_pipeline=True
    tier_counts: dict | None = None  # TierStats.counts() when the run used a
                                     # budgeted tiered store (outside the
                                     # digest surface; compared separately)
    compute_report: dict | None = None  # ComputeEngine.report() when the run
                                     # used compute="measured" (losses and
                                     # step timings; outside the digest
                                     # surface — see digest.measured_*)
    trace: dict | None = None        # greentrace payload (cfg.trace=True):
                                     # per-rank section from the worker,
                                     # wrapped into the full run payload by
                                     # run()/run_cluster (outside the digest
                                     # surface — the trace OBSERVES the run)

    def totals(self) -> dict:
        return self.meter.totals_kj()


def build_trace(cfg: RunConfig, rank: int = 0, rng=None, graph=None,
                owner=None):
    """Shared per-(dataset,batch) trace so all methods see identical load.

    Seeds are drawn in *locality order* (community-sorted with a rotating
    offset per epoch): consecutive mini-batches expand nearby neighborhoods,
    so the hot remote set drifts within the epoch — the physical driver of
    the paper's decaying h(W) (fresh small-window caches track the drift,
    epoch-level caches cannot; Section II-C).

    ``rank``/``rng``/``graph``/``owner`` support the cluster driver: every
    worker presamples from ITS partition of the shared graph with its own
    ``SeedSequence``-spawned stream (see ``worker.worker_rngs``). The
    defaults reproduce the legacy rank-0 trace bit-for-bit."""
    if graph is None:
        # greenlint: literal-ok — the graph/partition are fixtures shared by
        # every method and seed; plumbing cfg.seed here would change the
        # dataset per run and break cross-method comparability
        graph = datasets.materialize(cfg.dataset, seed=0)
    if owner is None:
        # greenlint: literal-ok — same fixture contract as the dataset above:
        # the partition layout is shared by every method/seed on purpose
        owner = partition_graph(graph, cfg.n_parts, seed=0)
    if rng is None:
        rng = np.random.default_rng(cfg.seed + 17)
    local_nodes = np.where(owner == rank)[0]
    # locality-ordered traversal: sort by community, jitter within community
    comm = graph.labels[local_nodes].astype(np.int64)
    order = np.lexsort((rng.random(len(local_nodes)), comm))
    local_sorted = local_nodes[order]
    batch = max(cfg.batch_size // max(cfg.batch_divisor, 1), 32)
    mbs = []
    for epoch in range(cfg.n_epochs):
        # rotate the traversal start each epoch (epoch-shuffled locality)
        roll = rng.integers(0, len(local_sorted))
        epoch_nodes = np.roll(local_sorted, roll)
        mbs.append(
            presample_epoch(
                graph, epoch_nodes, batch, list(cfg.fanouts),
                cfg.steps_per_epoch, rng, pad=cfg.pad_blocks,
                sequential=True, locality_frac=cfg.locality_frac,
            )
        )
    traces = [[mb.input_nodes for mb in epoch] for epoch in mbs]
    return graph, owner, traces, mbs


def _closed_form_delta(cfg: RunConfig, epoch: int, n_owners: int) -> np.ndarray:
    """Injected per-owner delay [ms] for the analytic (non-fabric) path."""
    if cfg.fixed_delta_ms is not None:
        fd = np.asarray(cfg.fixed_delta_ms, np.float64).ravel()
        if fd.size == 1:
            return np.full(n_owners, fd[0])
        if fd.size != n_owners:
            raise ValueError(
                f"fixed_delta_ms has {fd.size} entries, run has "
                f"{n_owners} owner links"
            )
        return fd.copy()
    if cfg.congested:
        return np.asarray(dr.paper_schedule_delta(epoch, cfg.n_epochs, n_owners))
    return np.zeros(n_owners)


def _fetch_time(params, per_owner_rows: np.ndarray, delta_ms: np.ndarray,
                bytes_per_row: float) -> tuple[float, float, float, int]:
    """ONE consolidated bulk RPC per owner, concurrently across owners.

    Two quantities fall out (DESIGN.md "Measured vs modeled"):
      raw   — wall latency of the slowest owner: alpha + 2*delta (injected
              RTT) + Eq. 4 payload terms (Eq. 3 straggler semantics);
      cpu   — CPU *processing* time summed over owners (initiation +
              payload + delay-inflated protocol work; Eq. 4 without the
              passive network wait) — this is what draws p_cpu_rpc and is
              the paper's dominant energy term (Section VI-B).
    Returns (raw_s, cpu_s, bytes, n_rpcs)."""
    active = per_owner_rows > 0
    if not active.any():
        return 0.0, 0.0, 0.0, 0
    payload = per_owner_rows * bytes_per_row
    per_owner_t = cm.rpc_cpu_s(
        float(params.alpha_rpc), float(params.beta), float(params.gamma_c),
        payload, delta_ms,
    )
    raw = float(np.max(np.where(
        active, per_owner_t + cm.PROP_RTT_BULK_S_PER_MS * delta_ms, 0.0
    )))
    cpu = float(np.sum(np.where(active, per_owner_t, 0.0)))
    return raw, cpu, float(payload.sum()), int(active.sum())


def _chunked_fetch_time(params, per_owner_rows: np.ndarray,
                        delta_ms: np.ndarray, bytes_per_row: float,
                        chunk: int, concurrency: int
                        ) -> tuple[float, float, float, int]:
    """Fine-grained DistTensor path (Default DGL / BGL): each owner's rows go
    as ceil(N/chunk) small RPCs with ``concurrency`` in flight, so the fixed
    initiation cost is paid ~n_chunks/Q times on the wall clock and
    n_chunks times on the CPU — the Fig. 1 regime where initiation
    dominates — plus one pipelined injected RTT."""
    active = per_owner_rows > 0
    if not active.any():
        return 0.0, 0.0, 0.0, 0
    n_chunks = np.ceil(per_owner_rows / chunk)
    payload = per_owner_rows * bytes_per_row
    payload_t = (
        float(params.beta) * payload
        + float(params.gamma_c) * payload * delta_ms
    )
    wall = (
        np.maximum(n_chunks / concurrency, 1.0) * float(params.alpha_rpc)
        + cm.PROP_RTT_CHUNKED_S_PER_MS * delta_ms  # pipelined injected RTT
        + payload_t
    )
    cpu_t = n_chunks * float(params.alpha_rpc) + payload_t
    raw = float(np.max(np.where(active, wall, 0.0)))
    cpu = float(np.sum(np.where(active, cpu_t, 0.0)))
    return raw, cpu, float(payload.sum()), int(n_chunks.sum())


def run(cfg: RunConfig, trace_bundle=None) -> RunResult:
    """Single-trainer entry point — the P=1 special case of the cluster.

    Assembles one :class:`repro.train.worker.TrainerWorker` (partition 0's
    store/cache/controller/pipeline/meter) over a single-requester fabric
    and drives its epochs in a plain loop. The multi-worker generalization
    — P workers over ONE requester-aware fabric with emergent cross-worker
    congestion and a costed gradient-sync barrier — is
    ``repro.train.cluster.run_cluster``.
    """
    from repro.net import CLOSED_FORM, build_scenario
    from repro.train.worker import TrainerWorker

    if trace_bundle is None:
        trace_bundle = build_trace(cfg)

    # ---- network substrate: event fabric (scenario) or analytic Eq. 4 ----
    fabric = None
    if cfg.scenario not in CLOSED_FORM:
        fabric = build_scenario(
            cfg.scenario, params=cfg.params, n_owners=cfg.n_parts - 1,
            seed=cfg.seed, n_epochs=cfg.n_epochs,
            steps_per_epoch=cfg.steps_per_epoch,
        )

    worker = TrainerWorker(cfg, trace_bundle, rank=0, fabric=fabric)
    try:
        for epoch in range(cfg.n_epochs):
            worker.begin_epoch(epoch)
            for step in range(cfg.steps_per_epoch):
                worker.step(epoch, step)
            worker.end_epoch(epoch)
    finally:
        # threads must not outlive the run, even on error paths
        worker.close()
    res = worker.result()
    if res.trace is not None:
        from repro.obs import build_payload, run_meta

        res.trace = build_payload(
            [res.trace],
            meta=run_meta(
                cfg,
                scenario=(
                    "closed_form" if cfg.scenario in CLOSED_FORM
                    else cfg.scenario
                ),
                n_workers=1,
            ),
        )
    return res


def _controller_stats(
    stats: CacheStats, meter: EnergyMeter, t_base: float,
    e_baseline: float | None, step: int, steps_per_epoch: int, n_owners: int,
    snapshot: dict | None = None, rebuild_stall: float = 0.0,
    headroom: float = 1.0,
) -> ctl.ControllerStats:
    """Observations over the LAST WINDOW (meter delta since ``snapshot``) —
    the same quantities the simulator's _observe emits, so the deployed
    state distribution matches training (sim-to-real, Section IV-C.2b)."""
    per_owner = (
        stats.per_owner_hit_rates()
        if stats.per_owner_hits is not None
        else np.zeros(n_owners)
    )
    if snapshot:
        d_steps = max(meter.n_steps - snapshot["n"], 1)
        t_step = (meter.wall_s - snapshot["wall"]) / d_steps
        e_step = (
            meter.gpu_j + meter.cpu_j - snapshot["energy"]
        ) / d_steps
    else:
        n = max(meter.n_steps, 1)
        t_step = meter.wall_s / n
        e_step = (meter.gpu_j + meter.cpu_j) / n
    return ctl.ControllerStats(
        owner_hit_rates=per_owner,
        global_hit_rate=stats.hit_rate(),
        t_step=t_step,
        f_rebuild=rebuild_stall / max(t_step, 1e-9),
        f_miss=max(0.0, (t_step - t_base - rebuild_stall) / max(t_step, 1e-9)),
        e_step=e_step,
        e_baseline=e_baseline if e_baseline else e_step,
        batches_remaining=1.0 - step / steps_per_epoch,
        headroom=headroom,
    )


# --------------------------------------------------------------- real model
def _init_model(graph, cfg: RunConfig):
    import jax
    import jax.numpy as jnp

    from repro import optim
    from repro.models.gnn import sage
    from repro.train.compute import sage_config

    mcfg = sage_config(graph)
    params, _ = sage.init(jax.random.PRNGKey(cfg.seed), mcfg)
    opt = optim.adamw(3e-3)

    @jax.jit
    def step(params, opt_state, x_in, blocks_flat, labels):
        def loss_fn(p):
            from repro.models.gnn.common import cross_entropy

            logits = sage.apply_blocks(p, mcfg, x_in, blocks_flat)
            return cross_entropy(logits, labels)

        l, g = jax.value_and_grad(loss_fn)(params)
        upd, new_state = opt.update(g, opt_state, params)
        return optim.apply_updates(params, upd), new_state, l

    return {
        "params": params, "opt_state": opt.init(params), "cfg": mcfg,
        "step": step, "graph": graph, "losses": [],
    }


def _model_step(state, mb):
    import jax.numpy as jnp

    graph = state["graph"]
    blocks = [
        {
            "edge_src": jnp.asarray(b.edge_src),
            "edge_dst": jnp.asarray(b.edge_dst),
            "edge_mask": jnp.asarray(b.edge_mask),
            "dst_pos": jnp.asarray(b.dst_pos),
        }
        for b in mb.blocks
    ]
    x_in = jnp.asarray(graph.features[mb.input_nodes])
    labels = jnp.asarray(graph.labels[mb.seeds])
    params, opt_state, loss = state["step"](
        state["params"], state["opt_state"], x_in, blocks, labels
    )
    state["params"], state["opt_state"] = params, opt_state
    state["losses"].append(float(loss))
    return state


def _model_eval(state, graph, n_eval: int = 2048, apply_full=None):
    """Accuracy of ``state``'s model (``apply_full``: SAGE's unless
    given) on the induced subgraph of the first ``n_eval`` nodes."""
    import jax.numpy as jnp

    from repro.models.gnn import sage
    from repro.models.gnn.common import accuracy

    apply_full = apply_full or sage.apply_full
    x = jnp.asarray(graph.features[:n_eval])
    # evaluate on the induced subgraph of the first n_eval nodes
    ei = graph.edge_index
    m = (ei[0] < n_eval) & (ei[1] < n_eval)
    logits = apply_full(
        state["params"], state["cfg"], x, jnp.asarray(ei[:, m])
    )
    return float(accuracy(logits, jnp.asarray(graph.labels[:n_eval])))
