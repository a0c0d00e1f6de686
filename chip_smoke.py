"""Chip smoke: measured GraphSAGE training on one TPU at reddit width.

    python3 chip_smoke.py [--seed 0] [--steps 4]

Drives the normal training path once — ``gnn_trainer.build_trace`` then
``gnn_trainer.run`` with ``compute="measured"`` — on a seeded power-law
graph with reddit's published shape (232,965 nodes, 602-wide features, 41
classes), the paper's batch of 2000 seeds and fan-out (10, 25). The tiered
store's device tier serves cache hits through the ``embedding_bag`` row
gather, neighbourhood aggregation runs through the ``block_spmm`` Pallas
kernel (backward pass included), and a ``greendygnn`` policy trained here
picks the rebuild windows. The weights are random, made from ``--seed``.

Checks: the Pallas aggregation is on the path, the block path matches the
scatter reference on the first batch and its gradient matches the XLA
block path on a small input, every loss is finite, the device tier
served hits, and peak device memory stays under the chip's limit. Any
failed check exits non-zero. Step times are printed as a smoke timing,
not a benchmark. The last line of standard output is one JSON object
naming the device. Without a TPU the script exits non-zero before any
result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# reddit (graph/datasets.PAPER_REDDIT): published nodes, width, classes
N_NODES = 232_965
N_FEAT = 602
N_CLASSES = 41
REDDIT_EDGES = 114_615_892
# cut: the host-side generator builds avg degree 50 instead of reddit's
# ~492, so the graph builds in seconds; the sampled batch still saturates
# the fan-out (10, 25) for almost every node
AVG_DEGREE = 50.0
GRAD_TOL = 1e-4   # float32 summation order over <= 128-wide contractions
POLICY_ITERS = 400  # a short DQN run: the smoke needs a policy, not a good one


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[chip_smoke] FAILED: {what}")
    log(f"ok: {what}")


def vjp_parity(seed: int) -> float:
    """Max |grad| difference, Pallas block_spmm VJP vs the XLA block path,
    on a small seeded input at full float32 matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.segment_mm import block_spmm_xla, to_block_sparse
    from repro.kernels.segment_mm.kernel import block_spmm_kernel

    rng = np.random.default_rng(seed)
    n_src, n_dst, n_edges, f = 2048, 640, 20_000, 128
    src = rng.integers(0, n_src // 2, n_edges)  # upper src blocks unused
    dst = rng.integers(0, n_dst, n_edges)
    rows, cols, blocks, ndb, n_src_pad = to_block_sparse(
        src, dst, n_dst, n_src
    )
    r, c, b = jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(blocks)
    x = jnp.asarray(rng.standard_normal((n_src_pad, f)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((ndb * 128, f)), jnp.float32)

    def grad(spmm):
        # tiles are jit arguments, not constants XLA would fold
        return jax.jit(jax.grad(
            lambda x, r, c, b: jnp.sum(spmm(r, c, b, x, ndb) * g)
        ))

    with jax.default_matmul_precision("highest"):
        got = grad(block_spmm_kernel)(x, r, c, b)
        want = grad(block_spmm_xla)(x, r, c, b)
    return float(jnp.max(jnp.abs(got - want)))


def run_smoke(n_nodes: int, avg_degree: float, n_feat: int, n_classes: int,
              batch_size: int, steps: int, seed: int,
              policy_iters: int) -> dict:
    """Build the graph, train a policy, run the measured trainer; returns
    the numbers the checks read."""
    import jax
    import numpy as np

    from repro.core import cost_model as cm
    from repro.graph.synthetic import power_law_graph
    from repro.kernels.segment_mm import default_interpret
    from repro.store import MemoryBudget
    from repro.train import gnn_trainer as gt
    from repro.train import policy as pol

    out: dict = {"default_interpret": default_interpret()}
    t0 = time.perf_counter()
    graph = power_law_graph(
        n_nodes=n_nodes, avg_degree=avg_degree, n_feat=n_feat,
        n_classes=n_classes, seed=seed,
    )
    log(f"graph: {graph.n_nodes} nodes, {graph.edge_index.shape[1]} edges, "
        f"{n_feat}-wide features, {n_classes} classes "
        f"({time.perf_counter() - t0:.1f} s host build)")

    cfg = gt.RunConfig(
        method="greendygnn", dataset="reddit", batch_size=batch_size,
        batch_divisor=1, fanouts=(10, 25), n_epochs=1,
        steps_per_epoch=steps, warmup_epochs=0, scenario="clean",
        mem_budget=MemoryBudget(), compute="measured", seed=seed,
    )
    t0 = time.perf_counter()
    bundle = gt.build_trace(cfg, graph=graph)
    log(f"trace: partition + presample {steps} batches "
        f"({time.perf_counter() - t0:.1f} s host)")

    # a policy trained here, never a checkpoint found on disk
    t0 = time.perf_counter()
    cfg.q_fn, _ = pol.get_or_train_policy(
        pol.make_params_pool([cm.CostModelParams()]),
        name="qnet_chip_smoke", iterations=policy_iters, force=True,
    )
    log(f"policy: {policy_iters} DQN iterations "
        f"({time.perf_counter() - t0:.1f} s)")

    t0 = time.perf_counter()
    res = gt.run(cfg, bundle)
    log(f"trainer: 1 epoch of {steps} measured steps "
        f"({time.perf_counter() - t0:.1f} s, compiles included)")
    rep = res.compute_report
    out.update(
        agg_impl=rep["agg_impl"],
        n_steps=rep["n_steps"],
        losses=rep["losses"],
        step_s=rep["step_s"],
        prep_s=rep["prep_s"],
        n_compiles=rep["n_compiles"],
        compile_s=rep["compile_s"],
        parity_max_diff=rep["parity_max_diff"],
        device_hits=res.tier_counts["device_hits"],
        windows=res.window_per_epoch.tolist(),
    )
    out["grad_max_diff"] = vjp_parity(seed)
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes"] = stats.get("peak_bytes_in_use")
    out["bytes_limit"] = stats.get("bytes_limit")
    out["finite"] = bool(np.all(np.isfinite(rep["losses"])))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args(argv)
    if args.steps < 4:
        ap.error("--steps must be at least 4")

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"[chip_smoke] needs a TPU; JAX found {device.platform!r}",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {device.device_kind} x{len(jax.devices())}")
    log(f"cut: avg degree {AVG_DEGREE:g} -> "
        f"{int(N_NODES * AVG_DEGREE)} generated edges "
        f"(reddit has {REDDIT_EDGES})")
    log(f"cut: 1 epoch of {args.steps} steps; seeded random features "
        f"and weights")

    r = run_smoke(N_NODES, AVG_DEGREE, N_FEAT, N_CLASSES, 2000, args.steps,
                  args.seed, POLICY_ITERS)

    log(f"agg_impl: {r['agg_impl']}")
    log(f"parity_max_diff (block path vs scatter reference, first batch): "
        f"{r['parity_max_diff']!r}")
    log(f"grad_max_diff (Pallas VJP vs XLA block path, small input): "
        f"{r['grad_max_diff']!r}")
    log(f"losses: {r['losses']!r}")
    log(f"n_compiles: {r['n_compiles']}  compile_s: {r['compile_s']!r}")
    log(f"step_s (smoke timing, not a benchmark): {r['step_s']!r}")
    log(f"prep_s (host build, upload and free; smoke timing): "
        f"{r['prep_s']!r}")
    log(f"device-tier hits: {r['device_hits']}")
    log(f"rebuild windows (policy): {r['windows']!r}")
    log(f"peak_bytes_in_use: {r['peak_bytes']} of {r['bytes_limit']}")

    check(not r["default_interpret"], "Pallas kernels compile (no interpreter)")
    check(r["agg_impl"] == "pallas", "aggregation through the Pallas kernel")
    check(r["n_steps"] >= 4, f"{r['n_steps']} measured steps (>= 4)")
    check(r["finite"], "every loss finite")
    check(r["parity_max_diff"] is not None and r["parity_max_diff"] <= 2e-3,
          "block path within 2e-3 of the scatter reference")
    check(r["grad_max_diff"] <= GRAD_TOL,
          f"Pallas VJP within {GRAD_TOL:g} of the XLA gradient")
    check(r["device_hits"] > 0, "device tier served hits")
    check(r["peak_bytes"] is not None and r["bytes_limit"] is not None
          and r["peak_bytes"] < r["bytes_limit"],
          "peak device memory under the chip's limit")

    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
