"""Perf-iteration harness.

Two modes share this entry point:

SAGE measured-lane bench (default, no positional args)::

    PYTHONPATH=src python benchmarks/perf_iter.py --steps 8 --check

Runs the real jitted GraphSAGE step (``repro.train.compute``) through the
trainer's ``compute="measured"`` lane and emits
``results/bench/perf_iter.json`` with

  * per-step wall times of the compiled step (warm-up compile and host
    preparation excluded by the engine),
  * on an accelerator, the roofline terms of the compiled SAGE step
    (``repro.launch.roofline.roofline_terms`` over the AOT executable's
    cost analysis + HLO text, priced at that chip's peaks) and the
    achieved fraction of that bound (``null`` on CPU),
  * an aggregation microbenchmark — the engine's compiled block-sparse
    path vs a jitted per-edge segment-sum reference at SAGE-layer-like
    many-to-few shapes (min-of-k timing),
  * the modeled-vs-measured energy delta after ``calibrate_compute``
    refits ``t_base`` from the measured samples.

``--check`` turns the bench into a gate: the block path must not lose to
the segment-sum reference at any benchmark shape, and re-running the
modeled lane with the calibrated ``t_base`` must land within tolerance
of the measured run's compute energy.

Legacy cell-variant mode (positional args, unchanged)::

    PYTHONPATH=src python benchmarks/perf_iter.py <cell> <variant>

re-lowers a launch cell under config/rule variants and reports the
roofline-term deltas (the hypothesis -> change -> measure loop).
"""
import os
import sys

_LEGACY = len(sys.argv) > 1 and not sys.argv[1].startswith("-")
if _LEGACY:
    # the cell variants lower against a production mesh of virtual hosts;
    # the SAGE bench times real compute and must NOT fragment the CPU
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse
import dataclasses
import json
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "perf")
BENCH_JSON = os.path.join(
    os.path.dirname(__file__), "..", "results", "bench", "perf_iter.json"
)


# ---------------------------------------------------------------------------
# SAGE measured-lane bench
# ---------------------------------------------------------------------------

# many-to-few SAGE-layer-like aggregation shapes: (n_dst, n_src, n_edges,
# n_feat) — dense enough per 128x128 block that the block-matmul path is
# the right algorithm, which is exactly the regime the engine runs in
AGG_SHAPES = (
    (256, 2048, 120_000, 64),
    (512, 4096, 400_000, 128),
)
_TIMING_REPS = 5


def _time_compiled(fn, *args) -> float:
    """min-of-k wall time of an already-warm jitted callable [s]."""
    best = float("inf")
    for _ in range(_TIMING_REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def bench_aggregation(tile: int = 128) -> list[dict]:
    """Engine block path vs jitted per-edge segment-sum reference."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.segment_mm import block_spmm_xla, to_block_sparse

    from functools import partial

    @partial(jax.jit, static_argnames=("n_dst",))
    def _ref(x, src, dst, w, n_dst):
        return jax.ops.segment_sum(
            x[src] * w[:, None], dst, num_segments=n_dst
        )

    out = []
    for n_dst, n_src, n_edges, n_feat in AGG_SHAPES:
        rng = np.random.default_rng(0)
        src = rng.integers(0, n_src, n_edges).astype(np.int32)
        dst = rng.integers(0, n_dst, n_edges).astype(np.int32)
        x = rng.standard_normal((n_src, n_feat)).astype(np.float32)
        w = np.ones(n_edges, np.float32)

        rows, cols, blocks, ndb, n_src_pad = to_block_sparse(
            src, dst, n_dst, n_src, tile, tile, edge_weight=w
        )
        x_pad = np.zeros((n_src_pad, n_feat), np.float32)
        x_pad[:n_src] = x
        rows_j, cols_j = jnp.asarray(rows), jnp.asarray(cols)
        blocks_j, x_j = jnp.asarray(blocks), jnp.asarray(x_pad)

        def _block(r, c, b, xp, ndb=ndb):
            return block_spmm_xla(r, c, b, xp, ndb, tile, tile)

        # warm both once, assert parity, then time
        y_block = np.asarray(_block(rows_j, cols_j, blocks_j, x_j))[:n_dst]
        srj, dsj = jnp.asarray(src), jnp.asarray(dst)
        wj, xj = jnp.asarray(w), jnp.asarray(x)
        y_ref = np.asarray(_ref(xj, srj, dsj, wj, n_dst))
        max_diff = float(np.max(np.abs(y_block - y_ref)))
        scale = float(np.max(np.abs(y_ref))) or 1.0
        block_s = _time_compiled(_block, rows_j, cols_j, blocks_j, x_j)
        ref_s = _time_compiled(_ref, xj, srj, dsj, wj, n_dst)
        out.append({
            "shape": [n_dst, n_src, n_edges, n_feat],
            "block_ms": round(block_s * 1e3, 4),
            "segment_sum_ms": round(ref_s * 1e3, 4),
            "speedup": round(ref_s / block_s, 3),
            "rel_diff": max_diff / scale,
        })
    return out


def bench_sage(args) -> dict:
    import numpy as np

    from repro.core import calibration as cal
    from repro.launch import roofline as rl
    from repro.train import gnn_trainer as gt
    from repro.train.compute import ComputeEngine

    cfg = gt.RunConfig(
        method="static_w", dataset=args.dataset, batch_size=args.batch,
        n_epochs=1, steps_per_epoch=args.steps, scenario="clean",
        seed=args.seed, compute="measured",
        grad_compression=args.grad_compression,
    )
    bundle = gt.build_trace(cfg)

    # measured lane end to end: the engine's wall times feed the meter
    res_meas = gt.run(cfg, bundle)
    rep = res_meas.compute_report
    step_s = np.asarray(rep["step_s"], np.float64)
    edges = np.asarray(rep["step_edges"], np.float64)

    # refit t_base from what the meter charged (host preparation plus the
    # compiled step), replay the modeled lane
    charged_s = step_s + np.asarray(rep["prep_s"], np.float64)
    params_cal, fit = cal.calibrate_compute(edges, charged_s)
    cfg_mod = dataclasses.replace(
        cfg, compute="modeled",
        params=dataclasses.replace(cfg.params, t_base=params_cal.t_base),
    )
    res_mod = gt.run(cfg_mod, bundle)
    gpu_meas = float(res_meas.meter.gpu_j)
    gpu_mod = float(res_mod.meter.gpu_j)
    energy_delta = abs(gpu_meas - gpu_mod) / max(gpu_mod, 1e-12)

    mean_step = float(step_s.mean())
    roofline = None
    dev = jax.devices()[0]
    if dev.platform != "cpu":
        # roofline of the compiled step, priced at the running chip's own
        # peaks (a CPU wall time is no device metric, so a CPU run records
        # none): one standalone engine, one step, then the AOT
        # executable's cost analysis + HLO text
        graph, _owner, _traces, mbs = bundle
        eng = ComputeEngine(graph, cfg)
        mb = mbs[0][0]
        eng.step(
            mb, np.asarray(graph.features[mb.input_nodes], np.float32)
        )
        exe = next(iter(eng._exec.values()))
        cost = exe.cost_analysis()
        if not isinstance(cost, dict):
            cost = cost[0]
        terms = rl.roofline_terms(cost, exe.as_text(), 1.0,
                                  device_kind=dev.device_kind)
        bound_s = max(
            terms["compute_s"], terms["memory_s"], terms["collective_s"]
        )
        roofline = {
            "compute_s": terms["compute_s"],
            "memory_s": terms["memory_s"],
            "collective_s": terms["collective_s"],
            "dominant": terms["dominant"],
            "roofline_fraction": round(terms["roofline_fraction"], 4),
            "bound_s": bound_s,
            # wall time over the ideal-hardware bound: -> 1 as the step
            # approaches the chip's roofline
            "achieved_over_bound": round(mean_step / max(bound_s, 1e-12), 2),
        }

    agg = bench_aggregation()

    return {
        "backend": jax.default_backend(),
        "device_kind": dev.device_kind,
        "agg_impl": rep["agg_impl"],
        "grad_compression": rep["grad_compression"],
        "sync_wire_bytes": rep["sync_wire_bytes"],
        "steps": int(rep["n_steps"]),
        "step_wall_s": [round(float(t), 6) for t in step_s],
        "mean_step_s": round(mean_step, 6),
        "min_step_s": round(float(step_s.min()), 6),
        "compile_s": round(float(rep["compile_s"]), 3),
        "parity_max_diff": rep["parity_max_diff"],
        "roofline": roofline,
        "energy": {
            "measured_gpu_j": gpu_meas,
            "modeled_gpu_j_calibrated": gpu_mod,
            "rel_delta": energy_delta,
            "t_base_calibrated_s": float(params_cal.t_base),
            "fit_r2": float(fit.r2),
        },
        "aggregation": agg,
    }


def run_checks(rec: dict, tol_energy: float = 0.05) -> bool:
    ok = True
    for row in rec["aggregation"]:
        good = row["block_ms"] <= row["segment_sum_ms"]
        parity = row["rel_diff"] <= 1e-4
        status = "OK " if (good and parity) else "FAIL"
        print(f"[perf_iter] {status} agg {tuple(row['shape'])}: "
              f"block {row['block_ms']:.3f} ms vs segment-sum "
              f"{row['segment_sum_ms']:.3f} ms "
              f"(x{row['speedup']:.2f}, rel diff {row['rel_diff']:.1e})")
        ok &= good and parity
    delta = rec["energy"]["rel_delta"]
    e_ok = delta <= tol_energy
    print(f"[perf_iter] {'OK ' if e_ok else 'FAIL'} energy: "
          f"measured {rec['energy']['measured_gpu_j']:.3f} J vs "
          f"calibrated-modeled "
          f"{rec['energy']['modeled_gpu_j_calibrated']:.3f} J "
          f"(rel delta {delta:.2e} <= {tol_energy})")
    ok &= e_ok
    return ok


def sage_main(argv) -> int:
    p = argparse.ArgumentParser(description="SAGE measured-lane bench")
    p.add_argument("--steps", type=int, default=16)
    p.add_argument("--batch", type=int, default=600)
    p.add_argument("--dataset", default="reddit")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--grad-compression", default="none",
                   choices=("none", "int8", "topk"))
    p.add_argument("--check", action="store_true",
                   help="gate: block path <= segment-sum reference and "
                        "modeled-vs-measured energy within tolerance")
    p.add_argument("--json", default=BENCH_JSON,
                   help="output path (default results/bench/perf_iter.json)")
    args = p.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    rec = bench_sage(args)
    os.makedirs(os.path.dirname(args.json), exist_ok=True)
    with open(args.json, "w") as f:
        json.dump(rec, f, indent=1)
        f.write("\n")
    print(f"[perf_iter] wrote {os.path.relpath(args.json)}")
    print(json.dumps({k: rec[k] for k in
                      ("backend", "agg_impl", "mean_step_s", "compile_s")}))
    if args.check:
        return 0 if run_checks(rec) else 1
    return 0


# ---------------------------------------------------------------------------
# Legacy cell-variant mode
# ---------------------------------------------------------------------------

def measure(arch_id, shape, config_patch=None, rule_patch=None, label="base"):
    from repro.configs.registry import get_arch
    from repro.launch import roofline as rl
    from repro.launch.cell import build_cell
    from repro.launch.mesh import make_production_mesh

    arch = get_arch(arch_id)
    if config_patch:
        base_make = arch.make_config

        def patched(*a, **k):
            return dataclasses.replace(base_make(*a, **k), **config_patch)

        arch = dataclasses.replace(arch, make_config=patched)
    if rule_patch:
        arch = dataclasses.replace(
            arch, rule_overrides={**arch.rule_overrides, **rule_patch}
        )
    mesh = make_production_mesh(multi_pod=False)
    cell = build_cell(arch, shape, mesh)
    t0 = time.time()
    compiled = (
        jax.jit(cell["step_fn"], in_shardings=cell["in_shardings"])
        .lower(*cell["args"]).compile()
    )
    cost = compiled.cost_analysis()
    if not isinstance(cost, dict):
        cost = cost[0]
    factor = rl.loop_factor(arch_id, shape)
    if config_patch and "grad_accum" in config_patch:
        cfg = arch.make_config()
        factor = max(cfg.n_scan_layers, 1) * config_patch["grad_accum"]
    terms = rl.roofline_terms(cost, compiled.as_text(), factor)
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 1e9
    rec = {
        "cell": f"{arch_id}/{shape}", "variant": label,
        "compile_s": round(time.time() - t0, 1),
        "peak_gb": round(peak, 2),
        "compute_s": terms["compute_s"],
        "memory_s": terms["memory_s"],
        "collective_s": terms["collective_s"],
        "dominant": terms["dominant"],
        "fraction": round(terms["roofline_fraction"], 4),
        "collectives": {k: round(v / 1e9, 2)
                        for k, v in terms["collective_breakdown"].items()},
    }
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(
            RESULTS, f"{arch_id}__{shape}__{label}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec))
    return rec


VARIANTS = {
    # --- cell A: deepseek-v2-236b x train_4k (most collective-bound) ------
    ("deepseek-v2-236b", "train_4k"): {
        "base": ({}, {}),
        # H1: grad-accum re-gathers FSDP-sharded weights once per microbatch
        #     -> fewer microbatches cut weight all-gathers ~4x (memory peak
        #     rises with the bigger microbatch)
        "accum2": ({"grad_accum": 2}, {}),
        # H2: ZeRO-1 for expert weights: keep them replicated across data
        #     (sharded over experts/model only) -> no per-use all-gather at
        #     all; optimizer state grows per device
        "zero1": ({"grad_accum": 2}, {"embed_rows": None}),
        # H3: bigger attention KV blocks -> fewer scan steps re-reading Q
        "blockk4096": ({"grad_accum": 2, "attn_block_k": 4096}, {}),
    },
    # --- cell B: nequip x ogb_products (paper-domain, collective-bound) ---
    ("nequip", "ogb_products"): {
        "base": ({}, {}),
        # H1: node features gathered across ALL axes per edge chunk; keep
        #     node arrays sharded over data only -> model-axis gathers vanish
        "nodes_data_only": ({}, {"nodes": "data"}),
        # H2: bigger edge chunks -> fewer scan iterations (less re-gather),
        #     more VMEM per chunk
        "chunk2m": ("edge_chunk_2m", {}),
        # H3: combine both
        "combined": ("edge_chunk_2m", {"nodes": "data"}),
    },
    # --- cell C: qwen3-1.7b x train_4k (baseline best fraction) -----------
    ("qwen3-1.7b", "train_4k"): {
        "base": ({}, {}),
        # H1: the 1.7B weights fit per-device: drop FSDP (replicate rows
        #     over data) -> param all-gathers vanish, grad all-reduce stays
        "replicated": ({}, {"embed_rows": None}),
        # H2: no microbatching (batch fits once FSDP gathers are gone)
        "accum1": ({"grad_accum": 1}, {"embed_rows": None}),
        # H3: coarser CE chunks -> fewer lm-head passes
        "chunk2048": ({"grad_accum": 1, "loss_chunk": 2048},
                      {"embed_rows": None}),
    },
}


def legacy_main():
    cell = (sys.argv[1], sys.argv[2])
    variants = VARIANTS[cell]
    which = sys.argv[3:] or list(variants)
    for label in which:
        patch, rules = variants[label]
        if patch == "edge_chunk_2m":
            patch = {"edge_chunk": 2_097_152}
        measure(cell[0], cell[1], patch, rules, label)


if __name__ == "__main__":
    if _LEGACY:
        legacy_main()
    else:
        sys.exit(sage_main(sys.argv[1:]))
