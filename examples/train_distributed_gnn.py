"""End-to-end driver: distributed GraphSAGE training under the GreenDyGNN
pipeline — real sampled mini-batches, real jitted train steps, the adaptive
cache, energy accounting, checkpointing, and fault-tolerant restart.

    PYTHONPATH=src python examples/train_distributed_gnn.py [--epochs 8]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.train import checkpoint as ckpt
from repro.train import gnn_trainer as gt
from repro.train import policy as pol


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--dataset", default="reddit")
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--ckpt-dir", default="/tmp/greendygnn_ckpt")
    ap.add_argument(
        "--async-pipeline", action="store_true",
        help="run the real threaded cache-builder + prefetch pipeline "
             "(measured rebuild overlap) instead of the analytic model",
    )
    args = ap.parse_args()
    enable_compile_cache()

    cfg = gt.RunConfig(
        method="greendygnn", dataset=args.dataset, batch_size=2000,
        n_epochs=args.epochs, steps_per_epoch=args.steps,
        run_model=True, pad_blocks=True, congested=True,
        async_pipeline=args.async_pipeline,
    )
    print("building trace (partition + presample)...")
    bundle = gt.build_trace(cfg)

    print("calibrating simulator + loading/training the RL policy...")
    tp = pol.calibrate_table_from_bundle(bundle, cfg)
    q_fn, _ = pol.get_or_train_policy(
        pol.make_params_pool([tp]), name="qnet_example", iterations=8_000,
    )
    cfg.q_fn = q_fn

    print("training GraphSAGE under the adaptive cache pipeline...")
    result = gt.run(cfg, bundle)

    t = result.totals()
    print(f"\ntotal energy: {t['total_kj']:.2f} kJ "
          f"(gpu {t['gpu_kj']:.2f} / cpu {t['cpu_kj']:.2f})")
    print(f"mean epoch time: {result.meter.mean_epoch_time():.3f} s")
    print("per-epoch hit rate:", np.round(result.hit_rate_per_epoch, 3))
    print("per-epoch mean window:", np.round(result.window_per_epoch, 1))
    if result.accuracy_per_epoch is not None:
        print("per-epoch eval accuracy:",
              np.round(result.accuracy_per_epoch, 3))
    if result.pipeline is not None:
        rep = result.pipeline
        print(f"pipeline: {rep.n_rebuilds} rebuilds, "
              f"overlap efficiency {rep.overlap_efficiency:.1%}, "
              f"mean swap {rep.swap_latency_s * 1e6:.0f} us, "
              f"prefetch lead {rep.prefetch_mean_lead_s * 1e3:.2f} ms")

    # checkpoint the final meter state + energy trace (restartable)
    os.makedirs(args.ckpt_dir, exist_ok=True)
    import jax.numpy as jnp
    ckpt.save_checkpoint(args.ckpt_dir, args.epochs, {
        "hit_rate": jnp.asarray(result.hit_rate_per_epoch),
        "windows": jnp.asarray(result.window_per_epoch),
    })
    print(f"checkpointed to {args.ckpt_dir} "
          f"(latest step {ckpt.latest_step(args.ckpt_dir)})")


if __name__ == "__main__":
    main()
