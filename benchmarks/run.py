"""Benchmark driver — one module per paper table/figure.

Prints ``name,value,derived`` CSV rows per benchmark plus a claims summary.
A module that raises prints an ``<name>/ERROR`` row, the other modules
still run, and the driver exits non-zero.
"""
from __future__ import annotations

import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> int:
    from benchmarks import (
        fig1_rpc_energy,
        fig5_overhead,
        fig6_clean,
        fig7_adaptation,
        fig8_sim_validation,
        fig9_cumulative,
        pipeline_overlap,
        roofline_report,
        table1_energy,
        table2_ablation,
    )

    modules = [
        ("fig1_rpc_energy", fig1_rpc_energy),
        ("table1_energy", table1_energy),
        ("fig5_overhead", fig5_overhead),
        ("fig6_clean", fig6_clean),
        ("fig7_adaptation", fig7_adaptation),
        ("fig8_sim_validation", fig8_sim_validation),
        ("fig9_cumulative", fig9_cumulative),
        ("table2_ablation", table2_ablation),
        ("pipeline_overlap", pipeline_overlap),
        ("roofline_report", roofline_report),
    ]
    print("name,value,derived")
    failed = []
    for name, mod in modules:
        t0 = time.time()
        try:
            rows = mod.main()
        except Exception as e:  # noqa: BLE001 — report it, run the rest
            traceback.print_exc()
            rows = [f"{name}/ERROR,{type(e).__name__},{e}"]
            failed.append(name)
        for row in rows:
            print(row, flush=True)
        print(f"{name}/wall_s,{time.time() - t0:.1f},", flush=True)
    if failed:
        print(f"failed: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
