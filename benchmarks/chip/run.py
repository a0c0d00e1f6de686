"""Chip benchmark: measured GNN training on the program's normal path.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

Runs from the root of a checkout on a machine that holds the chips the
cell asks for, and on nothing else: without a TPU (or with fewer chips) it
exits 2 and prints no result. The cells, configurations and metrics are
those of ``BENCHMARK.json``; see ``harness.py`` for what a run does. The
last line of standard output is the result, one JSON object; the numbers
the correctness check compared, each beside its limit, are the last lines
of standard error and the last key of the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = harness.load_spec()
    plan = harness.plan(spec, args.workload, bool(args.trace))
    try:
        out = harness.run(plan, args.seed, args.seconds, bool(args.trace),
                          T_START)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    print(f"[bench] correct: {out['correct']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"[bench] check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
