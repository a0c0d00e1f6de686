"""Upper readings of the correctness check: the control and planted faults.

    python3 benchmarks/chip/readings.py --workload <cell> --seeds 1,2,3

For each seed it presamples the cell's epoch as a run does, makes the
run's weights with the configuration's model module, and puts in the
program's place, at the cell's own sizes:

- ``control``: the reference (the model's ``forward``) with every matmul
  and the aggregation's inputs at three bf16 passes (``high``), the
  precision below the configuration's ``highest``;
- ``unchanged``: a step that returns its state unchanged;
- ``half_batch``: half of the batch's seeds left out, the mean taken over
  the rest;
- ``altered_row``: one feature row altered where the step reads it.

It prints the numbers ``reference.compare`` gives for each, beside the
configuration's limits, one JSON line per seed and a last line with the
smallest reading of each number over the seeds. The benchmark's own runs
do not run this; the lower readings are the numbers they print.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

import fixtures
import harness
import reference

FAULTS = ("control", "unchanged", "half_batch", "altered_row")


def planted(kind: str, forward, params0, batches: list[dict], opt: dict
            ) -> dict:
    """The observations a program with fault ``kind`` would give, the
    reference following the model's ``forward``."""
    import jax

    b1 = opt["b1"]
    x_want = [b["x"][: b["n_input"]] for b in batches]
    if kind == "unchanged":
        zeros = jax.tree.map(lambda p: np.zeros(p.shape), params0)
        ref = reference.train(forward, params0, batches, opt)
        return {"x": x_want, "x_want": x_want, "losses": ref["losses"],
                "mu": zeros, "params": harness._to_host(params0)}
    if kind == "half_batch":
        batches = [dict(b, mask=b["mask"] * (np.arange(len(b["mask"]))
                                             < len(b["mask"]) // 2))
                   for b in batches]
    if kind == "altered_row":
        batches = [dict(b, x=b["x"].copy()) for b in batches]
        for b in batches:
            b["x"][0] += 1.0
    x_got = [b["x"][: b["n_input"]] for b in batches]
    out = reference.train(forward, params0, batches, opt,
                          control=kind == "control")
    return {"x": x_got if kind == "altered_row" else x_want,
            "x_want": x_want,
            "losses": out["losses"],
            "mu": jax.tree.map(lambda g: g * (1 - b1), out["grad"]),
            "params": out["params"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    plan = harness.plan(harness.load_spec(), args.workload, False)
    config, traffic = plan["config"], plan["traffic"]
    mod = harness.model(config)
    harness.device_info(plan["cell"].get("chips", 1), require_chip=True)

    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    os.environ["REPRO_ARTIFACTS"] = fixtures.cache_dir(config)
    from repro.core.cost_model import CostModelParams
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    arrays = fixtures.graph_arrays(config, log=harness.log)
    graph = fixtures.program_graph(arrays)
    params = CostModelParams(**config["cost_model"])
    opt = config["training"]["optimizer"]
    lowest: dict = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        cfg = harness.program_config(config, traffic, seed, None, params)
        mbs = harness.presample(cfg, graph, arrays["owner"])
        batches = [reference.batch_arrays(mb, arrays["features"],
                                          arrays["labels"])
                   for mb in mbs[: harness.CHECK_STEPS]]
        params0 = mod.init_params(seed, config)
        ref = reference.train(mod.forward, params0, batches, opt)
        line = {"seed": seed}
        for kind in FAULTS:
            obs = planted(kind, mod.forward, params0, batches, opt)
            nums = reference.compare(obs, ref, params0, opt["b1"])
            nums.pop("update_leaf")
            line[kind] = nums
            for k, v in nums.items():
                key = (kind, k)
                lowest[key] = min(lowest.get(key, np.inf), v)
        print(json.dumps(line), flush=True)
    print(json.dumps({"lowest": {f"{a}.{b}": v for (a, b), v in
                                 lowest.items()},
                      "limits": config["limits"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
