"""One run of one cell: set-up, the measured window, the check, the metrics.

The harness drives the program's normal training path and reimplements no
layer of it: one ``TrainerWorker`` built as ``gnn_trainer.run`` builds it
(the fabric from ``build_scenario``, ``compute="measured"``, the tiered
store with its device tier), stepped through ``begin_epoch``, ``step`` and
``end_epoch`` by the harness so that it can time the window.

Everything that belongs to one configuration, model, traffic mix or
metric is a file found by its name in ``BENCHMARK.json`` or in the
configuration:

- ``configs/<config>.json``: sizes, lane settings, pinned cost-model
  parameters and the limits of the check;
- ``models/<arch>.py``, named by the configuration's ``model.arch``: the
  model's part of the yardstick. ``validate(config)`` refuses settings
  its reference does not implement; ``program_options(config)`` gives the
  ``RunConfig`` arguments that select the model in the program;
  ``init_params(seed, config)`` the weights handed to the program, laid
  out as the program's tree; ``forward(params, x, blocks, control=False)``
  the plain reference forward (float32 at ``HIGHEST``, ``control``: three
  bf16 passes); ``model_flops(layers, config)`` and
  ``kernel_calls(layers, config) -> {kernel: [{"flops", "bytes"}, ...]}``
  the algorithm's work from a batch's true counts;
- ``traffic/<traffic>.json``: scenario, horizon, first measured epoch,
  steps per epoch, locality of the seeds;
- ``metrics/<metric>.py``: a reader ``read(run) -> float | None`` over the
  run record this module builds (``None``: nothing to read there, and the
  metric is left out of the line).

So a new model is additions only: ``models/<arch>.py``, its configuration
file, its readers and its entries in ``BENCHMARK.json``.

A run: the fixtures (cached), one epoch presampled from ``--seed``, the
worker with the benchmark's weights, then a rehearsal in the epoch before
the first measured one: the checked steps, and more while they last less
than the window (so that the window replays batches whose shapes are
compiled). The window replays the same epoch from ``start_epoch`` on and
runs whole steps until the first that ends at or after ``--seconds``. Then
the program's state is freed, the epoch's sampled blocks are checked
against the fixture graph, and the plain reference follows the checked
steps.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time

import numpy as np

import fixtures
import reference
import work
import xtrace

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
TRACE_DIR = os.path.join(fixtures.CACHE_ROOT, "trace")
# the rehearsal runs steps until as many steps, at the mean wall time of
# those after its first (which compiles), last this share of --seconds:
# the window replays the same batches, so it finds each one compiled
# unless its steps run a quarter faster than the rehearsal's
REHEARSAL_MARGIN = 1.25
# steps the reference follows: the first gradient is read from the
# optimizer's first moment after step 1, the parameters after the last
CHECK_STEPS = 2


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ spec
def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(*parts) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def plan(spec: dict, workload: str, trace: bool) -> dict:
    """The cell, its configuration and traffic files, and its metrics;
    exits on an unknown cell or model before any device work."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = _read_json("traffic", f"{cell['traffic']}.json")
    model(config)
    return {"cell": cell, "config": config, "traffic": traffic,
            "metrics": metrics_for(spec, workload, trace)}


def metrics_for(spec: dict, workload: str, trace: bool) -> list[dict]:
    """The end-to-end metrics (``trace`` off) or the per-layer metrics
    (``trace`` on) whose readers the run calls; a reader that finds nothing
    to read in this cell returns ``None``."""
    return spec["per_layer"] if trace else spec["end_to_end"]


def _load(kind: str, name: str):
    """The module ``<kind>/<name>.py``."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """``metrics/<name>.py``'s ``read``."""
    return _load("metrics", name).read


def known_models() -> list[str]:
    """The ``arch`` names that have a module under ``models/``."""
    names = os.listdir(os.path.join(BENCH_DIR, "models"))
    return sorted(f[:-3] for f in names if f.endswith(".py"))


def model(config: dict):
    """``models/<arch>.py`` of the configuration's ``model.arch``, which
    has validated the configuration's model settings."""
    arch = config["model"]["arch"]
    if arch not in known_models():
        raise SystemExit(f"unknown model {arch!r} in configuration "
                         f"{config['name']!r}; known: {known_models()}")
    mod = _load("models", arch)
    try:
        mod.validate(config)
    except ValueError as e:
        raise SystemExit(f"configuration {config['name']!r}: {e}") from None
    return mod


# ----------------------------------------------------------------- device
def device_info(chips: int, require_chip: bool) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    log(f"device: {info['platform']} {info['kind']!r} x{info['count']}")
    if require_chip and (dev.platform != "tpu" or len(devices) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {dev.platform} device(s)")
    return info


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


# ---------------------------------------------------------------- program
def program_config(config: dict, traffic: dict, seed: int, q_fn, params):
    """The program's ``RunConfig`` for this cell and seed."""
    from repro.store import MemoryBudget
    from repro.train import gnn_trainer as gt

    tr, lane = config["training"], config["lane"]
    return gt.RunConfig(
        method=lane["method"], dataset=config["name"],
        batch_size=tr["batch_size"], batch_divisor=1,
        fanouts=tuple(tr["fanouts"]), n_parts=tr["n_parts"],
        n_epochs=traffic["horizon_epochs"],
        steps_per_epoch=traffic["steps_per_epoch"],
        warmup_epochs=lane["warmup_epochs"], cache_frac=lane["cache_frac"],
        scenario=traffic["scenario"], locality_frac=traffic["locality_frac"],
        mem_budget=MemoryBudget(device_payloads=lane["device_payloads"]),
        async_pipeline=lane["async_pipeline"], compute="measured",
        seed=seed, params=params, q_fn=q_fn,
        **model(config).program_options(config),
    )


def presample(cfg, graph, owner) -> list:
    """One epoch of minibatches, presampled from ``cfg.seed`` by the
    program's ``build_trace``."""
    import dataclasses

    from repro.train import gnn_trainer as gt

    one = dataclasses.replace(cfg, n_epochs=1)
    return gt.build_trace(one, graph=graph, owner=owner)[3][0]


def build_worker(cfg, graph, owner):
    """Presample one epoch and build the worker over it, every epoch index
    replaying that epoch."""
    from repro.net import CLOSED_FORM, build_scenario
    from repro.train.worker import TrainerWorker

    mbs = presample(cfg, graph, owner)
    traces = [mb.input_nodes for mb in mbs]
    bundle = (graph, owner, [traces] * cfg.n_epochs, [mbs] * cfg.n_epochs)
    fabric = None
    if cfg.scenario not in CLOSED_FORM:
        fabric = build_scenario(
            cfg.scenario, params=cfg.params, n_owners=cfg.n_parts - 1,
            seed=cfg.seed, n_epochs=cfg.n_epochs,
            steps_per_epoch=cfg.steps_per_epoch,
        )
    return TrainerWorker(cfg, bundle, rank=0, fabric=fabric), mbs


def batch_layers(mb) -> list[dict]:
    """True node and edge counts of a batch's layers (input layer first)."""
    return [{"n_src": int(len(b.src_nodes)), "n_dst": int(len(b.dst_nodes)),
             "n_edges": int(np.asarray(b.edge_mask).sum())}
            for b in mb.blocks]


class Stepper:
    """Times ``worker.step`` on the host clock and reads the program's
    spans and counters around it."""

    def __init__(self, worker, epoch_mbs):
        self.w = worker
        self.layers = [batch_layers(mb) for mb in epoch_mbs]
        self.seeds = [int(np.asarray(mb.blocks[-1].dst_mask).sum())
                      for mb in epoch_mbs]

    def _counters(self) -> dict:
        w = self.w
        tiers = getattr(w.store, "tier_stats", None)
        return {
            "energy_j": w.meter.gpu_j + w.meter.cpu_j,
            "remote_bytes": w.meter.remote_bytes,
            "device_hits": getattr(tiers, "device_hits", 0),
            "rows_gathered": getattr(w.device, "rows_gathered", 0),
            "compiles": w.engine.n_compiles,
            "n_spans": len(w.engine.step_s),
        }

    def step(self, epoch: int, s: int) -> dict:
        import jax

        w = self.w
        before = self._counters()
        with jax.profiler.TraceAnnotation("bench.step"):
            t0 = time.perf_counter()
            w.step(epoch, s)
            wall = time.perf_counter() - t0
        after = self._counters()
        eng = w.engine
        timed = after["n_spans"] > before["n_spans"]
        return {
            "epoch": epoch, "step": s, "wall_s": wall,
            "prep_s": eng.prep_s[-1] if timed else None,
            "step_s": eng.step_s[-1] if timed else None,
            "loss": eng.losses[-1] if timed else None,
            "seeds": self.seeds[s],
            "remote_rows": int(w.step_hits[-1] + w.step_misses[-1]),
            "layers": self.layers[s],
            **{k: after[k] - before[k] for k in
               ("energy_j", "remote_bytes", "device_hits", "rows_gathered",
                "compiles")},
        }


def _annotated(name: str, fn, *args):
    import jax

    with jax.profiler.TraceAnnotation(f"bench.{name}"):
        return fn(*args)


def _to_host(tree):
    import jax

    return jax.tree.map(lambda a: np.array(a, np.float64), tree)


def inject_weights(engine, params0, arch: str) -> None:
    """Hand the benchmark's weights (of model ``arch``) to the program's
    step state."""
    import jax

    own = engine.params
    same = jax.tree.structure(own) == jax.tree.structure(params0) and all(
        a.shape == b.shape for a, b in
        zip(jax.tree.leaves(own), jax.tree.leaves(params0)))
    if not same:
        raise RuntimeError(f"the program's parameters are not laid out as "
                           f"{arch}'s, as the configuration states")
    engine.params = params0


# -------------------------------------------------------------------- run
def run(plan_: dict, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True) -> dict:
    """One run; returns the result line as a dict."""
    config, traffic, cell = plan_["config"], plan_["traffic"], plan_["cell"]
    mod = model(config)
    dev_info = device_info(cell.get("chips", 1), require_chip)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["REPRO_ARTIFACTS"] = fixtures.cache_dir(config)
    import jax

    from repro.core.cost_model import CostModelParams
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peak = (work.peaks(dev_info["kind"]) if dev_info["platform"] != "cpu"
            else None)

    arrays = fixtures.graph_arrays(config, log=log)
    graph = fixtures.program_graph(arrays)
    params = CostModelParams(**config["cost_model"])
    q_fn = fixtures.policy(config, params, log=log)
    cfg = program_config(config, traffic, seed, q_fn, params)
    worker, epoch_mbs = build_worker(cfg, graph, arrays["owner"])
    params0 = mod.init_params(seed, config)
    n_check = CHECK_STEPS
    spe, start = traffic["steps_per_epoch"], traffic["start_epoch"]
    stepper = Stepper(worker, epoch_mbs)
    obs = {"x": [], "x_want": [], "losses": []}
    try:
        engine = worker.engine
        inject_weights(engine, params0, config["model"]["arch"])
        # the engine's own first-step parity check (its forward against an
        # unjitted reference, compiled op by op at each new batch's exact
        # sizes) is skipped: the check below covers that forward and more,
        # and set-up then does not depend on whether the compile cache has
        # seen this seed's batches
        engine.parity_max_diff = float("nan")
        real_step = engine.step

        def observed(mb, x_in):
            obs["x"].append(np.array(x_in, np.float32))
            obs["x_want"].append(arrays["features"][
                np.asarray(mb.input_nodes, np.int64)])
            return real_step(mb, x_in)

        # rehearsal: the checked steps, then more while they fit the window
        engine.step = observed
        _annotated("begin_epoch", worker.begin_epoch, start - 1)
        rehearsal, k = [], 0
        while k < spe and (k < n_check or k * np.mean(
                [r["wall_s"] for r in rehearsal[1:]])
                < REHEARSAL_MARGIN * seconds):
            rehearsal.append(stepper.step(start - 1, k))
            if k < n_check:
                obs["losses"].append(rehearsal[-1]["loss"])
            if k == 0:
                obs["mu"] = _to_host(engine.opt_state.mu)
            if k == n_check - 1:
                obs["params"] = _to_host(engine.params)
                del engine.step
            k += 1
        _annotated("end_epoch", worker.end_epoch, start - 1)
        log(f"rehearsal: {k} steps, wall {[r['wall_s'] for r in rehearsal]}")

        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        steps, truncated = [], False
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        with jax.profiler.TraceAnnotation("bench.window"):
            epoch, s = start, 0
            _annotated("begin_epoch", worker.begin_epoch, epoch)
            while True:
                steps.append(stepper.step(epoch, s))
                s += 1
                if time.perf_counter() - t0 >= seconds:
                    break
                if s == spe:
                    _annotated("end_epoch", worker.end_epoch, epoch)
                    epoch, s = epoch + 1, 0
                    if epoch >= traffic["horizon_epochs"]:
                        truncated = True
                        break
                    _annotated("begin_epoch", worker.begin_epoch, epoch)
        window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        mem_peak = _memory_peak(jax.devices())
    finally:
        worker.close()
    compiles = sum(r["compiles"] for r in steps)
    log(f"window: {window_s!r} s, {len(steps)} steps, {compiles} compiles "
        f"inside{', horizon reached' if truncated else ''}")
    log(f"window steps wall_s: {[r['wall_s'] for r in steps]!r}")
    log(f"window steps prep_s: {[r['prep_s'] for r in steps]!r}")
    log(f"window steps step_s: {[r['step_s'] for r in steps]!r}")
    log(f"setup_s: {setup_s!r}; peak device memory {mem_peak} bytes")

    del worker, engine, stepper, real_step, observed
    gc.collect()

    for r in steps:
        r["model_flops"] = mod.model_flops(r["layers"], config)
        r["kernel_calls"] = mod.kernel_calls(r["layers"], config)
    run_rec = {
        "setup_s": setup_s, "window_s": window_s, "steps": steps,
        "n_feat": config["graph"]["n_feat"], "peaks": peak,
        "memory_peak_bytes": mem_peak, "trace": None,
    }
    if trace:
        path = xtrace.find_xplane(TRACE_DIR)
        run_rec["trace"] = (xtrace.reduce(xtrace.read_events(path))
                            if path else None)
    checks = check(config, mod.forward, epoch_mbs, arrays, params0, obs)
    return result(plan_, run_rec, dev_info, checks)


def check(config, forward, epoch_mbs, arrays, params0, obs) -> dict:
    """The epoch's sampled blocks against the fixture graph, and the plain
    reference (the model's ``forward``) through the checked steps; each
    number beside its limit."""
    t0 = time.perf_counter()
    in_edges = reference.edge_keys(arrays["indptr"], arrays["indices"])
    fanouts = config["training"]["fanouts"]
    block_faults = sum(
        reference.block_faults(mb, arrays["indptr"], in_edges, fanouts)
        for mb in epoch_mbs)
    batches = [reference.batch_arrays(mb, arrays["features"],
                                      arrays["labels"])
               for mb in epoch_mbs[:CHECK_STEPS]]
    opt = config["training"]["optimizer"]
    ref = reference.train(forward, params0, batches, opt)
    numbers = {"blocks": float(block_faults),
               **reference.compare(obs, ref, params0, opt["b1"])}
    log(f"check: {len(epoch_mbs)} batches' blocks and {len(batches)} "
        f"reference steps in {time.perf_counter() - t0:.1f} s; losses "
        f"{ref['losses']!r}, program {obs['losses']!r}; widest update_norm "
        f"leaf {numbers.pop('update_leaf')}")
    limits = config["limits"]
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}


def result(plan_: dict, run_rec: dict, dev_info: dict, checks: dict) -> dict:
    metrics = {}
    for m in plan_["metrics"]:
        value = reader(m["name"])(run_rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    losses = [r["loss"] for r in run_rec["steps"]]
    failed = sum(1 for x in losses if x is None or not math.isfinite(x))
    device = dict(dev_info, memory_peak_bytes=run_rec["memory_peak_bytes"])
    out = {"correct": None, "attempted": len(losses), "failed": failed,
           "metrics": metrics, "device": device}
    tr = run_rec["trace"]
    if tr is not None:
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out["breakdown"] = xtrace.breakdown(tr)
    ok = failed == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    out["correct"] = bool(ok)
    out["checks"] = checks
    return out
