"""Paired same-seed determinism harness (the dynamic twin of greenlint).

Runs the same configuration twice in-process and asserts the two runs are
bit-identical via :mod:`repro.analysis.digest` — the exact property the
static determinism rules (no wall clock, no global RNG, no env branches in
sim paths) exist to protect. Seven targets:

    PYTHONPATH=src python scripts/check_determinism.py trainer
    PYTHONPATH=src python scripts/check_determinism.py cluster --workers 2
    PYTHONPATH=src python scripts/check_determinism.py store
    PYTHONPATH=src python scripts/check_determinism.py compute
    PYTHONPATH=src python scripts/check_determinism.py trace --workers 4
    PYTHONPATH=src python scripts/check_determinism.py twins
    PYTHONPATH=src python scripts/check_determinism.py all

``trainer`` pairs the legacy single-rank ``gnn_trainer.run``; ``cluster``
pairs ``run_cluster`` at P workers (thread scheduling varies between the
two runs, so a match also certifies the virtual-time release order).
``store`` pairs a run under a TIGHT tiered memory budget
(``repro.store``): the digest covers the energy/traffic surface and the
per-tier hit/eviction counters are compared exactly — CLOCK eviction,
block fetch charging and window pinning must all be pure functions of
(config, seed). Synchronous pipeline only: the async path's digests are
wall-clock-shaped (pre-existing), though its tier counts still match.
``compute`` pairs ``compute="measured"`` runs on the reduced digest
surface (:func:`repro.analysis.digest.measured_result_digest`): step
TIMES are real wall-clock, but everything discrete — hit/miss/byte
streams, the jitted SAGE loss trajectory, per-step edge counts — must
stay a pure function of (config, seed), and must match the modeled
lane's shared surface bit for bit (the measured step perturbs energy,
never the sim).
Exit code 0 on match, 1 with both digests printed on divergence.

``trace`` pairs TRACED (``RunConfig.trace=True``) cluster runs under a
congested hot-owner fabric: the exported greentrace payloads must be
byte-identical (virtual-time stamping — no host clock leaks into events),
each payload's energy ledger must reconcile bit-exactly against the
meters, and the traced run's report digest must equal an untraced run's
(the null-tracer hot path cannot perturb the modeled lane).

``twins`` is the numeric half of greendrift (``repro.analysis.drift``):
every ``dynamic``-kind twin in the registry — pairings whose sides are
intentionally different shapes, so the static canonicalizer cannot
compare them — is run on matched inputs and asserted bitwise/allclose.
The target REFUSES to pass if a registered dynamic twin has no runner
here (or a runner has no registry entry), so retiring either side of the
contract alone fails CI.

Run it with ``REPRO_SANITIZE=1`` to arm the runtime sanitizer on top.
"""
from __future__ import annotations

import argparse
import sys


def _pair(label: str, run_once) -> bool:
    d1 = run_once()
    d2 = run_once()
    ok = d1 == d2
    status = "OK " if ok else "FAIL"
    print(f"[determinism] {status} {label}: {d1[:16]}"
          + ("" if ok else f" != {d2[:16]}"))
    return ok


def check_trainer(args) -> bool:
    from repro.analysis import digest as dg
    from repro.train import gnn_trainer as gt

    cfg = gt.RunConfig(
        method=args.method, dataset=args.dataset, batch_size=args.batch,
        n_epochs=args.epochs, steps_per_epoch=args.steps,
        scenario=args.scenario, seed=args.seed,
    )

    def run_once():
        return dg.result_digest(gt.run(cfg, gt.build_trace(cfg)))

    return _pair(f"trainer {args.method}/{args.scenario}", run_once)


def check_cluster(args) -> bool:
    from repro.analysis import digest as dg
    from repro.train import gnn_trainer as gt
    from repro.train.cluster import ClusterConfig, run_cluster

    cfg = gt.RunConfig(
        method=args.method, dataset=args.dataset, batch_size=args.batch,
        n_epochs=args.epochs, steps_per_epoch=args.steps,
        scenario=args.scenario, seed=args.seed,
    )
    cc = ClusterConfig(n_workers=args.workers)

    def run_once():
        return dg.report_digest(run_cluster(cfg, cc))

    return _pair(f"cluster P={args.workers} {args.method}", run_once)


def check_store(args) -> bool:
    from repro.analysis import digest as dg
    from repro.graph import datasets
    from repro.store import MemoryBudget
    from repro.train import gnn_trainer as gt

    graph = datasets.materialize(args.dataset, seed=0)
    feat_bytes = (
        graph.features.nbytes if graph.features is not None
        else graph.n_nodes * graph.feature_source.bytes_per_row
    )
    budget = MemoryBudget(
        host_bytes=args.mem_frac * float(feat_bytes), chunk_rows=256,
    )
    cfg = gt.RunConfig(
        method=args.method, dataset=args.dataset, batch_size=args.batch,
        n_epochs=args.epochs, steps_per_epoch=args.steps,
        scenario=args.scenario, seed=args.seed, mem_budget=budget,
    )

    counts = []

    def run_once():
        r = gt.run(cfg, gt.build_trace(cfg))
        counts.append(r.tier_counts)
        return dg.result_digest(r)

    ok = _pair(
        f"store {args.method} mem_frac={args.mem_frac}", run_once
    )
    tiers_ok = counts[0] == counts[1]
    if not tiers_ok:
        print(f"[determinism] FAIL store tier counts: "
              f"{counts[0]} != {counts[1]}")
    elif counts[0] is not None and counts[0]["block_fetches"] == 0:
        # a budget so loose nothing spills checks nothing — flag it
        print(f"[determinism] FAIL store: no tier traffic under "
              f"mem_frac={args.mem_frac} (vacuous check)")
        tiers_ok = False
    return ok and tiers_ok


def check_trace(args) -> bool:
    """greentrace determinism: paired same-seed traced runs at P workers
    under a congested (hot-owner) fabric must export BYTE-identical trace
    payloads, and enabling the trace must leave the modeled-lane report
    digest bit-identical to an untraced run."""
    import dataclasses

    from repro.analysis import digest as dg
    from repro.obs import reconcile, trace_digest
    from repro.train import gnn_trainer as gt
    from repro.train.cluster import ClusterConfig, run_cluster

    cfg = gt.RunConfig(
        method=args.method, dataset=args.dataset, batch_size=args.batch,
        n_epochs=args.epochs, steps_per_epoch=args.steps,
        scenario=args.scenario, seed=args.seed, trace=True,
    )
    hot = tuple(
        0.35 if p == 0 else 1.0 for p in range(cfg.n_parts)
    )
    cc = ClusterConfig(n_workers=args.workers, link_rate_scale=hot)

    reports = []

    def run_once():
        rep = run_cluster(cfg, cc)
        reconcile(rep.trace)  # raises on a broken energy ledger
        reports.append(rep)
        return trace_digest(rep.trace)

    ok = _pair(
        f"trace P={args.workers} {args.method} hot-owner", run_once
    )
    rep_off = run_cluster(dataclasses.replace(cfg, trace=False), cc)
    lane_ok = dg.report_digest(reports[0]) == dg.report_digest(rep_off)
    if not lane_ok:
        print("[determinism] FAIL trace: traced report digest != "
              "untraced digest (tracing perturbed the modeled lane)")
    if rep_off.trace is not None:
        print("[determinism] FAIL trace: trace=False produced a payload")
        lane_ok = False
    return ok and lane_ok


def check_compute(args) -> bool:
    import dataclasses

    from repro.analysis import digest as dg
    from repro.train import gnn_trainer as gt

    cfg = gt.RunConfig(
        method=args.method, dataset=args.dataset, batch_size=args.batch,
        n_epochs=args.epochs, steps_per_epoch=args.steps,
        scenario=args.scenario, seed=args.seed, compute="measured",
    )
    results = []

    def run_once():
        r = gt.run(cfg, gt.build_trace(cfg))
        results.append(r)
        return dg.measured_result_digest(r)

    ok = _pair(f"compute measured {args.method}/{args.scenario}", run_once)

    # step-count invariants: the engine stepped exactly once per sim step
    total = args.epochs * args.steps
    rep = results[0].compute_report or {}
    counts_ok = (
        rep.get("n_steps") == total
        and len(rep.get("losses", ())) == total
        and len(rep.get("step_s", ())) == total
    )
    if not counts_ok:
        print(f"[determinism] FAIL compute step counts: "
              f"expected {total}, report says {rep.get('n_steps')!r}")

    # the measured lane must not perturb the sim: every non-energy field
    # of the digest surface matches a modeled run of the same config
    r_mod = gt.run(
        dataclasses.replace(cfg, compute="modeled"),
        gt.build_trace(cfg),
    )
    fa = dg.result_fields(results[0])
    fb = dg.result_fields(r_mod)
    for name in dg._ENERGY_FIELDS:
        fa.pop(name)
        fb.pop(name)
    shared_ok = dg.digest(fa) == dg.digest(fb)
    if not shared_ok:
        diverged = [
            k for k in fa if dg.digest(fa[k]) != dg.digest(fb[k])
        ]
        print(f"[determinism] FAIL compute measured-vs-modeled shared "
              f"surface diverged in fields: {diverged}")
    else:
        print("[determinism] OK  compute measured==modeled on the "
              "non-energy surface")
    return ok and counts_ok and shared_ok


# ---------------------------------------------------------------- twins
# Numeric runners for the dynamic greendrift twins. Each runner returns
# True/False and prints one [twins] line per pairing; tolerances are tight
# where the sides share float paths and loosened only for float32-vs-
# float64 transcendental differences (documented per runner).

def _twin_report(name: str, ok: bool, detail: str = "") -> bool:
    status = "OK " if ok else "FAIL"
    print(f"[twins] {status} {name}" + (f": {detail}" if detail else ""))
    return ok


def _twin_fabric_rpc_wall(args) -> bool:
    """One isolated clean-fabric transfer == the Eq. 4 closed form."""
    from repro.core import cost_model as cm
    from repro.net.fabric import probe_rpc

    params = cm.CostModelParams()
    worst = 0.0
    for rows in (64.0, 1024.0, 16384.0):
        for d in (0.0, 5.0, 20.0):
            tr = probe_rpc(params, rows, d, 400.0)
            want = cm.rpc_wall_s(
                float(params.alpha_rpc), float(params.beta),
                float(params.gamma_c), rows * 400.0, d,
            )
            worst = max(worst, abs(tr.raw_s - want) / max(abs(want), 1e-12))
    return _twin_report(
        "fabric-rpc-wall", worst <= 1e-9, f"max rel err {worst:.2e}"
    )


def _twin_sigma_law(args) -> bool:
    """Fabric-reported sigma at u=0 == 1 + (gamma_c/beta) * delta."""
    import numpy as np

    from repro.core import cost_model as cm
    from repro.net.background import ConstantDelta
    from repro.net.fabric import Fabric

    params = cm.CostModelParams()
    worst = 0.0
    for d in (0.0, 2.0, 10.0):
        fabric = Fabric(
            params, 3, delta_process=ConstantDelta(d), name="twin-sigma"
        )
        got = np.asarray(fabric.sigma())
        want = float(cm.sigma_from_delta(params, d))
        worst = max(worst, float(np.max(np.abs(got - want))))
    return _twin_report(
        "sigma-law", worst <= 1e-6, f"max abs err {worst:.2e}"
    )


def _twin_store_headroom(args) -> bool:
    """Fluid W-headroom == tiered-store byte accounting at block-aligned
    residency (budget = frac of the feature bytes, working set = the
    W/MAX_WINDOW fraction of the rows)."""
    import types

    import numpy as np

    from repro.core import queue_sim as qs
    from repro.store import MemoryBudget
    from repro.store.tiered import TieredFeatureStore

    chunk = 32
    n_rows = int(qs.MAX_WINDOW) * chunk
    feat = np.zeros((n_rows, 4), np.float32)
    owner_of = np.zeros(n_rows, np.int64)
    frac = 0.5
    cfg = types.SimpleNamespace(mem_budget_frac=frac)
    worst = 0.0
    for w in (8, 16, 32):
        budget = MemoryBudget(
            host_bytes=frac * n_rows * feat.itemsize * feat.shape[1],
            chunk_rows=chunk,
        )
        store = TieredFeatureStore(feat, owner_of, 0, 2, budget=budget)
        store.touch(np.arange(w * chunk))      # exactly w resident blocks
        got = store.headroom()
        want = float(qs.mem_headroom(cfg, float(w)))
        worst = max(worst, abs(got - want))
    return _twin_report(
        "store-headroom", worst <= 1e-9, f"max abs err {worst:.2e}"
    )


def _twin_store_spill(args) -> bool:
    """No-overflow endpoint: the fluid spill multiplier is exactly 1.0
    iff re-touching the working set under a matching block budget fetches
    nothing (and > 1.0 iff the CLOCK tier thrashes)."""
    import types

    import numpy as np

    from repro.core import queue_sim as qs
    from repro.store.host_tier import HostTier

    chunk = 32
    frac = 0.5
    budget_blocks = int(frac * int(qs.MAX_WINDOW))
    cfg = types.SimpleNamespace(mem_budget_frac=frac)
    ok = True
    for w in (16, 48, 64, 96, 120):
        spill = float(qs.mem_spill(cfg, float(w)))
        tier = HostTier(
            int(qs.MAX_WINDOW) * chunk, chunk, budget_blocks
        )
        rows = np.arange(w * chunk)
        tier.touch(rows)
        refetched = len(tier.touch(rows))      # steady-state thrash
        agree = (spill == 1.0) == (refetched == 0)
        if not agree:
            ok = False
        ok &= spill >= 1.0
    return _twin_report("store-spill", ok)


def _twin_delta_np(args) -> bool:
    """Full-profile delta_at == delta_at_np, including the `sev` fragment
    the law twins exclude. float32 sin vs float64 sin on large phase
    arguments bounds the tolerance."""
    import jax
    import numpy as np

    from repro.core import domain_rand as dr

    worst = 0.0
    for n_owners in (1, 3, 7):
        for seed in range(4):
            prof = dr.sample_profile(
                jax.random.PRNGKey(seed), 512, n_owners
            )
            for step in (0.0, 10.0, 100.0, 300.0, 511.0):
                a = np.asarray(dr.delta_at(prof, step, n_owners))
                b = dr.delta_at_np(
                    int(prof.archetype), float(prof.severity_ms),
                    float(prof.onset), float(prof.duration),
                    float(prof.period), int(prof.link_a),
                    int(prof.link_b), float(prof.phase), step, n_owners,
                )
                worst = max(worst, float(np.max(np.abs(a - b))))
    return _twin_report(
        "delta-np-numeric", worst <= 5e-3, f"max abs err {worst:.2e} ms"
    )


def _twin_paper_schedule(args) -> bool:
    """Eval-schedule jnp/np twins over every epoch and odd cluster sizes."""
    import numpy as np

    from repro.core import domain_rand as dr

    n_epochs = 12
    worst = 0.0
    for n_owners in (1, 3, 7):
        for epoch in range(n_epochs):
            a = np.asarray(
                dr.paper_schedule_delta(epoch, n_epochs, n_owners)
            )
            b = dr.paper_schedule_delta_np(epoch, n_epochs, n_owners)
            worst = max(worst, float(np.max(np.abs(a - b))))
    return _twin_report(
        "paper-schedule-numeric", worst <= 1e-5, f"max abs err {worst:.2e}"
    )


def _twin_collective(args) -> bool:
    """The cluster twin's jnp `collective` closure == ring_collective_cost.

    The closure is compiled FROM THE REGISTERED SOURCE (the same AST node
    greendrift resolves), so this exercises the shipped code, not a
    re-statement of it.
    """
    import ast
    import os
    import textwrap
    import types

    import jax.numpy as jnp
    import numpy as np

    from repro.analysis.drift import _resolve_qualname
    from repro.analysis.engine import package_root
    from repro.core import cost_model as cm
    from repro.distributed.collectives import ring_collective_cost

    path = os.path.join(package_root(), "envs", "cluster_sim.py")
    with open(path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    fn = _resolve_qualname(tree, "_window_dynamics.collective")
    if fn is None:
        return _twin_report(
            "collective-numeric", False,
            "_window_dynamics.collective not found in envs/cluster_sim.py",
        )
    code = (
        "def _make(cfg, params, scatter):\n"
        + textwrap.indent(ast.unparse(fn), "    ")
        + "\n    return collective\n"
    )
    ns: dict = {"jnp": jnp}
    exec(compile(code, path, "exec"), ns)  # noqa: S102 — shipped source

    params = cm.CostModelParams()
    worst = 0.0
    for scatter in (False, True):
        cfg = types.SimpleNamespace(
            sync="reduce_scatter" if scatter else "ring",
            grad_bytes=2.0e6,
        )
        coll = ns["_make"](cfg, params, scatter)
        for n in (2, 4, 8):
            wall, cpu = coll(jnp.asarray(float(n), jnp.float32))
            want_wall, want_cpu, _, _ = ring_collective_cost(
                n, cfg.grad_bytes, params, scatter=scatter
            )
            worst = max(
                worst,
                abs(float(wall) - want_wall) / max(want_wall, 1e-12),
                abs(float(cpu) - want_cpu) / max(want_cpu, 1e-12),
            )
    return _twin_report(
        "collective-numeric", worst <= 1e-5, f"max rel err {worst:.2e}"
    )


def _twin_compute_law(args) -> bool:
    """Measured lane -> ``calibrate_compute`` -> t_base round trip.

    Two halves. Law recovery: synthetic samples generated FROM
    ``cost_model.compute_step_s`` must be fit back to the same (t0,
    per_edge) and to a t_base that equals the law at the reference edge
    count — so the calibration predicts through the shared helper, not a
    re-inlined copy. Plumbing: a ``ComputeEngine`` driven by a virtual
    clock that advances a fixed dt per read measures exactly dt for
    every step (warm-up compile reads are excluded by construction);
    calibrating on ``engine.calibration_samples()`` must therefore
    recover t_base == dt, proving the timed region spans exactly one
    exec and nothing else leaks into the samples.
    """
    import numpy as np

    from repro.core import calibration as cal
    from repro.core import cost_model as cm

    # -- law recovery on synthetic samples drawn from the shared helper
    t0, per_edge = 2.5e-3, 7.5e-8
    edges = np.array([1.0e3, 5.0e3, 2.0e4, 1.0e5])
    times = np.asarray(
        [cm.compute_step_s(t0, per_edge, float(e)) for e in edges]
    )
    params, fit = cal.calibrate_compute(edges, times)
    want_tb = float(cm.compute_step_s(t0, per_edge, float(edges.mean())))
    worst = max(
        abs(fit.t0 - t0) / t0,
        abs(fit.per_edge - per_edge) / per_edge,
        abs(float(params.t_base) - want_tb) / want_tb,
    )

    # -- engine plumbing under a virtual clock (1 ms per read)
    from repro.train import gnn_trainer as gt
    from repro.train.compute import ComputeEngine

    cfg = gt.RunConfig(
        method="static_w", dataset=args.dataset, batch_size=args.batch,
        n_epochs=1, steps_per_epoch=3, scenario="clean", seed=args.seed,
        compute="measured",
    )
    graph, _owner, _traces, mbs = gt.build_trace(cfg)

    class _VClock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            self.t += 1e-3
            return self.t

    dt = 1e-3
    eng = ComputeEngine(graph, cfg, clock=_VClock())
    for s in range(cfg.steps_per_epoch):
        mb = mbs[0][s]
        eng.step(
            mb, np.asarray(graph.features[mb.input_nodes], np.float32)
        )
    e_s, t_s = eng.calibration_samples()
    p2, _fit2 = cal.calibrate_compute(e_s, t_s)
    worst = max(worst, abs(float(p2.t_base) - dt) / dt)
    return _twin_report(
        "compute-law-numeric", worst <= 1e-6, f"max rel err {worst:.2e}"
    )


_TWIN_RUNNERS = {
    "fabric-rpc-wall": _twin_fabric_rpc_wall,
    "sigma-law": _twin_sigma_law,
    "store-headroom": _twin_store_headroom,
    "store-spill": _twin_store_spill,
    "delta-np-numeric": _twin_delta_np,
    "paper-schedule-numeric": _twin_paper_schedule,
    "collective-numeric": _twin_collective,
    "compute-law-numeric": _twin_compute_law,
}


def check_twins(args) -> bool:
    """Run every registered dynamic twin; coverage itself is asserted."""
    from repro.analysis.drift.registry import dynamic_twins

    registered = [t.name for t in dynamic_twins()]
    ok = True
    for twin in dynamic_twins():
        runner = _TWIN_RUNNERS.get(twin.name)
        if runner is None:
            ok = _twin_report(
                twin.name, False,
                "registered dynamic twin has no numeric runner — add one "
                "to _TWIN_RUNNERS or retire the registry entry",
            ) and ok
            continue
        ok = runner(args) and ok
    for name in _TWIN_RUNNERS:
        if name not in registered:
            ok = _twin_report(
                name, False,
                "runner has no registry entry — register the twin in "
                "repro.analysis.drift.registry or delete the runner",
            ) and ok
    return ok


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "target",
        choices=("trainer", "cluster", "store", "compute", "trace", "twins",
                 "all"),
    )
    p.add_argument("--method", default="static_w")
    p.add_argument("--dataset", default="reddit")
    p.add_argument("--scenario", default="clean")
    p.add_argument("--batch", type=int, default=600)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--mem-frac", type=float, default=0.2,
                   help="store target: host budget as a fraction of the "
                        "graph's feature bytes (tight by default)")
    args = p.parse_args(argv)

    ok = True
    if args.target in ("trainer", "all"):
        ok &= check_trainer(args)
    if args.target in ("cluster", "all"):
        ok &= check_cluster(args)
    if args.target in ("store", "all"):
        ok &= check_store(args)
    if args.target in ("compute", "all"):
        ok &= check_compute(args)
    if args.target in ("trace", "all"):
        ok &= check_trace(args)
    if args.target in ("twins", "all"):
        ok &= check_twins(args)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
