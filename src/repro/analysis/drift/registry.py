"""greendrift twin registry: every paired implementation, declared once.

The repo carries the windowed cost law in four hand-maintained
implementations (event fabric, fluid twin, cluster twin, worker
estimator), np↔jnp process twins, and the PR-7 spill-law twins. Each
pairing is declared here as a :class:`Twin` so the static pass
(``drift/__init__.check_project``) can prove the sides still encode the
same law, and the dynamic pass (``scripts/check_determinism.py twins``)
can run them on matched inputs. Three kinds:

``law``
    Sites name an anchor — a local variable whose (first) assignment RHS
    is the law fragment, or ``"return"`` for the function's return
    expression. Every site canonicalizes (``drift/canon.py``) and must
    match the FIRST site (the reference) structurally; the first
    divergent subtree is reported with both source spans.

``shared-helper``
    The law exists once; the twin obligation is that the caller site
    still CALLS the shared helper (terminal callee name). Deleting the
    call and re-inlining a private copy is the drift mode this catches —
    the re-inlined copy would otherwise be invisible to the law twins.

``dynamic``
    Sides are intentionally different shapes (event-driven vs closed
    form, byte accounting vs fluid fraction) so structural comparison
    cannot apply. Statically we pin only that both qualnames still
    resolve; the numeric agreement lives in ``check_determinism.py
    twins``, which refuses to pass if a ``dynamic`` twin has no runner —
    so retiring a runner without retiring the registry entry fails too.

Suppression: a divergence is silenced line-scoped by
``# greenlint: twin-ok <why>`` on (or above) EITHER side's anchor line.

Registering a new twin (e.g. the ROADMAP temporal lane's staleness
process): add the Twin here, run ``python -m repro.analysis --check`` to
see it compared, and add a runner to the ``twins`` target if it is
``dynamic``. See DESIGN.md "Invariants as code, part 2".
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Site:
    """One side of a twin: where an implementation (fragment) lives."""

    module: str               # repro-package-relative posix path
    qualname: str             # dotted; classes and nested defs supported
    anchor: str | None = None  # local var whose assignment RHS is the law,
    #                            or "return"; None for non-law sites
    inline: tuple[str, ...] = ()  # single-assignment locals substituted
    #                               into the anchor before canonicalizing


@dataclasses.dataclass(frozen=True)
class Twin:
    """One registered pairing of implementations."""

    name: str
    kind: str                       # "law" | "shared-helper" | "dynamic"
    sites: tuple[Site, ...]         # law/dynamic: first site is reference
    helper: Site | None = None      # shared-helper: the helper definition
    note: str = ""


_QS = "core/queue_sim.py"
_CS = "envs/cluster_sim.py"
_DR = "core/domain_rand.py"
_CM = "core/cost_model.py"

TWINS: tuple[Twin, ...] = (
    # ---- the fluid service law: one formula, three implementations ----
    Twin(
        name="service-law",
        kind="law",
        sites=(
            Site(_QS, "_window_dynamics.substep", "phi"),
            Site(_CS, "_window_dynamics.substep", "phi_base"),
            Site("net/fabric.py", "Fabric._transfer_locked", "service"),
        ),
        note="phi = (1 - u) / (1 + slope * delta): the congestion service "
             "factor every cost path divides by",
    ),
    # ---- cluster twin's scripted-peer law vs the shared ego law ----
    Twin(
        name="peer-miss-rows",
        kind="law",
        sites=(
            Site(_QS, "action_volumes", "miss_rows"),
            Site(_CS, "_window_dynamics.substep", "peer_miss_rows"),
        ),
    ),
    Twin(
        name="peer-miss-work",
        kind="law",
        sites=(
            Site(_QS, "action_volumes", "miss_work"),
            Site(_CS, "_window_dynamics.substep", "peer_mw"),
        ),
    ),
    Twin(
        name="peer-active",
        kind="law",
        sites=(
            Site(_QS, "action_volumes", "active"),
            Site(_CS, "_window_dynamics.substep", "peer_act"),
        ),
    ),
    # ---- ring collective: host law vs the cluster twin's jnp closure ----
    # (the `chunk` anchors intentionally differ: the jnp side guards the
    # n==0 division that the host side excludes by precondition)
    Twin(
        name="collective-phases",
        kind="law",
        sites=(
            Site("distributed/collectives.py", "ring_collective_cost",
                 "phases"),
            Site(_CS, "_window_dynamics.collective", "phases"),
        ),
    ),
    Twin(
        name="collective-per-phase",
        kind="law",
        sites=(
            Site("distributed/collectives.py", "ring_collective_cost",
                 "per_phase"),
            Site(_CS, "_window_dynamics.collective", "per_phase"),
        ),
    ),
    Twin(
        name="collective-wall",
        kind="law",
        sites=(
            Site("distributed/collectives.py", "ring_collective_cost",
                 "wall"),
            Site(_CS, "_window_dynamics.collective", "wall"),
        ),
    ),
    Twin(
        name="collective-cpu",
        kind="law",
        sites=(
            Site("distributed/collectives.py", "ring_collective_cost",
                 "cpu"),
            Site(_CS, "_window_dynamics.collective", "cpu"),
        ),
    ),
    # ---- domain_rand np<->jnp twins (fabric host side vs vmap side) ----
    Twin(
        name="delta-active",
        kind="law",
        sites=(
            Site(_DR, "delta_at", "active"),
            Site(_DR, "delta_at_np", "active"),
        ),
    ),
    Twin(
        name="delta-onehot",
        kind="law",
        sites=(
            Site(_DR, "delta_at", "onehot_a"),
            Site(_DR, "delta_at_np", "onehot_a"),
        ),
    ),
    Twin(
        name="delta-flip",
        kind="law",
        sites=(
            Site(_DR, "delta_at", "flip"),
            Site(_DR, "delta_at_np", "flip", inline=("p",)),
        ),
    ),
    Twin(
        name="delta-switching",
        kind="law",
        sites=(
            Site(_DR, "delta_at", "switching"),
            Site(_DR, "delta_at_np", "switching"),
        ),
    ),
    Twin(
        name="delta-osc",
        kind="law",
        sites=(
            Site(_DR, "delta_at", "osc"),
            Site(_DR, "delta_at_np", "osc", inline=("p",)),
        ),
    ),
    Twin(
        name="delta-branches",
        kind="law",
        sites=(
            Site(_DR, "delta_at", "branches"),
            Site(_DR, "delta_at_np", "branches"),
        ),
        note="the archetype table itself; `sev` is excluded (mask-multiply "
             "vs scalar branch) and covered numerically by the twins target",
    ),
    Twin(
        name="paper-schedule-phase",
        kind="law",
        sites=(
            Site(_DR, "paper_schedule_delta", "phase"),
            Site(_DR, "paper_schedule_delta_np", "phase"),
        ),
    ),
    Twin(
        name="paper-schedule-window",
        kind="law",
        sites=(
            Site(_DR, "paper_schedule_delta", "in_window"),
            Site(_DR, "paper_schedule_delta_np", "in_window"),
        ),
    ),
    Twin(
        name="paper-schedule-severity",
        kind="law",
        sites=(
            Site(_DR, "paper_schedule_delta", "sev"),
            Site(_DR, "paper_schedule_delta_np", "sev"),
        ),
    ),
    Twin(
        name="paper-schedule-links",
        kind="law",
        sites=(
            Site(_DR, "paper_schedule_delta", "onehot_b"),
            Site(_DR, "paper_schedule_delta_np", "onehot_b"),
        ),
    ),
    Twin(
        name="diurnal-law",
        kind="law",
        sites=(
            Site(_DR, "diurnal_util", "return"),
            Site("net/background.py", "DiurnalLoad.utilization", "return"),
        ),
        note="jnp twin guards period with maximum(p, 1) upstream of the "
             "anchor; the shared return shape is the law",
    ),
    # ---- shared-helper obligations: the cluster twin must keep calling
    # the queue_sim single-source-of-truth helpers ----
    Twin(
        name="cluster-action-volumes",
        kind="shared-helper",
        helper=Site(_QS, "action_volumes"),
        sites=(Site(_CS, "_window_dynamics"),),
    ),
    Twin(
        name="cluster-reference-volumes",
        kind="shared-helper",
        helper=Site(_QS, "reference_volumes"),
        sites=(Site(_CS, "_window_dynamics"),),
    ),
    Twin(
        name="cluster-step-cost",
        kind="shared-helper",
        helper=Site(_QS, "make_step_cost"),
        sites=(Site(_CS, "_window_dynamics"),),
    ),
    Twin(
        name="cluster-summary",
        kind="shared-helper",
        helper=Site(_QS, "summarize_window"),
        sites=(Site(_CS, "_window_dynamics"),),
    ),
    Twin(
        name="cluster-mem-spill",
        kind="shared-helper",
        helper=Site(_QS, "mem_spill"),
        sites=(Site(_CS, "_window_dynamics"),),
    ),
    Twin(
        name="worker-rpc-wall",
        kind="shared-helper",
        helper=Site(_CM, "rpc_wall_s"),
        sites=(Site("train/worker.py", "TrainerWorker._step"),),
        note="the worker's per-owner estimator feeding the controller "
             "deque must stay the shared Eq. 4 closed form",
    ),
    Twin(
        name="trainer-rpc-cpu",
        kind="shared-helper",
        helper=Site(_CM, "rpc_cpu_s"),
        sites=(Site("train/gnn_trainer.py", "_fetch_time"),),
    ),
    Twin(
        name="compute-step-law",
        kind="shared-helper",
        helper=Site(_CM, "compute_step_s"),
        sites=(Site("core/calibration.py", "calibrate_compute"),),
        note="the t_base calibration must predict through the shared "
             "per-step compute law — a re-inlined copy of t0 + per_edge*E "
             "could silently diverge from the modeled lane's energy split",
    ),
    # ---- dynamic-only twins: different shapes, numeric agreement pinned
    # by `scripts/check_determinism.py twins` ----
    Twin(
        name="fabric-rpc-wall",
        kind="dynamic",
        sites=(
            Site(_CM, "rpc_wall_s"),
            Site("net/fabric.py", "probe_rpc"),
        ),
        note="one isolated clean-fabric transfer must equal the closed "
             "form: alpha + prop*delta + beta*p + gamma_c*p*delta",
    ),
    Twin(
        name="store-headroom",
        kind="dynamic",
        sites=(
            Site(_QS, "mem_headroom"),
            Site("store/tiered.py", "TieredFeatureStore.headroom"),
        ),
        note="fluid headroom of a W working set == the tiered store's "
             "byte accounting at block-aligned residency",
    ),
    Twin(
        name="store-spill",
        kind="dynamic",
        sites=(
            Site(_QS, "mem_spill"),
            Site("store/host_tier.py", "HostTier.touch"),
        ),
        note="no-overflow endpoint: spill multiplier 1.0 iff a matching "
             "byte budget produces zero block fetches",
    ),
    Twin(
        name="delta-np-numeric",
        kind="dynamic",
        sites=(
            Site(_DR, "delta_at"),
            Site(_DR, "delta_at_np"),
        ),
        note="full-profile numeric agreement incl. `sev`, which the law "
             "twins exclude",
    ),
    Twin(
        name="paper-schedule-numeric",
        kind="dynamic",
        sites=(
            Site(_DR, "paper_schedule_delta"),
            Site(_DR, "paper_schedule_delta_np"),
        ),
    ),
    Twin(
        name="collective-numeric",
        kind="dynamic",
        sites=(
            Site("distributed/collectives.py", "ring_collective_cost"),
            Site(_CS, "_window_dynamics.collective"),
        ),
    ),
    Twin(
        name="sigma-law",
        kind="dynamic",
        sites=(
            Site(_CM, "sigma_from_delta"),
            Site("net/fabric.py", "Fabric.sigma"),
        ),
        note="fabric-reported sigma at (u=0, delta) must equal "
             "1 + (gamma_c/beta) * delta",
    ),
    Twin(
        name="compute-law-numeric",
        kind="dynamic",
        sites=(
            Site(_CM, "compute_step_s"),
            Site("train/compute.py", "ComputeEngine.step"),
        ),
        note="measured lane -> calibrate_compute -> t_base: engine step "
             "times under a virtual clock must round-trip the shared law "
             "exactly (timing plumb-through, and OLS law recovery)",
    ),
)


def dynamic_twins() -> tuple[Twin, ...]:
    """The twins whose agreement is pinned numerically, not structurally
    (``scripts/check_determinism.py twins`` iterates this)."""
    return tuple(t for t in TWINS if t.kind == "dynamic")
