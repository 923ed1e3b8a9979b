"""The workload manager: Scenario -> engine inputs -> one simulation.

``resolve`` turns a declarative :class:`~repro.union.scenario.Scenario`
into everything ``netsim.engine.build_engine`` needs (skeletons, topology,
placements, NetConfig, arrival offsets); ``build`` compiles the engine;
``run_scenario`` runs a single member and returns the standard report.
Ensemble campaigns over many members live in :mod:`repro.union.ensemble`.

:func:`build_job_skeleton` is the shared app-resolution entry point: both
scenario jobs and online-scheduler trace jobs
(:mod:`repro.sched.trace`) resolve through it, so the two input languages
share one app catalog (SPECS names, ``hlo:`` records, inline DSL).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.core import workloads as W
from repro.core.translator import translate_source
from repro.kernels.ops import drain_widths
from repro.netsim import metrics as MET
from repro.netsim.config import NetConfig
from repro.netsim.engine import (
    Engine,
    EngineCapacity,
    JobSpec,
    URSpec,
    get_engine,
    inject_candidates,
    job_vm,
    member_state,
    routed_candidates,
)
from repro.netsim.placement import place_jobs
from repro.netsim.topology import Fabric, get_topology
from repro.union.scenario import Scenario, ScenarioJob, UR_RANKS
from repro.union.seeds import engine_seed

DEFAULT_POOL = {"small": 8192, "paper": 65536}


def build_job_skeleton(job: ScenarioJob, scale: str):
    """One ScenarioJob -> a registered SkeletonProgram.

    Three app sources: an inline DSL ``source``, an hlo2skeleton dry-run
    record (``hlo:<arch>:<shape>[:<mesh>]``), or a `workloads.SPECS` name.
    """
    if job.source is not None:
        return translate_source(
            job.source, f"{job.app}_{job.ranks}", job.ranks, job.overrides
        )
    if job.app.startswith("hlo:"):
        from repro.core.hlo2skeleton import build_ml_skeleton

        parts = job.app.split(":")
        if len(parts) not in (3, 4):
            raise ValueError(f"bad hlo app spec {job.app!r}; want hlo:<arch>:<shape>[:<mesh>]")
        arch, shape = parts[1], parts[2]
        mesh = parts[3] if len(parts) == 4 else "single"
        return build_ml_skeleton(
            arch, shape, mesh=mesh, n_ranks=job.ranks or 256,
            overrides=job.overrides,
        )
    if job.ranks is None:
        return W.build_skeleton(job.app, scale, overrides=job.overrides)
    src, default_ranks, ov = W.get_source(job.app, scale)
    ov.update(job.overrides)
    return translate_source(src, f"{job.app}_{scale}_{job.ranks}", job.ranks, ov)


@dataclass
class ResolvedScenario:
    scenario: Scenario
    topo: Fabric
    jobs: List[JobSpec]  # placement for placement_seed baked in
    ur: Optional[URSpec]
    net: NetConfig
    app_names: List[str]  # jobs + ["ur"] when UR present
    job_sizes: List[int]  # jobs + UR ranks when present (placement order)
    pool_size: int
    horizon_us: float
    placement_seed: int

    def placements(self, seed: int) -> List[np.ndarray]:
        """Per-member placements: same scenario shape, a fresh draw."""
        return place_jobs(self.topo, self.job_sizes, self.scenario.placement, seed=seed)

    @property
    def start_us(self) -> List[float]:
        return [j.start_us for j in self.jobs]

    @property
    def capacity(self) -> EngineCapacity:
        """The (Jmax, Pmax, OPmax) envelope this scenario needs — the
        bucketing key for ragged campaigns. A scenario ``reserve`` widens
        it so differently-shaped scenarios share one compiled engine."""
        cap = EngineCapacity.of_jobs(self.jobs)
        rv = self.scenario.reserve
        if rv:
            cap = cap.union(EngineCapacity(
                Jmax=rv.get("jobs", 1), Pmax=rv.get("ranks", 1),
                OPmax=rv.get("ops", 1),
            ))
        return cap

    def padded_app_names(self, cap: EngineCapacity) -> List[Optional[str]]:
        """Metric-row names under capacity ``cap``: real jobs first, None
        for padded job rows, 'ur' on the final row when UR is present."""
        names: List[Optional[str]] = [j.name for j in self.jobs]
        names += [None] * (cap.Jmax - len(self.jobs))
        if self.ur is not None:
            names.append("ur")
        return names


def resolve(scenario: Scenario, seed: int = 0) -> ResolvedScenario:
    scenario.validate()
    topo = get_topology(scenario.topo, scenario.scale)
    skels = [build_job_skeleton(j, scenario.scale) for j in scenario.jobs]
    sizes = [s.n_ranks for s in skels]
    ur_decl = scenario.ur
    if ur_decl is not None:
        sizes = sizes + [ur_decl.ranks or UR_RANKS[scenario.scale]]
    placements = place_jobs(topo, sizes, scenario.placement, seed=seed)
    jobs = [
        JobSpec(j.app, skel, placements[i], start_us=j.start_us)
        for i, (j, skel) in enumerate(zip(scenario.jobs, skels))
    ]
    ur = (
        URSpec(
            "ur", placements[-1], size_bytes=ur_decl.size_bytes,
            interval_us=ur_decl.interval_us, start_us=ur_decl.start_us,
        )
        if ur_decl is not None
        else None
    )
    pool_size = scenario.pool_size or DEFAULT_POOL[scenario.scale]
    net = NetConfig(pool_size=pool_size, tick_us=scenario.tick_us)
    return ResolvedScenario(
        scenario=scenario, topo=topo, jobs=jobs, ur=ur, net=net,
        app_names=[j.app for j in scenario.jobs] + (["ur"] if ur else []),
        job_sizes=sizes, pool_size=pool_size,
        horizon_us=scenario.horizon_ms * 1000.0, placement_seed=seed,
    )


def build(rs: ResolvedScenario, capacity: Optional[EngineCapacity] = None,
          probes=None, hist=None):
    """The engine for a resolved scenario: an
    :class:`~repro.netsim.engine.Engine` (unpacks as ``init, run, tick``;
    carries ``run_window`` for windowed/scheduled runs).

    Drawn from the **process-wide engine cache** (compiled once per
    capacity envelope + system config), with this scenario's job set and
    UR placement bound as the init-time defaults — job tables are runtime
    data, so scenarios sharing an envelope share one set of jits.

    ``capacity`` widens the envelope beyond this scenario's own needs so
    the same compiled engine can serve other (smaller) scenarios — the
    ragged-campaign path in :mod:`repro.union.ensemble`. ``probes`` (a
    :class:`repro.obs.ProbeConfig`) selects the probed variant of the
    engine — a separate cache entry; the unprobed one is untouched.
    ``hist`` (a :class:`repro.obs.HistConfig`) likewise selects the
    variant with full-fidelity latency histograms compiled in.
    """
    cap = rs.capacity if capacity is None else capacity.union(rs.capacity)
    eng = get_engine(
        rs.topo, routing=rs.scenario.routing, ur=rs.ur, net=rs.net,
        pool_size=rs.pool_size, horizon_us=rs.horizon_us, capacity=cap,
        probes=probes, hist=hist,
    )
    return bind_jobs(eng, rs)


def bind_jobs(eng: Engine, rs: ResolvedScenario) -> Engine:
    """Wrap a cached (job-free) engine so ``init_state`` defaults to this
    scenario's jobs and UR placement — the historical ``build_engine``
    call shape, without a per-scenario compile."""
    default_placements = [np.asarray(j.rank2node) for j in rs.jobs]
    if rs.ur is not None:
        default_placements.append(np.asarray(rs.ur.rank2node))

    def init_state(seed: int = 1, placements=None, start_us=None,
                   jobs_override=None, rank_slowdown_override=None,
                   faults=None):
        if jobs_override is None:
            jobs_override = rs.jobs
            if placements is None:
                placements = default_placements
        return eng.init_state(
            seed=seed, placements=placements, start_us=start_us,
            jobs_override=jobs_override,
            rank_slowdown_override=rank_slowdown_override,
            faults=faults,
        )

    return Engine(
        init_state=init_state, run=eng.run, tick=eng.tick,
        run_window=eng.run_window, capacity=eng.capacity,
        _lower=eng._lower,
    )


def member_report(state, rs: ResolvedScenario, wall_s: float = 0.0,
                  seed: int = 0, strict: bool = False,
                  start_us: Optional[Sequence[float]] = None,
                  capacity: Optional[EngineCapacity] = None) -> Dict:
    """``start_us`` records this member's *actual* arrival schedule when it
    differs from the scenario's (e.g. campaign arrival jitter);
    ``capacity`` is the engine envelope the state was simulated under
    (defaults to the scenario's own)."""
    cap = capacity or rs.capacity
    names = rs.padded_app_names(cap)
    rep = MET.run_report(state, names, rs.topo, rs.net, wall_s,
                         strict=strict)
    sc = rs.scenario
    rep["config"] = dict(
        workload=sc.name, topo=sc.topo, placement=sc.placement,
        routing=sc.routing, scale=sc.scale, seed=seed, ranks=rs.job_sizes,
        start_us=[float(s) for s in (start_us if start_us is not None
                                     else rs.start_us)],
        all_done=[
            bool(np.asarray(job_vm(state, ji).done).all())
            for ji in range(len(rs.jobs))
        ],
        envelope=dict(Jmax=cap.Jmax, Pmax=cap.Pmax, OPmax=cap.OPmax),
    )
    m = state.metrics
    ticks, inject_ticks, active, peak, rungs = jax.device_get(
        (m.ticks, m.inject_ticks, m.active_slot_ticks, m.peak_active,
         m.drain_rung_ticks))
    ticks, slots = int(ticks), int(state.pool.active.shape[-1])
    inject_ticks, cands = int(inject_ticks), inject_candidates(state)
    rep["engine"] = dict(
        ticks=ticks, inject_ticks=inject_ticks,
        mean_pool_occupancy=(float(active) / (ticks * slots)
                             if ticks else 0.0),
        peak_pool_occupancy=int(peak) / slots,
        mean_drain_width=(
            int(np.dot(rungs, np.asarray(drain_widths(slots), np.int64)))
            / (ticks * slots) if ticks else 0.0),
        mean_inject_width=(
            float(routed_candidates(state)[0]) / (inject_ticks * cands)
            if inject_ticks else 0.0),
    )
    if getattr(state, "probes", None) is not None:
        from repro.obs import probe_timelines

        rep["probes"] = probe_timelines(
            state.probes, list(rs.topo.link_levels()), names
        )
    return rep


def run_scenario(
    scenario: Scenario, seed: int = 0, strict: bool = False
) -> Dict:
    """Deprecated front door — run a single scenario member.

    Shim over the :mod:`repro.union.experiment` facade
    (``union.run(Experiment(scenarios=[sc], members=1, base_seed=seed))``),
    bit-identical to the historical direct run: ``seed`` drives both the
    placement draw and the engine RNG, so a batched campaign member with
    the same seed reproduces this run exactly.
    """
    from repro.union import experiment as EXP

    EXP.deprecated_entry(
        "repro.union.run_scenario",
        "repro.union.run(Experiment(scenarios=[...], members=1))",
    )
    res = EXP.run(EXP.Experiment(
        name=scenario.name, scenarios=[scenario], members=1,
        base_seed=seed, strict=strict, vmapped=False,
    ))
    return res.cells[0].report


# back-compat alias: the derivation now lives in repro.union.seeds,
# shared with every other execution path (pinned in tests).
_engine_seed = engine_seed
