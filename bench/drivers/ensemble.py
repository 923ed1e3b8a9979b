"""Closed job mixes run as seeded ensembles: each call of the window is
one ``repro.union.run`` of the traffic's mix on the configuration's
fabric, ``members`` members at once. The members' seeds (each sets a
member's placement and routing draws) come from the traffic's fixed set
``member_seeds``, in an order drawn from the run's seed
(``rates.call_members``).

Work is simulated time summed over every member (``sim_us_per_s``). A
member fails when it dropped a message, ended short of the horizon, or
has a NaN in its report. The check runs the reference over every member
the window ran, once the window has closed.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List, Tuple

import numpy as np

from rates import call_members

RATE_METRIC = "sim_us_per_s"


def _has_nan(obj) -> bool:
    if isinstance(obj, float):
        return math.isnan(obj)
    if isinstance(obj, dict):
        return any(_has_nan(v) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return any(_has_nan(v) for v in obj)
    return False


class Driver:
    def __init__(self, config: Dict, traffic: Dict, seed: int):
        from repro import union

        self.union = union
        self.config, self.traffic, self.seed = config, traffic, seed
        self.members = traffic["members"]
        self.member_seeds = traffic["member_seeds"]
        self.horizon_us = traffic["horizon_ms"] * 1000.0
        ur = traffic.get("ur")
        self.scenario = union.Scenario(
            name=traffic.get("name", "mix"), topo=config["topo"],
            scale=config["scale"], placement=traffic["placement"],
            routing=config["routing"], tick_us=config["tick_us"],
            horizon_ms=traffic["horizon_ms"], pool_size=traffic["pool_size"],
            jobs=[union.ScenarioJob(app=j["app"], ranks=j["ranks"],
                                    source=j["source"],
                                    overrides=j.get("overrides", {}),
                                    start_us=j.get("start_us", 0.0))
                  for j in traffic["jobs"]],
            ur=(union.URDecl(ranks=ur["ranks"], size_bytes=ur["size_bytes"],
                             interval_us=ur["interval_us"],
                             start_us=ur.get("start_us", 0.0))
                if ur else None))
        self.kept: List[Tuple[int, object]] = []  # (member seed, state)
        self.attempted = 0
        self.failed = 0
        fb = config["fabric"]
        n_apps = len(traffic["jobs"]) + (1 if ur else 0)
        self.shapes = dict(
            B=self.members, M=traffic["pool_size"],
            K=config["sizes"]["route_width"], L=config["sizes"]["links"],
            R=fb["groups"] * fb["routers_per_group"], apps=n_apps)

    def experiment(self, k: int):
        return self.union.Experiment(
            name=self.scenario.name, scenarios=[self.scenario],
            members=self.members, seeds=call_members(
                self.member_seeds, self.members, self.seed, k))

    def setup(self) -> None:
        """Compile, or load from the persistent cache, every program a
        call runs, without simulating: the engine runs once on initial
        states whose clock already stands at the horizon."""
        import jax
        import jax.numpy as jnp

        from repro.netsim.engine import member_state, stack_members
        from repro.union import manager, planner
        from repro.union.experiment import node_engine

        exp = self.experiment(0)
        node = planner.plan(exp).nodes[0]
        eng, inits, _ = node_engine(node, exp)
        inits = [s._replace(t=jnp.full_like(s.t, self.horizon_us))
                 for s in inits]
        final = jax.block_until_ready(eng.run(stack_members(inits)))
        states = [member_state(final, i) for i in range(len(inits))]
        for cell, st in zip(node.cells, states):
            # a report reads each application's latency only where it
            # delivered a message: mark every one as having delivered
            m = st.metrics._replace(lat_cnt=jnp.ones_like(st.metrics.lat_cnt))
            manager.member_report(st._replace(metrics=m), cell.rs, 0.0,
                                  seed=cell.seed, capacity=node.capacity)

    def call(self, k: int) -> float:
        """One ``union.run``; returns simulated us summed over members."""
        res = self.union.run(self.experiment(k), keep_states=True)
        work = 0.0
        for cell, st in zip(res.cells, res.states):
            rep = cell.report
            self.attempted += 1
            self.failed += int(
                rep["dropped"] > 0 or _has_nan(rep)
                or rep["virtual_time_ms"] * 1000.0 < self.horizon_us
                * (1 - 1e-6))
            work += rep["virtual_time_ms"] * 1000.0
            self.kept.append((cell.seed, st))
        return work

    def check(self) -> Dict[str, float]:
        """The worst of each compared number over every member the window
        ran, against the reference. Frees the program's states first."""
        import jax

        from reference import compare, member

        progs = []
        for seed, st in self.kept:
            J = len(self.traffic["jobs"])
            m, v = jax.device_get((st.metrics, st.vms))
            P = [j["ranks"] for j in self.traffic["jobs"]]
            progs.append((seed, dict(
                t=np.asarray(jax.device_get(st.t)),
                link_bytes=np.asarray(m.link_bytes)[
                    :self.config["sizes"]["links"]],
                lat_sum=m.lat_sum, lat_cnt=m.lat_cnt, lat_min=m.lat_min,
                lat_max=m.lat_max,
                pc=[v.pc[j, :P[j]] for j in range(J)],
                send_done=[v.send_done[j, :P[j]] for j in range(J)],
                recv_done=[v.recv_done[j, :P[j]] for j in range(J)],
                ur_count=(np.asarray(jax.device_get(st.ur.count))
                          if st.ur is not None else np.zeros(0, np.int64)))))
        self.kept = []
        f = member.fabric_of(self.config)
        worst: Dict[str, float] = {}
        t0 = time.perf_counter()
        for seed, prog in progs:
            ref = member.simulate(self.config, self.traffic, seed, f)
            for k, v in compare.gaps(prog, ref).items():
                worst[k] = max(worst.get(k, 0.0), v)
        self.check_s = time.perf_counter() - t0
        return worst
