"""Closed job mixes run as seeded ensembles over every chip of the host:
the calls of ``ensemble`` (one ``repro.union.run`` of ``members`` members
a call, seeds from the traffic's fixed set), with the member axis
sharded over the local devices by the program's own dispatch
(``repro.union.experiment.run_members``).

Set-up compiles, or loads, what that dispatch runs: the sharded engine
run, the placing of the members on the member mesh and their split back
into members on each device, and each member's report on the device
that holds it. The check compares every kept state with the reference
for its member seed, simulated once a seed: the reference depends on
the seed alone.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Dict

_spec = importlib.util.spec_from_file_location(
    "bench_driver_ensemble",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "ensemble.py"))
ensemble = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ensemble)

RATE_METRIC = ensemble.RATE_METRIC


class Driver(ensemble.Driver):
    def __init__(self, config: Dict, traffic: Dict, seed: int):
        super().__init__(config, traffic, seed)
        # the deployment the configuration states is the one the mix runs:
        # its members, split evenly over its chips
        dep = config.get("deployment")
        if dep and (dep["members"] != self.members
                    or dep["members"] != dep["chips"] * dep["members_per_chip"]):
            raise ValueError(f"traffic runs {self.members} members; the "
                             f"configuration's deployment states {dep}")

    def setup(self) -> None:
        """Run the program's member dispatch once on initial states whose
        clock already stands at the horizon, and report every member."""
        import jax.numpy as jnp

        from repro.union import manager, planner
        from repro.union.experiment import node_engine, run_members

        exp = self.experiment(0)
        node = planner.plan(exp).nodes[0]
        eng, inits, _ = node_engine(node, exp)
        inits = [s._replace(t=jnp.full_like(s.t, self.horizon_us))
                 for s in inits]
        for cell, st in zip(node.cells, run_members(eng, inits)):
            # a report reads each application's latency only where it
            # delivered a message: mark every one as having delivered
            m = st.metrics._replace(lat_cnt=jnp.ones_like(st.metrics.lat_cnt))
            manager.member_report(st._replace(metrics=m), cell.rs, 0.0,
                                  seed=cell.seed, capacity=node.capacity)

    def check(self) -> Dict[str, float]:
        from reference import member

        simulate, refs = member.simulate, {}

        def once(config, traffic, seed, f=None):
            if seed not in refs:
                refs[seed] = simulate(config, traffic, seed, f)
            return refs[seed]

        member.simulate = once
        try:
            return super().check()
        finally:
            member.simulate = simulate
