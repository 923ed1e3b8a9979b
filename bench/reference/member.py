"""One member of a benchmark cell, simulated by the reference from the
cell's configuration and traffic files and the member's seed: its final
counters."""
from __future__ import annotations

from typing import Dict

import numpy as np

from . import fabric as FB
from . import program as PG
from . import sim as SM


def fabric_of(config: Dict) -> FB.Dragonfly:
    fb, net = config["fabric"], config["net"]
    return FB.build(
        fb["variant"], fb["groups"], fb["routers_per_group"],
        fb["nodes_per_router"], fb["global_per_router"],
        fb.get("rows", 0), fb.get("cols", 0),
        terminal_bw=net["terminal_bw"], local_bw=net["local_bw"],
        global_bw=net["global_bw"])


def simulate(config: Dict, traffic: Dict, seed: int, f: FB.Dragonfly = None,
             dtype=np.float32) -> Dict:
    """The final counters of the member with ``seed``."""
    f = f or fabric_of(config)
    jobs = [SM.Job(PG.parse(j["source"], j["ranks"], j.get("overrides")),
                   j["ranks"], j.get("start_us", 0.0))
            for j in traffic["jobs"]]
    sizes = [j.ranks for j in jobs]
    ur = None
    if traffic.get("ur"):
        u = traffic["ur"]
        ur = SM.UR(u["ranks"], u["size_bytes"], u["interval_us"],
                   u.get("start_us", 0.0))
        sizes.append(ur.ranks)
    net = config["net"]
    return SM.simulate(
        f, jobs, SM.place(f, sizes, traffic["placement"], seed), seed=seed,
        horizon_us=traffic["horizon_ms"] * 1000.0, tick_us=config["tick_us"],
        pool_size=traffic["pool_size"], hop_us=net["hop_latency_us"],
        window_us=net["window_us"], adaptive=config["routing"] == "ADP",
        ur=ur, dtype=dtype)
