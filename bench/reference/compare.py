"""What the check compares between the program and the reference.

For an ensemble member, its final state, as three numbers:

- ``link_bytes_gap``: the bytes each link carried (injection, routing
  and the drain), as the sum over links of the absolute difference over
  the reference's total;
- ``latency_gap``: each application's delivered-message count, latency
  sum, least and largest latency (the drain), as the largest relative
  difference; an application the reference never delivers for must have
  none delivered by the program either;
- ``state_mismatches``: how many of the exact integers differ: each
  rank's program counter and cumulative sends and receives completed
  (the rank programs), each background rank's sends (the uniform-random
  source), and the final clock.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def _rel(got: float, want: float) -> float:
    if got == want:
        return 0.0
    return abs(got - want) / max(abs(want), 1e-30)


def gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    lb_p = np.asarray(prog["link_bytes"], np.float64)
    lb_r = np.asarray(ref["link_bytes"], np.float64)
    link = float(np.abs(lb_p - lb_r).sum() / max(lb_r.sum(), 1.0))

    lat = 0.0
    for a in range(len(prog["lat_cnt"])):
        c_p, c_r = int(prog["lat_cnt"][a]), int(ref["lat_cnt"][a])
        if c_r == 0:
            lat = max(lat, 0.0 if c_p == 0 else 1.0)
            continue
        lat = max(lat, _rel(c_p, c_r),
                  *(_rel(float(prog[k][a]), float(ref[k][a]))
                    for k in ("lat_sum", "lat_min", "lat_max")))

    miss = int(float(prog["t"]) != float(ref["t"]))
    for k in ("pc", "send_done", "recv_done"):
        for p, r in zip(prog[k], ref[k]):
            miss += int(np.count_nonzero(np.asarray(p) != np.asarray(r)))
    miss += int(np.count_nonzero(
        np.asarray(prog["ur_count"]) != np.asarray(ref["ur_count"])))
    return dict(link_bytes_gap=link, latency_gap=lat,
                state_mismatches=float(miss))
