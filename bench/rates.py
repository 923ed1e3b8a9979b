"""The measured window: calls until ``seconds`` have passed, and the
rate of all their work over all of the window's time.

A call that is running when ``seconds`` have passed finishes and
counts, so a window lasts at most one call longer than ``seconds``. The
rate is the work of every call over the time from the first call's
start to the last call's end, gaps between calls included.
"""
from __future__ import annotations

import time
from typing import Callable, List, Sequence, Tuple

import numpy as np


def run_window(call: Callable[[int], float], seconds: float,
               clock: Callable[[], float] = time.perf_counter
               ) -> List[Tuple[float, float, float]]:
    """Run ``call(k)`` for k = 0, 1, ... until ``seconds`` have passed
    since the first started; ``[(start, end, work), ...]``."""
    out: List[Tuple[float, float, float]] = []
    t0 = clock()
    while not out or clock() - t0 < seconds:
        s = clock()
        work = call(len(out))
        out.append((s, clock(), work))
    return out


def window_rate(calls: List[Tuple[float, float, float]]) -> float:
    span = calls[-1][1] - calls[0][0]
    return sum(w for _, _, w in calls) / span


def call_members(fixed: Sequence[int], members: int, seed: int,
                 k: int) -> List[int]:
    """The member seeds of call ``k`` of a run with ``seed``: the calls
    walk through ``fixed`` in passes, each pass a permutation drawn from
    ``seed``, ``members`` at a time. Every run simulates the same set of
    members, in an order of its own, so the seed does not change how much
    work a window holds."""
    n = len(fixed)
    if not 0 < members <= n or n % members:
        raise ValueError(f"{n} member seeds do not split into calls of "
                         f"{members}")
    i = k * members
    rng = np.random.default_rng([seed % 2**63, i // n])
    order = [int(fixed[j]) for j in rng.permutation(n)]
    return order[i % n:i % n + members]
