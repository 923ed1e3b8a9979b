"""The least bytes one engine tick must move through HBM, from the
cell's shapes alone, whatever implements the tick.

One tick's pool pass reads every pool slot's route (``K`` int32 link
ids), its bytes left (f32, read and written), its active flag (read and
written), its application id (int32), its earliest delivery time and
its injection time (f32); it reads each link's bandwidth and destination
router, reads and writes each link's byte counter, and reads and writes
each application's per-router window counters. ``B`` members, ``M``
pool slots, ``L`` links (plus one dummy row), ``R`` routers, ``apps``
applications.
"""
from __future__ import annotations


def slot_bytes(K: int) -> int:
    # routes, bytes left in + out, active in + out, app id, arrival, inject
    return 4 * K + (4 + 4) + (1 + 1) + 4 + 4 + 4


def tick_bytes(B: int, M: int, K: int, L: int, R: int, apps: int) -> int:
    links = (L + 1) * (4 + 4) + B * (L + 1) * 4 * 2
    routers = B * apps * R * 4 * 2
    return B * M * slot_bytes(K) + links + routers


def least_tick_s(shapes: dict, hbm_bytes_per_s: float) -> float:
    """Seconds one tick needs at the HBM peak."""
    return tick_bytes(**shapes) / hbm_bytes_per_s
