"""The least time the window's ticks need at the HBM peak for the work
they hold: each pool slot active in a drain pass moves
``work.slot_bytes(K)``, and each loop iteration moves the link and
router tables of ``work.tick_bytes`` (with no pool slots); over the
device busy time. Slots counted by the program (``active_slot_ticks`` and
``iterations`` of the window's ``engine.counters`` events), so this is
the least any implementation of the tick needs for the same semantics."""

import tick_phases
import work


def read(ctx):
    runs = tick_phases.counters()
    if not runs:
        return None
    s = ctx["shapes"]
    tables = work.tick_bytes(B=s["B"], M=0, K=s["K"], L=s["L"], R=s["R"],
                             apps=s["apps"])
    least_bytes = (sum(c["active_slot_ticks"] for c in runs)
                   * work.slot_bytes(s["K"])
                   + sum(c["iterations"] for c in runs) * tables)
    busy_s = ctx["reduction"]["busy_ns"] * 1e-9
    return 100.0 * least_bytes / ctx["peaks"]["hbm_bytes_per_s"] / busy_s
