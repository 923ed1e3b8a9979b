"""The pool slots the window's drain passes ran over, as a share of the
full pool's: Σ over the drain's rungs of ticks x width
(``drain_slot_ticks`` of the window's ``engine.counters`` events) over
member ticks x pool slots. 100% is a drain over the whole pool every
tick. A program that does not count its drain widths gives nothing."""

import tick_phases


def read(ctx):
    runs = tick_phases.counters()
    if not runs or any("drain_slot_ticks" not in c for c in runs):
        return None
    ticks = sum(c["member_ticks"] for c in runs)
    if not ticks:
        return None
    drained = sum(c["drain_slot_ticks"] for c in runs)
    return 100.0 * drained / (ticks * ctx["shapes"]["M"])
