"""The candidates the window's injecting ticks routed, as a share of
all they had: the candidates routed, job and UR, padding included
(``inject_cand_ticks`` of the window's ``engine.counters`` events), over
injecting member ticks x every candidate of a member
(``inject_cand_width``). 100% is every candidate routed on every
injecting tick. A program that does not count its injection widths
gives nothing."""

import tick_phases


def read(ctx):
    runs = tick_phases.counters()
    if not runs or any("inject_cand_ticks" not in c for c in runs):
        return None
    full = sum(c["inject_ticks"] * c["inject_cand_width"] for c in runs)
    if not full:
        return None
    return 100.0 * sum(c["inject_cand_ticks"] for c in runs) / full
