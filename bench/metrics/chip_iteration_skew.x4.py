"""The share of chip time spent waiting on the slowest chip: each call
runs until its slowest chip's loop ends, so a chip that ran fewer
while-loop iterations waits out the difference. Σ over calls and chips
of (the call's largest ``chip_iterations`` − the chip's), over chips ×
Σ of the calls' largest. A program that does not count iterations per
chip gives nothing."""

import tick_phases


def read(ctx):
    runs = [c["chip_iterations"] for c in tick_phases.counters()
            if "chip_iterations" in c]
    top = sum(max(r) for r in runs)
    if not top:
        return None
    waited = sum(max(r) - x for r in runs for x in r)
    return 100.0 * waited / (len(runs[0]) * top)
