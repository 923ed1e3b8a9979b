"""Device milliseconds a tick-loop iteration spends in every other
phase of the tick: ``tick/loop`` (the loop's condition and freeze mask),
``tick/faults``, ``tick/latency``, ``tick/window``, ``tick/skip``,
``tick/counters`` and ``tick/probes``, over the loop's iterations."""

import tick_phases

NAMED = ("emit", "inject", "drain", "deliver", "advance")


def read(ctx):
    return tick_phases.ms_per_iteration(ctx, other_than=NAMED)
