"""Device milliseconds a tick-loop iteration spends in the rank VMs
after the drain: ops under ``tick/deliver`` (delivery notices, the free
stack) and ``tick/advance`` (op completion and advance), over the loop's
iterations."""

import tick_phases


def read(ctx):
    return tick_phases.ms_per_iteration(ctx, ("deliver", "advance"))
