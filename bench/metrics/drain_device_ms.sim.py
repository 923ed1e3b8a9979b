"""Device milliseconds a tick-loop iteration spends in the drain: ops
under the ``tick/drain`` scope (link demand, fair-share rates, drain and
delivery over the whole pool, per-link byte counters), over the loop's
iterations."""

import tick_phases


def read(ctx):
    return tick_phases.ms_per_iteration(ctx, ("drain",))
