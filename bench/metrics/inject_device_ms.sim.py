"""Device milliseconds a tick-loop iteration spends emitting and
injecting: ops under the ``tick/emit`` and ``tick/inject`` scopes (rank
emission, the UR source, and the injection branch's link demand, pool
allocation and routing), over the loop's iterations."""

import tick_phases

PHASES = ("emit", "inject")


def read(ctx):
    return tick_phases.ms_per_iteration(ctx, PHASES)
