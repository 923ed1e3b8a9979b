"""Device milliseconds of one chip's tick-loop iteration when the members
are sharded over the host's chips: the self time on ``/device:TPU:0`` of
the ops the program's map names (``repro.obs.device_phases``), over chip
0's while-loop iterations (the first entry of each ``engine.counters``
event's ``chip_iterations``). A program that does not count iterations
per chip gives nothing."""

import tick_phases


def read(ctx):
    by_phase = tick_phases.phase_ns(ctx)
    runs = [c["chip_iterations"] for c in tick_phases.counters()
            if "chip_iterations" in c]
    n = sum(r[0] for r in runs)
    if by_phase is None or not n:
        return None
    return sum(by_phase.values()) / n * 1e-6
