"""What the per-phase metrics of the engine tick read: the traced
window's device ops (``ctx["reduction"]["ops"]``, self time by HLO
instruction), named by the program's own map of instructions to the
``tick/<phase>`` scopes they were traced in (``repro.obs.device_phases``),
and the while-loop iterations the program counted itself (the window's
``engine.counters`` events; the tracer is off during set-up).

A program without the map or the counters gives nothing, and every
metric that needs them then reads ``None``. An op of another program
whose instruction name equals one of the engine's is counted with it
only where the map names that instruction.
"""
from __future__ import annotations

from typing import Dict, List, Optional


def counters() -> List[Dict[str, float]]:
    """The ``engine.counters`` events of the window, one a run."""
    from repro import obs

    return [e["args"] for e in obs.get_tracer().events
            if e.get("ph") == "C" and e.get("name") == "engine.counters"]


def iterations() -> Optional[float]:
    """While-loop iterations in the window: the tick loop ran this often."""
    n = sum(c["iterations"] for c in counters())
    return n or None


def phase_ns(ctx) -> Optional[Dict[str, float]]:
    """Device self nanoseconds of the window by tick phase."""
    from repro import obs

    device_phases = getattr(obs, "device_phases", None)
    names = device_phases() if device_phases is not None else {}
    if not names:
        return None
    out: Dict[str, float] = {}
    for inst, row in ctx["reduction"]["ops"].items():
        phase = names.get(inst)
        if phase is not None:
            out[phase] = out.get(phase, 0.0) + row["self_ns"]
    return out


def ms_per_iteration(ctx, phases=None, other_than=()) -> Optional[float]:
    """Device ms an iteration spends in ``phases``, or in every phase but
    ``other_than`` when ``phases`` is None."""
    by_phase, n = phase_ns(ctx), iterations()
    if by_phase is None or n is None:
        return None
    ns = sum(v for p, v in by_phase.items()
             if (p in phases if phases is not None else p not in other_than))
    return ns / n * 1e-6
