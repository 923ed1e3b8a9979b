"""From a ``jax.profiler`` trace to the numbers the per-layer metrics read.

The device plane's ``XLA Ops`` line, clipped to the one host event named
by the annotation the traced run opens around its window. On a TPU each
event is named by its HLO instruction's text (``%fusion.544 =
f32[10560]{...} fusion(s32[1310720]{...} ...)``). The reduction gives:

- ``busy_ns``, the union of the ops' intervals, and ``idle_share``, one
  minus busy over the window;
- each instruction's run count and self time (its duration less the ops
  nested in it), and ``ticks``: the largest run count among the ops
  that hold at least ``TICK_OP_SHARE`` of the busy time. The tick loop's
  own passes run once per tick; small bookkeeping ops (async copies,
  the loop's condition) may run more often and are left out;
- the idle gaps between device ops, longest first.

Copied from ``benchmarks/profile_tick.py`` so that the benchmark keeps
its own arithmetic when the program changes.
"""
from __future__ import annotations

import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:0"
OPS_LINE = "XLA Ops"
TICK_OP_SHARE = 0.01

_HLO = re.compile(r"^%?(\S+) = (.*?) ([\w\-]+)\((.*)$")


def parse_op(name: str) -> Dict[str, str]:
    """An event name as ``inst`` (the HLO instruction's name) and
    ``opcode``; names that are not HLO text are their own instruction."""
    m = _HLO.match(name)
    if not m:
        return dict(inst=name, opcode=re.sub(r"\.\d+$", "", name))
    return dict(inst=m.group(1), opcode=m.group(3))


def _union(spans: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce_events(events, window) -> Dict:
    """Device time of ``events`` — ``(name, start_ns, dur_ns)`` on one
    device line — clipped to ``window`` ``(start_ns, end_ns)``."""
    w0, w1 = window
    spans = []
    for name, s, d in events:
        s0, e0 = max(s, w0), min(s + d, w1)
        if e0 > s0:
            spans.append((s0, e0, name))
    if not spans:
        raise ValueError(f"no device op inside the window {window}: the "
                         "host and device clocks do not line up")
    spans.sort(key=lambda x: (x[0], -x[1]))
    self_ns = [e - s for s, e, _ in spans]
    stack: List[int] = []  # indices of the enclosing ops
    for i, (s, e, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    ops: Dict[str, Dict] = {}
    for (s, e, name), own in zip(spans, self_ns):
        p = parse_op(name)
        row = ops.setdefault(p["inst"], dict(
            opcode=p["opcode"], count=0, total_ns=0.0, self_ns=0.0))
        row["count"] += 1
        row["total_ns"] += e - s
        row["self_ns"] += own
    busy = _union([(s, e) for s, e, _ in spans])
    busy_ns = sum(e - s for s, e in busy)
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = sorted(((edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]),
                  key=lambda g: g[0] - g[1])
    return dict(
        window_ns=w1 - w0, busy_ns=busy_ns,
        idle_share=1.0 - busy_ns / (w1 - w0), events=len(spans),
        ticks=max(r["count"] for r in ops.values()
                  if r["self_ns"] >= TICK_OP_SHARE * busy_ns),
        ops=dict(sorted(ops.items(), key=lambda kv: -kv[1]["self_ns"])),
        gaps=gaps,
    )


def line_events(pd, plane_name: str = DEVICE_PLANE,
                line_name: str = OPS_LINE):
    plane = pd.find_plane_with_name(plane_name)
    if plane is None:
        raise ValueError(f"no plane {plane_name!r} in the trace")
    lines = [line for line in plane.lines if line.name == line_name]
    if not lines:
        raise ValueError(f"no line {line_name!r} on {plane_name!r}; it has "
                         f"{[line.name for line in plane.lines]}")
    return [(e.name, e.start_ns, e.duration_ns)
            for line in lines for e in line.events]


def annotated_window(pd, annotation: str) -> Tuple[float, float]:
    """``(start_ns, end_ns)`` of the one host event named ``annotation``."""
    found = [(e.start_ns, e.start_ns + e.duration_ns)
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == annotation]
    if len(found) != 1:
        raise ValueError(f"{len(found)} host events named {annotation!r}, "
                         "not 1")
    return found[0]


def label_gaps(gaps, spans, offset_ns: float, top: int = 10):
    """Name each of the ``top`` longest idle gaps by the innermost host
    span that covers its middle. ``spans`` are ``(name, start_ns,
    end_ns)`` on the host clock; ``offset_ns`` moves them onto the
    trace's clock."""
    out = []
    for g0, g1 in gaps[:top]:
        mid = (g0 + g1) / 2 - offset_ns
        inside = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        out.append([min(inside)[1] if inside else "outside union.run",
                    (g1 - g0) * 1e-9])
    return out


def top_ops(red: Dict, top: int = 10):
    """``[[instruction opcode, self seconds], ...]``, most time first."""
    return [[f"{inst} {row['opcode']}", row["self_ns"] * 1e-9]
            for inst, row in list(red["ops"].items())[:top]]
