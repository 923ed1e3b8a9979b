"""Where a warm engine run spends the chip's time: one profiler trace of
``chip_smoke.py``'s paper-scale program, reduced to per-op device time.

    python3 benchmarks/profile_tick.py

The program is phase (c) of ``chip_smoke.py``: the paper's workload1 on
the paper's 1-D dragonfly (8,448 nodes, pool 65,536), ``MEMBERS`` at
once, only the horizon cut (``HORIZON_MS``). The engine and its initial states are the
ones ``union.run`` draws (``repro.netsim.rehearse.scenario_engine``).
One first ``engine.run`` (it compiles, or loads from the persistent
cache), three timed warm runs, then one more warm run inside
``jax.profiler.trace`` under a host annotation (``ANNOTATION``). The
trace goes to ``chiprun_out/profile_tick/`` and the reduction to
``chiprun_out/profile_tick.json``; the last line of standard output is
that reduction's summary as JSON.

The reduction (:func:`reduce_trace`) reads the xplane file with
``jax.profiler.ProfileData``: the device plane's ``XLA Ops`` line, clipped
to the annotated window on the host's line. On a TPU each event is named
by its HLO instruction's text (``%fusion.544 = f32[10560]{...}
fusion(s32[1310720]{...} ...)``). The reduction gives:

- busy time, the union of the ops' intervals, and the idle share, one
  minus busy over the window;
- each instruction's run count and self time (its duration less the ops
  nested in it);
- self time by run count: an op that runs once per tick runs as often as
  the tick loop, one inside the injection branch as often as it is taken;
- self time by role over the pool's route entries (B·M·K): a ``scatter``
  reads an s32 index operand of that length into a smaller output, a
  ``gather`` writes an output of that length from a smaller table.

:func:`roofline_estimate` is an estimate, not a measurement: 8 bytes per
route entry (index and value) at the published HBM peak in
``PEAK_HBM_BYTES_PER_S``, over the measured mean time of one op of a
role.

It runs only on a TPU: anywhere else it exits non-zero before any
result.
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import sys
import time
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "chiprun_out")

ANNOTATION = "profile_tick.run"
# chip_smoke.py phase (c)'s program: its default horizon and members
HORIZON_MS = 2.0
MEMBERS = 2
WARM_RUNS = 3
DEVICE_PLANE = "/device:TPU:0"
OPS_LINE = "XLA Ops"

# published HBM bandwidth per device kind (Google Cloud documentation,
# "TPU v5e"); a kind not listed is an error, not a default
PEAK_HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}

_HLO = re.compile(r"^%?(\S+) = (.*?) ([\w\-]+)\((.*)$")
_SHAPE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")


def _sizes(text: str):
    """``(dtype, element count)`` of every array shape in ``text``."""
    return [(dt, math.prod(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(text)]


def parse_op(name: str) -> dict:
    """An event name as ``inst`` (the HLO instruction's name), ``opcode``,
    and the ``out``/``operands`` shapes; names that are not HLO text (as
    on the CPU) are their own instruction."""
    m = _HLO.match(name)
    if not m:
        return dict(inst=name, opcode=re.sub(r"\.\d+$", "", name),
                    out=[], operands=[])
    inst, out, opcode, rest = m.groups()
    # the operand list ends at the ")" before the attributes (layouts
    # hold parentheses of their own)
    operands = re.split(r"\)(?=,\s*[a-z_]+=|\s*$)", rest, maxsplit=1)[0]
    return dict(inst=inst, opcode=opcode, out=_sizes(out),
                operands=_sizes(operands))


def role(op: dict, entries: int):
    """``scatter``, ``gather`` or None for one parsed op, relative to
    ``entries`` route entries."""
    if len(op["out"]) != 1:
        return None
    n_out = op["out"][0][1]
    idx = any(dt == "s32" and n == entries for dt, n in op["operands"])
    if not idx:
        return None
    if n_out < entries:
        return "scatter"
    if n_out == entries and any(n < entries for _, n in op["operands"]):
        return "gather"
    return None


def _busy_ns(spans) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    busy, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(spans):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy


def reduce_events(events, window, entries: int = 0):
    """Device time of ``events`` — ``(name, start_ns, dur_ns)`` on one
    device line — clipped to ``window`` ``(start_ns, end_ns)``, per HLO
    instruction, per run count and per role over ``entries``."""
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
    stack = []  # indices of the enclosing ops
    for i, (s, e, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    parsed = {}
    ops = {}
    for (s, e, name), own in zip(spans, self_ns):
        if name not in parsed:
            parsed[name] = parse_op(name)
        p = parsed[name]
        row = ops.setdefault(p["inst"], dict(
            opcode=p["opcode"], role=role(p, entries), count=0,
            total_ns=0.0, self_ns=0.0, hlo=name[:160]))
        row["count"] += 1
        row["total_ns"] += e - s
        row["self_ns"] += own
    by_count = defaultdict(lambda: dict(ops=0, self_ns=0.0))
    by_role = defaultdict(lambda: dict(ops=0, count=0, self_ns=0.0))
    for row in ops.values():
        c = by_count[row["count"]]
        c["ops"] += 1
        c["self_ns"] += row["self_ns"]
        if row["role"]:
            r = by_role[row["role"]]
            r["ops"] += 1
            r["count"] += row["count"]
            r["self_ns"] += row["self_ns"]
    window_ns = w1 - w0
    busy = _busy_ns([(s, e) for s, e, _ in spans])
    return dict(
        window_ns=window_ns, busy_ns=busy,
        idle_share=1.0 - busy / window_ns,
        events=len(spans), entries=entries,
        by_role=dict(by_role),
        by_count=dict(sorted(by_count.items(),
                             key=lambda kv: -kv[1]["self_ns"])),
        ops=dict(sorted(ops.items(), key=lambda kv: -kv[1]["self_ns"])),
    )


def _line_events(pd, plane_name, line_name):
    plane = pd.find_plane_with_name(plane_name)
    if plane is None:
        raise ValueError(f"no plane {plane_name!r} in the trace")
    lines = [line for line in plane.lines if line.name == line_name]
    if not lines:
        raise ValueError(f"no line {line_name!r} on {plane_name!r}; it has "
                         f"{[line.name for line in plane.lines]}")
    return [(e.name, e.start_ns, e.duration_ns)
            for line in lines for e in line.events]


def annotated_window(pd, annotation: str = ANNOTATION):
    """``(start_ns, end_ns)`` of the one host event named ``annotation``."""
    found = [(e.start_ns, e.start_ns + e.duration_ns)
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == annotation]
    if len(found) != 1:
        raise ValueError(f"{len(found)} host events named {annotation!r}, "
                         "not 1")
    return found[0]


def reduce_trace(pd, entries: int = 0, plane: str = DEVICE_PLANE,
                 line: str = OPS_LINE, annotation: str = ANNOTATION):
    """:func:`reduce_events` over ``plane``'s ``line`` inside the
    annotated window of a ``jax.profiler.ProfileData``."""
    return reduce_events(_line_events(pd, plane, line),
                         annotated_window(pd, annotation), entries)


def roofline_estimate(red, kind: str):
    """Per role, the estimated share of the HBM roofline one op reaches:
    the least time to move 8 bytes per route entry at the published peak,
    over the measured mean self time of one op of that role. An estimate
    from an assumed peak and a byte count from shapes, not a measured
    share."""
    peak = PEAK_HBM_BYTES_PER_S[kind]
    least_s = red["entries"] * 8 / peak
    out = {}
    for r, v in red["by_role"].items():
        mean_s = v["self_ns"] / v["count"] * 1e-9
        out[r] = dict(bytes=red["entries"] * 8, peak_bytes_per_s=peak,
                      least_s=least_s, mean_s=mean_s,
                      share=least_s / mean_s)
    return out


def main(argv=None) -> int:
    argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0]).parse_args(argv)

    import jax

    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        sys.exit(f"profile_tick: no TPU (platform {d0.platform!r})")
    if d0.device_kind not in PEAK_HBM_BYTES_PER_S:
        sys.exit(f"profile_tick: unknown device kind {d0.device_kind!r}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from jax.profiler import ProfileData, TraceAnnotation

    from repro.netsim.rehearse import scenario_engine
    from repro.union.scenario import mix_scenario

    sc = mix_scenario("workload1", topo="1d", scale="paper", tick_us=5.0,
                      horizon_ms=HORIZON_MS)
    eng, state = scenario_engine(sc, members=MEMBERS)

    def timed():
        t0 = time.perf_counter()
        final = jax.block_until_ready(eng.run(state))
        return time.perf_counter() - t0, final

    first_s, final = timed()
    warm_s = [timed()[0] for _ in range(WARM_RUNS)]
    trace_dir = os.path.join(OUT, "profile_tick")
    with jax.profiler.trace(trace_dir):
        with TraceAnnotation(ANNOTATION):
            traced_s, _ = timed()
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    entries = math.prod(state.pool.routes.shape)
    red = reduce_trace(ProfileData.from_file(path), entries)
    roof = roofline_estimate(red, d0.device_kind)
    out = dict(
        device=dict(platform=d0.platform, kind=d0.device_kind,
                    count=len(jax.devices())),
        horizon_ms=HORIZON_MS, members=MEMBERS,
        sim_t_us=[float(t) for t in jax.device_get(final.t)],
        first_s=first_s, warm_s=warm_s, traced_s=traced_s,
        trace=os.path.relpath(path, ROOT), reduction=red,
        roofline_estimate=roof,
    )
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "profile_tick.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(report(out))
    print(json.dumps(dict(
        device=out["device"], warm_s=warm_s,
        idle_share=red["idle_share"],
        by_role={r: v["self_ns"] / red["window_ns"]
                 for r, v in red["by_role"].items()})))
    return 0


def report(out) -> str:
    """The human-readable lines of one ``profile_tick.json``."""
    red = out["reduction"]
    w = red["window_ns"]
    lines = [
        f"first run {out['first_s']:.3f} s (compile or cache load), warm "
        + ", ".join(f"{s:.3f}" for s in out["warm_s"])
        + f" s, traced {out['traced_s']:.3f} s",
        f"window {w / 1e9:.6f} s, device busy {red['busy_ns'] / 1e9:.6f} s, "
        f"idle share {red['idle_share']:.6f}, {red['events']} op events",
        f"by role over {red['entries']} route entries:",
    ]
    for r, v in red["by_role"].items():
        est = out["roofline_estimate"][r]
        lines.append(
            f"  {r:<8} {v['ops']:>3} ops x {v['count']:>5} runs  self "
            f"{v['self_ns'] / 1e9:.6f} s ({v['self_ns'] / w:.2%}); roofline "
            f"estimate {est['share']:.4%} ({est['least_s'] * 1e6:.1f} us "
            f"least vs {est['mean_s'] * 1e6:.1f} us mean)")
    lines.append("by run count (ops that ran N times):")
    for n, v in list(red["by_count"].items())[:6]:
        lines.append(f"  {n:>6} runs: {v['ops']:>5} ops, self "
                     f"{v['self_ns'] / 1e9:.6f} s ({v['self_ns'] / w:.2%})")
    lines.append("top ops by self time:")
    for inst, v in list(red["ops"].items())[:12]:
        lines.append(f"  {inst:<16} {v['opcode']:<10} {v['role'] or '-':<8}"
                     f" {v['count']:>6} runs  self {v['self_ns'] / 1e9:.6f} s"
                     f" ({v['self_ns'] / w:.2%})  {v['hlo'][:90]}")
    return "\n".join(lines)


if __name__ == "__main__":
    raise SystemExit(main())
