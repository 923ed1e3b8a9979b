#!/usr/bin/env python3
"""Bring-up smoke run of the Union engine on a TPU: every phase in one
process, each through the user's entry point ``repro.union.run``.

    python3 chip_smoke.py                    # phases (a)-(d), one chip
    python3 chip_smoke.py --chips 4          # phase (e) only, four chips
    python3 chip_smoke.py --horizon-ms 2.0   # phase (c)/(e) horizon

(a) device: the JAX version and every device's platform and kind. There
    is no CPU path: a platform other than ``tpu``, or a device kind not in
    ``KNOWN_KINDS``, ends the run with a non-zero exit before any result.
(b) goldens: the two scenarios pinned by ``tests/data_engine_golden.json``.
    Hard checks: every job done, nothing dropped, and every VM's ``pc``,
    ``send_done`` and ``recv_done`` equal to the goldens — the programs fix
    these once the jobs finish. Whether ``t`` and ``rng`` match, and the
    largest relative deviation of each float field, are printed only.
(c) paper scale: ``workload1`` on the paper's 1-D dragonfly (8,448 nodes,
    pool 65,536, full rank counts), 2 members. Only the horizon is cut.
    Hard checks: nothing dropped, simulated time reaches the horizon, no
    NaN in any report, and the two members' reports differ. The walls it
    prints are one bring-up run, not metrics.
(d) scheduler windows: the batched ``TraceStudy`` of
    ``benchmarks/bench_union.py --trace --quick``; every job completes.
(e) four chips (``--chips 4`` only): the phase (c) ensemble at 8 members,
    ``pmap``-ed over the chips, against the same members run one by one
    on one chip. The final state must sit on 4 distinct devices and the
    per-member reports must equal the one-chip run.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Every failed check raises, so the process exits non-zero without it.
The phase functions take a ``scale`` so ``tests/test_chip_smoke.py`` can
run them on the CPU at small sizes.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
GOLDEN = os.path.join(ROOT, "tests", "data_engine_golden.json")

# device kinds this smoke run knows, as JAX reports them ("TPU v5 lite"
# is the v5e)
KNOWN_KINDS = frozenset({"TPU v5 lite"})

PAPER_HORIZON_MS = 2.0
FOUR_CHIP_MEMBERS = 8


class SmokeFailure(AssertionError):
    """A hard check of a phase failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(*parts) -> None:
    print(*parts, flush=True)


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------

def phase_device() -> dict:
    import jax

    devs = jax.devices()
    say(f"(a) jax {jax.__version__}, {len(devs)} device(s)")
    for d in devs:
        say(f"    device {d.id}: platform={d.platform} kind={d.device_kind}")
    d0 = devs[0]
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (platform {d0.platform!r}); "
                 "this run has no CPU path")
    if d0.device_kind not in KNOWN_KINDS:
        sys.exit(f"chip_smoke: unknown device kind {d0.device_kind!r}; "
                 f"known: {sorted(KNOWN_KINDS)}")
    return dict(platform=d0.platform, kind=d0.device_kind, count=len(devs))


# ---------------------------------------------------------------------------
# (b) goldens
# ---------------------------------------------------------------------------

def _rel_dev(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    fin = np.isfinite(want)
    if not np.array_equal(np.isfinite(got), fin):
        return math.inf
    if not fin.any():
        return 0.0
    den = np.maximum(np.abs(want[fin]), 1e-30)
    return float(np.max(np.abs(got[fin] - want[fin]) / den))


def phase_goldens() -> None:
    """Both golden scenarios through ``union.run``; the checks read the
    final engine state it keeps (``keep_states``)."""
    import numpy as np

    from repro import union
    from repro.netsim.engine import job_vm

    sys.path.insert(0, ROOT)
    from tests.test_engine_equivalence import CASES

    with open(GOLDEN) as f:
        golden = json.load(f)
    for case in sorted(CASES):
        make, seed = CASES[case]
        sc = make()
        res = union.run(union.Experiment(
            name=sc.name, scenarios=[sc], members=1, base_seed=seed,
            vmapped=False), keep_states=True)
        rep = res.cells[0].report
        check(all(rep["config"]["all_done"]), f"{case}: a job is not done")
        check(rep["dropped"] == 0, f"{case}: {rep['dropped']} dropped")

        st = res.states[0]
        g = golden[case]["state"]
        check(int(st.pool.dropped) == g["dropped"] == 0,
              f"{case}: dropped {int(st.pool.dropped)}")
        for ji in range(len(sc.jobs)):
            vm = job_vm(st, ji)
            check(bool(np.asarray(vm.done).all()) and g[f"vm{ji}_done"],
                  f"{case}: job {ji} not done")
            for field in ("pc", "send_done", "recv_done"):
                got = np.asarray(getattr(vm, field))
                check(np.array_equal(got, g[f"vm{ji}_{field}"]),
                      f"{case}: job {ji} {field} {got.tolist()} != golden "
                      f"{g[f'vm{ji}_{field}']}")
        m = st.metrics
        devs = dict(
            lat_sum=_rel_dev(m.lat_sum, g["lat_sum"]),
            lat_min=_rel_dev(m.lat_min, g["lat_min"]),
            lat_max=_rel_dev(m.lat_max, g["lat_max"]),
            link_bytes_total=_rel_dev(np.asarray(m.link_bytes).sum(),
                                      g["link_bytes_total"]),
            comm_time=max(
                _rel_dev(job_vm(st, ji).comm_time, g[f"vm{ji}_comm_time"])
                for ji in range(len(sc.jobs))),
        )
        say(f"(b) {case}: integer fields equal the goldens; "
            f"t {float(st.t)} (golden {g['t']}, "
            f"{'match' if float(st.t) == g['t'] else 'DIFFERS'}); "
            f"rng {'match' if int(st.rng) == g['rng'] else 'DIFFERS'}")
        say(f"    largest relative deviation: "
            + ", ".join(f"{k} {v:.3g}" for k, v in devs.items()))


# ---------------------------------------------------------------------------
# (c) paper scale
# ---------------------------------------------------------------------------

def _nan_paths(obj, path="report"):
    if isinstance(obj, float) and math.isnan(obj):
        yield path
    elif isinstance(obj, dict):
        for k, v in obj.items():
            yield from _nan_paths(v, f"{path}.{k}")
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            yield from _nan_paths(v, f"{path}[{i}]")


def paper_scenario(scale: str, horizon_ms: float):
    from repro.union.scenario import mix_scenario

    return mix_scenario("workload1", topo="1d", scale=scale, tick_us=5.0,
                        horizon_ms=horizon_ms)


def _check_reports(cells, horizon_ms: float, tag: str) -> None:
    for c in cells:
        rep = c.report
        check(rep["dropped"] == 0, f"{tag} seed {c.seed}: "
              f"{rep['dropped']} messages dropped")
        check(rep["virtual_time_ms"] >= horizon_ms * (1 - 1e-6),
              f"{tag} seed {c.seed}: simulated time "
              f"{rep['virtual_time_ms']} ms short of {horizon_ms} ms")
        nans = list(_nan_paths(rep))
        check(not nans, f"{tag} seed {c.seed}: NaN at {nans[:5]}")


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def phase_paper(scale: str = "paper", horizon_ms: float = PAPER_HORIZON_MS,
                members: int = 2) -> dict:
    """``members`` of workload1 on the 1-D dragonfly at ``scale``: a cold
    ``union.run`` (it compiles, or loads from the persistent cache) and a
    warm one with fresh seeds."""
    from repro import union
    from repro.obs import spans

    sc = paper_scenario(scale, horizon_ms)

    def campaign(base_seed):
        t0 = time.perf_counter()
        res = union.run(union.Experiment(
            name=sc.name, scenarios=[sc], members=members,
            base_seed=base_seed))
        return time.perf_counter() - t0, res

    was_tracing = spans.tracing()
    spans.enable()  # host spans per campaign, not per tick
    try:
        cold_s, res_cold = campaign(0)
        warm_s, res_warm = campaign(members)
    finally:
        if not was_tracing:
            spans.disable()
    for tag, res in (("cold", res_cold), ("warm", res_warm)):
        _check_reports(res.cells, horizon_ms, f"(c) {tag}")
        a, b = (dict(c.report, config=None, sim_wall_s=None)
                for c in res.cells[:2])
        check(a != b, f"(c) {tag}: the members' reports are identical")
    cfg = res_warm.cells[0].report["config"]
    sim_us = horizon_ms * 1000.0
    out = dict(
        scale=scale, horizon_ms=horizon_ms, members=members,
        ranks=cfg["ranks"], envelope=cfg["envelope"],
        cold_wall_s=cold_s, warm_wall_s=warm_s,
        sim_us_per_wall_s=sim_us / warm_s,
        peak_bytes_in_use=_peak_bytes(),
    )
    say(f"(c) workload1 {scale} 1-D dragonfly, ranks {cfg['ranks']}, "
        f"envelope {cfg['envelope']}, {members} members, horizon "
        f"{horizon_ms} ms: dropped 0, no NaN, members differ")
    say(f"    one bring-up run, not a metric: cold union.run {cold_s:.3f} s "
        f"(compile or cache load), warm {warm_s:.3f} s, "
        f"{sim_us / warm_s:.1f} simulated us per wall s ({members} members "
        f"at once), peak_bytes_in_use "
        f"{out['peak_bytes_in_use']}")
    for tag, res in (("cold", res_cold), ("warm", res_warm)):
        by_name = res.telemetry["spans"]["by_name"]
        out[f"{tag}_spans_ms"] = {k: v["total_ms"] for k, v in by_name.items()}
        say(f"    {tag} host spans (ms): " + ", ".join(
            f"{k} {v['total_ms']:.1f}" for k, v in sorted(
                by_name.items(), key=lambda kv: -kv[1]["total_ms"])))
    return out


# ---------------------------------------------------------------------------
# (d) scheduler windows
# ---------------------------------------------------------------------------

def phase_windows() -> dict:
    """The batched TraceStudy: ``run_window``, ``window_host_view`` and
    ``admit_jobs``/``retire_jobs`` through one lock-stepped engine."""
    from repro import union

    sys.path.insert(0, ROOT)
    from benchmarks.bench_union import _bench_trace_spec

    trace, n_jobs, slots, seeds, policies = _bench_trace_spec(quick=True)
    t0 = time.perf_counter()
    res = union.run(union.Experiment(
        name="chip-smoke-trace",
        trace=union.TraceStudy(trace=trace, policies=policies, seeds=seeds,
                               batch=True)))
    wall = time.perf_counter() - t0
    total = n_jobs * len(seeds) * len(policies)
    completed = sum(c.report["completed"] for c in res.cells)
    check(completed == total, f"(d) {completed}/{total} jobs completed")
    kinds = res.telemetry["node_kinds"]
    check("windowed_batch" in kinds, f"(d) no batched windows ran: {kinds}")
    windows = max(c.report["windows"] for c in res.cells)
    say(f"(d) batched trace: {completed}/{total} jobs completed in "
        f"{len(res.cells)} cells, {windows} windows, {slots} slots "
        f"({wall:.3f} s with compiles or cache loads)")
    return dict(completed=completed, windows=windows)


# ---------------------------------------------------------------------------
# (e) four chips
# ---------------------------------------------------------------------------

def _same_report(a: dict, b: dict, tag: str) -> None:
    """Per-member equality as the CPU tests hold batched and sequential
    runs to it: exact time and counts, floats to rtol 1e-6."""
    check(a["virtual_time_ms"] == b["virtual_time_ms"],
          f"{tag}: virtual time {a['virtual_time_ms']} != "
          f"{b['virtual_time_ms']}")
    check(a["dropped"] == b["dropped"], f"{tag}: dropped differ")
    for app, la in a["latency"].items():
        lb = b["latency"][app]
        check(la["count"] == lb["count"], f"{tag} {app}: latency count "
              f"{la['count']} != {lb['count']}")
        if la["count"]:
            check(math.isclose(la["avg_us"], lb["avg_us"], rel_tol=1e-6),
                  f"{tag} {app}: avg latency {la['avg_us']} != "
                  f"{lb['avg_us']}")
    for app, ca in a["comm_time"].items():
        cb = b["comm_time"][app]
        check(math.isclose(ca["max_ms"], cb["max_ms"], rel_tol=1e-6),
              f"{tag} {app}: comm time {ca['max_ms']} != {cb['max_ms']}")


def phase_four_chips(scale: str = "paper",
                     horizon_ms: float = PAPER_HORIZON_MS,
                     members: int = FOUR_CHIP_MEMBERS) -> dict:
    """``members`` through ``union.run`` sharded over every local device
    (``Engine.prun``), against the same members one by one on one."""
    import jax

    from repro import union

    n_dev = jax.local_device_count()
    check(n_dev > 1 and members % n_dev == 0,
          f"(e) {members} members cannot shard over {n_dev} device(s)")
    sc = paper_scenario(scale, horizon_ms)

    def campaign(vmapped):
        t0 = time.perf_counter()
        res = union.run(union.Experiment(
            name=sc.name, scenarios=[sc], members=members, base_seed=0,
            vmapped=vmapped))
        return time.perf_counter() - t0, res

    one_s, one = campaign(False)
    check(one.telemetry["devices"] == [jax.devices()[0].id],
          f"(e) the one-chip run used devices {one.telemetry['devices']}")
    shard_s, sharded = campaign(True)
    # the ids of the devices holding the final state's shards
    devices = sharded.telemetry["devices"]
    check(len(devices) == n_dev,
          f"(e) final state on {len(devices)} device(s): {devices}")
    _check_reports(sharded.cells, horizon_ms, "(e) sharded")
    for a, b in zip(sharded.cells, one.cells):
        check(a.seed == b.seed, "(e) member order differs")
        _same_report(a.report, b.report, f"(e) seed {a.seed}")
    say(f"(e) {members} members of workload1 {scale}, horizon {horizon_ms} "
        f"ms: final state on {len(devices)} distinct devices "
        f"({devices}); every member's report equals "
        "the one-chip run")
    say(f"    one bring-up run, not a metric: sharded union.run "
        f"{shard_s:.3f} s, one chip one by one {one_s:.3f} s (both "
        "compile or load from the cache)")
    return dict(devices=len(devices), sharded_wall_s=shard_s,
                one_chip_wall_s=one_s)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run phase (e) across four chips, nothing else")
    ap.add_argument("--horizon-ms", type=float, default=PAPER_HORIZON_MS,
                    help="simulated horizon of phases (c) and (e)")
    args = ap.parse_args(argv)

    device = phase_device()
    if args.chips == 4:
        check(device["count"] >= 4,
              f"--chips 4 needs 4 devices, JAX sees {device['count']}")
    sys.path.insert(0, SRC)
    from repro.compile_cache import use_persistent_cache

    say(f"    compile cache: {use_persistent_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(horizon_ms=args.horizon_ms)
    else:
        phase_goldens()
        phase_paper(horizon_ms=args.horizon_ms)
        phase_windows()
    say(f"    all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps(dict(ok=True, device=device)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
