"""Ensemble-throughput bench: batched vs looped campaigns (members/sec).

The engine's batching claim, measured: an N-member campaign (different
seeds × placements) through the natively-batched engine — member chunks
sharded across XLA devices (CPU cores are exposed as host devices
automatically) — vs a Python loop over the same jitted engine. Every run
goes through the Experiment facade (``union.run``); engines come from the
process-wide cache, so the warm run of each mode measures steady-state
members/sec. Each ``BENCH_union.json`` entry records its provenance (git
commit, jax version, backend, device count, compile cache). ``--quick``
is the CI smoke profile.

``main`` keeps JAX's persistent compile cache (``repro.compile_cache``),
so a profile's "cold" numbers are a compile only where the cache held
no entry for it. ``provenance["compile_cache"]`` records the cache
directory and how many entries it held when the process started (0:
every cold number is a compile); entries without that block predate
the cache and always compiled.

``--trace`` switches to the online-scheduler profile instead: the same
(seeds × policies) grid over a synthetic Poisson trace run both ways —
lock-stepped through one batched windowed engine (the planner's
``WindowedBatchNode``) and as sequential per-cell loops — recording
aggregate jobs/sec for each path and the batched speedup (the results
are bit-identical; the delta is pure execution strategy).

``--experiment`` measures the facade itself: warm ``union.run`` wall vs
the direct engine-level path at the same envelope (spec validation +
planning + summary must cost <= 2% warm).

``--fabric`` sweeps the same tiny mix over every registered fabric
(dragonfly 1d/2d, fat-tree, torus), recording cold (compile) and warm
tick wall per fabric — the cross-fabric cost profile of the pluggable
topology layer.

``--failures`` measures the failure-campaign promise (docs/faults.md):
the same ensemble healthy and on a degraded fabric (20% of fabric
links at half bandwidth) through ONE shared engine — the degraded
campaign's first run must cost zero engine builds (fault masks are
runtime data), and the warm walls give the degraded fabric's
steady-state simulation premium.

``--serve`` measures the simulation-as-a-service stack (docs/serve.md):
one in-process Union server with a fresh content-hash store takes the
same experiment at three temperatures — cold first submit (compile +
simulate + store-miss), warm re-submit with new seeds (engine cached,
store-miss), and a verbatim re-submit (pure store replay, 0 cells
simulated) — each measured as client-side submit-to-done wall over real
HTTP.

  PYTHONPATH=src python -m benchmarks.bench_union [--members 8] [--quick]
  PYTHONPATH=src python -m benchmarks.bench_union --trace [--quick]
  PYTHONPATH=src python -m benchmarks.bench_union --experiment [--quick]
  PYTHONPATH=src python -m benchmarks.bench_union --fabric [--quick]
  PYTHONPATH=src python -m benchmarks.bench_union --failures [--quick]
  PYTHONPATH=src python -m benchmarks.bench_union --serve [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

ROOT = os.path.join(os.path.dirname(__file__), "..")

# set by main(): the persistent compile cache's directory and its entry
# count when this process started
COMPILE_CACHE = None


def bench_scenario(quick: bool):
    from repro.union.scenario import Scenario, ScenarioJob

    reps = 4 if quick else 12
    ar = (
        f"For {reps} repetitions {{\n"
        " all tasks allreduce a 1 MiB message then\n"
        " all tasks compute for 1 milliseconds }"
    )
    return Scenario(
        name="bench-ensemble-quick" if quick else "bench-ensemble",
        jobs=[
            ScenarioJob(app="ar32", source=ar, ranks=32),
            ScenarioJob(app="nn", overrides={"iters": 1 if quick else 2},
                        start_us=1000.0),
        ],
        placement="RN", routing="ADP", tick_us=10.0,
        horizon_ms=80.0 if quick else 200.0,
        pool_size=4096,
    )


def provenance():
    """Record where each BENCH entry came from: commit, jax, backend,
    and the compile cache the cold numbers may have read from."""
    import jax

    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=ROOT,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or None
    except Exception:
        commit = None
    return dict(
        git_commit=commit,
        jax_version=jax.__version__,
        backend=jax.default_backend(),
        device_count=jax.device_count(),
        python=sys.version.split()[0],
        compile_cache=COMPILE_CACHE,
    )


def enable_host_devices(n: int) -> None:
    """Expose up to ``n`` XLA host devices (capped at the core count) so
    the batched campaign can shard members across CPU cores. Must run
    before jax is imported; a pre-set flag is left untouched."""
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    n = min(n, os.cpu_count() or 1)
    if n > 1:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip()
        )


def _check_entry(entry, where="new entry"):
    """The BENCH_union.json record contract: every record names its
    bench and carries a provenance block (commit, jax, backend)."""
    if not isinstance(entry, dict):
        raise ValueError(f"BENCH_union.json {where}: record must be an "
                         f"object, got {type(entry).__name__}")
    if not isinstance(entry.get("bench"), str) or not entry["bench"]:
        raise ValueError(
            f"BENCH_union.json {where}: missing/empty 'bench' name")
    if not isinstance(entry.get("provenance"), dict):
        raise ValueError(
            f"BENCH_union.json {where}: missing 'provenance' block "
            "(git_commit/jax_version/backend)")


def load_bench(path=None, backfill=False):
    """Load + schema-check BENCH_union.json records.

    With ``backfill``, legacy records missing a ``provenance`` block get
    a stub marked ``backfilled`` (their origin predates the contract and
    is unrecoverable); without it, such records fail the check.
    """
    path = path or os.path.join(ROOT, "BENCH_union.json")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        entries = json.load(f)
    if not isinstance(entries, list):
        entries = [entries]
    for i, e in enumerate(entries):
        if backfill and isinstance(e, dict) and "provenance" not in e:
            e["provenance"] = dict(backfilled=True)
        _check_entry(e, where=f"record {i}")
    return entries


def _append_entry(entry):
    _check_entry(entry)
    path = os.path.join(ROOT, "BENCH_union.json")
    existing = load_bench(path, backfill=True)
    existing.append(entry)
    with open(path, "w") as f:
        json.dump(existing, f, indent=1, default=float)
    print(f"wrote {path}")


def _bench_trace_spec(quick: bool):
    """The many-small-jobs regime ROADMAP item 1 targets: fine-grained
    pp/ar jobs streaming through a tight slot envelope, where per-window
    host + dispatch overhead (not tick compute) dominates the sequential
    loop — exactly what lock-step batching amortizes."""
    from repro.sched.trace import CatalogApp, synthetic_trace

    pp = (
        "For 6 repetitions {\n"
        " task 0 sends a 2048 byte message to task 1 then\n"
        " task 1 sends a 2048 byte message to task 0 }"
    )
    ar = (
        "For 2 repetitions {\n"
        " all tasks compute for 100 microseconds then\n"
        " all tasks allreduce a 4096 byte message }"
    )
    catalog = [
        CatalogApp(app="pp", ranks=2, est_runtime_us=1500.0, weight=2.0,
                   source=pp),
        CatalogApp(app="ar", ranks=4, est_runtime_us=2000.0, weight=1.0,
                   source=ar),
    ]
    n_jobs = 8 if quick else 32
    slots = 3 if quick else 4
    trace = synthetic_trace(
        n_jobs, arrival="poisson", mean_gap_us=300.0, seed=0,
        catalog=catalog, slots=slots, tick_us=20.0,
        horizon_ms=60_000.0, pool_size=256,
        name=f"bench-trace-{'quick' if quick else 'full'}",
    )
    seeds = [0, 1] if quick else [0, 1, 2, 3]
    policies = ["fcfs", "easy"] if quick else ["fcfs", "easy",
                                               "conservative"]
    return trace, n_jobs, slots, seeds, policies


def bench_trace(quick: bool):
    """Batched-vs-sequential scheduler campaign: the same (seeds ×
    policies) TraceStudy grid through the lock-step ``WindowedBatchNode``
    (one batched engine, per-member ``t_stop``) and through the per-cell
    sequential loop (``batch=False``). Warm walls (each mode runs twice,
    engines from the process-wide cache) give aggregate jobs/sec both
    ways plus the speedup — the results are bit-identical, so the delta
    is pure execution strategy."""
    from repro import union

    trace, n_jobs, slots, seeds, policies = _bench_trace_spec(quick)
    grid = len(seeds) * len(policies)
    total_jobs = n_jobs * grid
    print(f"trace={trace.name} jobs={n_jobs} slots={slots} grid="
          f"{len(seeds)} seeds x {len(policies)} policies ({grid} cells)")

    def run_mode(batch: bool):
        t0 = time.time()
        res = union.run(union.Experiment(
            name=f"bench-trace-{'batched' if batch else 'sequential'}",
            trace=union.TraceStudy(
                trace=trace, policies=policies, seeds=seeds, batch=batch),
        ))
        wall = time.time() - t0
        completed = sum(c.report["completed"] for c in res.cells)
        assert completed == total_jobs, (
            f"batch={batch}: only {completed}/{total_jobs} completed")
        return wall, res

    results = {}
    for mode, batch in (("sequential", False), ("batched", True)):
        cold_wall, _ = run_mode(batch)
        warm_wall, res = run_mode(batch)
        results[mode] = dict(
            cold_wall_s=cold_wall, warm_wall_s=warm_wall,
            jobs_per_sec=total_jobs / max(warm_wall, 1e-9),
            windows=max(c.report["windows"] for c in res.cells),
        )
        print(f"  {mode:>10}: cold {cold_wall:6.1f}s | warm {warm_wall:6.1f}s"
              f" ({total_jobs / max(warm_wall, 1e-9):.2f} jobs/s aggregate)")

    speedup = (results["sequential"]["warm_wall_s"]
               / max(results["batched"]["warm_wall_s"], 1e-9))
    print(f"speedup (warm, batched/sequential): {speedup:.2f}x")
    entry = dict(
        bench="union_trace_batched",
        jobs=n_jobs, slots=slots, seeds=len(seeds), policies=policies,
        grid_cells=grid, total_jobs=total_jobs,
        provenance=provenance(),
        trace=dict(name=trace.name, arrival="poisson", mean_gap_us=300.0,
                   placement=trace.placement),
        **{f"{m}_{k}": v for m, r in results.items() for k, v in r.items()},
        speedup_batched_over_sequential=speedup,
    )
    _append_entry(entry)


def bench_experiment(quick: bool):
    """Facade overhead: warm ``union.run`` (spec -> plan -> execute ->
    summarize) vs the direct engine-level path at the same envelope.
    Records the warm overhead ratio — the acceptance bar is <= 2%."""
    import numpy as np

    import jax

    from repro import union
    from repro.netsim.engine import get_engine, member_state, stack_members
    from repro.union import manager as MGR
    from repro.union.seeds import engine_seed

    members = 2 if quick else 8
    sc = bench_scenario(quick)
    print(f"scenario={sc.name} members={members} (facade-overhead profile)")

    def facade(base_seed: int) -> float:
        t0 = time.time()
        union.run(union.Experiment(
            name=sc.name, scenarios=[sc], members=members,
            base_seed=base_seed))
        return time.time() - t0

    rs = MGR.resolve(sc, seed=0)
    eng = get_engine(
        rs.topo, routing=sc.routing, ur=rs.ur, net=rs.net,
        pool_size=rs.pool_size, horizon_us=rs.horizon_us,
        capacity=rs.capacity)
    start = np.asarray(rs.start_us, np.float32)

    def direct(base_seed: int) -> float:
        t0 = time.time()
        inits = [
            eng.init_state(
                seed=engine_seed(base_seed + i),
                placements=rs.placements(base_seed + i),
                start_us=start, jobs_override=rs.jobs)
            for i in range(members)
        ]
        final = jax.block_until_ready(eng.run(stack_members(inits)))
        for i in range(members):
            MGR.member_report(member_state(final, i), rs, 0.0,
                              seed=base_seed + i, start_us=start,
                              capacity=rs.capacity)
        return time.time() - t0

    cold_facade = facade(0)       # pays the (shared) compile
    warm_direct = direct(100)
    warm_facade = facade(200)
    warm_direct2 = direct(300)
    warm_facade2 = facade(400)
    direct_s = min(warm_direct, warm_direct2)
    facade_s = min(warm_facade, warm_facade2)
    overhead = facade_s / max(direct_s, 1e-9) - 1.0
    print(f"  cold facade {cold_facade:6.1f}s | warm facade {facade_s:6.2f}s"
          f" | warm direct {direct_s:6.2f}s | overhead {overhead:+.2%}")
    if overhead > 0.02:
        print("  WARNING: facade overhead above the 2% budget")
    entry = dict(
        bench="union_experiment_facade",
        members=members,
        provenance=provenance(),
        scenario=sc.to_dict(),
        cold_facade_wall_s=cold_facade,
        warm_facade_wall_s=facade_s,
        warm_direct_wall_s=direct_s,
        warm_overhead=overhead,
    )
    _append_entry(entry)


def bench_fabric(quick: bool):
    """Warm tick wall per fabric: the same scenario shape through every
    registered fabric, engines from the shared cache — cold wall is the
    per-fabric compile price, warm wall the steady-state simulation
    cost of each topology's routing function."""
    from repro import union
    from repro.netsim.fabric import fabric_names

    members = 2 if quick else 4
    sc = bench_scenario(quick)
    print(f"scenario={sc.name} members={members} (fabric sweep profile)")

    results = {}
    for name in fabric_names():
        def campaign(base_seed):
            t0 = time.time()
            res = union.run(union.Experiment(
                name=f"{sc.name}-{name}", scenarios=[sc], members=members,
                base_seed=base_seed,
                grid=union.StudyGrid(fabrics=[name])))
            wall = time.time() - t0
            summary = next(iter(res.summary["scenario_studies"].values()))
            return wall, summary

        cold_wall, _ = campaign(0)
        warm_wall, summary = campaign(100)
        results[name] = dict(
            cold_wall_s=cold_wall, warm_wall_s=warm_wall,
            warm_members_per_sec=members / max(warm_wall, 1e-9),
            all_done=summary["all_done"], dropped=summary["dropped_total"],
        )
        print(f"  {name:>9}: cold {cold_wall:6.1f}s | warm {warm_wall:6.2f}s "
              f"({members / max(warm_wall, 1e-9):.2f} members/s) "
              f"all_done={summary['all_done']}")

    entry = dict(
        bench="union_fabric_profile",
        members=members,
        provenance=provenance(),
        scenario=sc.to_dict(),
        **{f"{n}_{k}": v for n, r in results.items() for k, v in r.items()},
    )
    _append_entry(entry)


_BENCH_FAILURE = "degrade:0.2:0.5"


def bench_failures(quick: bool):
    """Failure-campaign cost profile: the same ensemble healthy and on a
    degraded fabric (20% of fabric links at half bandwidth), sharing ONE
    compiled engine (fault masks are runtime data — the engine cache key
    has no failure term, pinned by the recorded build counters). The
    healthy campaign's cold run pays the one compile; the degraded
    campaign's FIRST run must already be warm
    (``degraded_engine_builds == 0``), and the warm walls of both
    coordinates give the steady-state price of simulating on a degraded
    fabric. A degrade factor (not a kill) keeps the bench deterministic:
    every job still completes, unlike permanent dead links, where even
    adaptive routing can stall when its one-shot detour draw crosses a
    dead link too — so ``all_done`` is asserted for the healthy
    coordinate and recorded (not asserted) for the degraded one."""
    from repro import union

    members = 2 if quick else 4
    sc = bench_scenario(quick)
    print(f"scenario={sc.name} members={members} (failure campaign "
          f"profile, healthy vs {_BENCH_FAILURE})")

    def campaign(failures, base_seed):
        t0 = time.time()
        res = union.run(union.Experiment(
            name=f"{sc.name}-failures", scenarios=[sc], members=members,
            base_seed=base_seed,
            grid=union.StudyGrid(failures=failures)))
        wall = time.time() - t0
        all_done = True
        for key, s in res.summary["scenario_studies"].items():
            assert s["dropped_total"] == 0, key
            if failures == ["healthy"]:
                assert s["all_done"], key
            all_done = all_done and bool(s["all_done"])
        return wall, res, all_done

    cold_wall, _, _ = campaign(["healthy"], 0)
    healthy_warm, _, _ = campaign(["healthy"], 100)
    deg_first_wall, res_first, _ = campaign([_BENCH_FAILURE], 200)
    deg_builds = res_first.engine_cache["builds"]
    assert deg_builds == 0, (
        "the degraded campaign must reuse the healthy campaign's engine")
    deg_warm, _, deg_done = campaign([_BENCH_FAILURE], 300)
    ratio = deg_warm / max(healthy_warm, 1e-9)
    print(f"  healthy: cold {cold_wall:6.1f}s | warm {healthy_warm:6.2f}s")
    print(f"  {_BENCH_FAILURE}: first {deg_first_wall:6.2f}s "
          f"(0 engine builds) | warm {deg_warm:6.2f}s "
          f"({ratio:.2f}x healthy, all_done={deg_done})")
    entry = dict(
        bench="union_failures_profile",
        members=members,
        provenance=provenance(),
        scenario=sc.to_dict(),
        failure=_BENCH_FAILURE,
        degraded_all_done=deg_done,
        healthy_cold_wall_s=cold_wall,
        healthy_warm_wall_s=healthy_warm,
        degraded_first_wall_s=deg_first_wall,
        degraded_warm_wall_s=deg_warm,
        degraded_engine_builds=deg_builds,
        degraded_over_healthy_warm=ratio,
    )
    _append_entry(entry)


def bench_serve(quick: bool):
    """Serve-stack temperatures: submit-to-done wall through one
    in-process Union server (real HTTP, fresh temp store). Cold pays
    compile + simulation; warm re-submits with fresh seeds so the engine
    cache is hot but every cell is a store miss; store-hit re-submits
    the warm spec verbatim — 0 cells simulated, pure replay. The
    cold/warm gap is the engine cache's contribution, warm/hit the
    store's."""
    import shutil
    import tempfile
    import threading

    from repro import union
    from repro.union.client import ServeClient
    from repro.union.serve import make_server

    members = 2 if quick else 4
    sc = bench_scenario(quick)
    store_dir = tempfile.mkdtemp(prefix="bench_union_serve_")
    srv = make_server(store=store_dir)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    client = ServeClient(f"http://127.0.0.1:{srv.port}", timeout=120)
    print(f"scenario={sc.name} members={members} (serve profile, "
          f"port {srv.port}, store {store_dir})")

    def submit(base_seed):
        exp = union.Experiment(name=f"{sc.name}-serve", scenarios=[sc],
                               members=members, base_seed=base_seed)
        t0 = time.time()
        job = client.submit(exp)
        st = client.wait(job, timeout=3600, poll_s=0.05)
        wall = time.time() - t0
        assert st["status"] == "done", st
        return wall, st

    try:
        cold_wall, st_cold = submit(0)
        warm_wall, st_warm = submit(100)
        hit_wall, st_hit = submit(100)
    finally:
        srv.close()
        shutil.rmtree(store_dir, ignore_errors=True)
    assert st_cold["store"]["misses"] == members, st_cold
    assert st_warm["store"]["misses"] == members, st_warm
    assert st_hit["store"]["hits"] == members, st_hit
    assert st_hit["store"]["misses"] == 0, st_hit
    for label, wall in (("cold submit", cold_wall),
                        ("warm submit", warm_wall),
                        ("store-hit submit", hit_wall)):
        print(f"  {label:>17}: {wall:7.2f}s")
    print(f"warm speedup over cold: {cold_wall / max(warm_wall, 1e-9):.2f}x"
          f" | store-hit over warm: "
          f"{warm_wall / max(hit_wall, 1e-9):.2f}x")
    entry = dict(
        bench="union_serve",
        members=members,
        provenance=provenance(),
        scenario=sc.to_dict(),
        cold_submit_wall_s=cold_wall,
        warm_submit_wall_s=warm_wall,
        store_hit_wall_s=hit_wall,
        warm_speedup_over_cold=cold_wall / max(warm_wall, 1e-9),
        hit_speedup_over_warm=warm_wall / max(hit_wall, 1e-9),
    )
    _append_entry(entry)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--members", type=int, default=None,
                    help="ensemble members (default 8; 2 with --quick)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke profile: tiny scenario, 2 members")
    ap.add_argument("--trace", action="store_true",
                    help="online-scheduler profile: jobs/sec through a"
                    " small slot envelope (FCFS + EASY)")
    ap.add_argument("--experiment", action="store_true",
                    help="facade-overhead profile: warm union.run vs the"
                    " direct engine-level path (budget: <= 2%%)")
    ap.add_argument("--fabric", action="store_true",
                    help="fabric sweep profile: the same mix on every"
                    " registered fabric, cold + warm wall per fabric")
    ap.add_argument("--serve", action="store_true",
                    help="serve profile: cold vs engine-warm vs store-hit"
                    " submit-to-done wall through the Union server")
    ap.add_argument("--failures", action="store_true",
                    help="failure campaign profile: healthy vs 2%%"
                    " dead-link warm wall through one shared engine")
    args = ap.parse_args()
    members = args.members if args.members is not None else (
        2 if args.quick else 8)
    profiles = (args.failures, args.trace, args.experiment, args.fabric,
                args.serve)
    if not any(profiles):
        enable_host_devices(members)  # before jax is imported
    from repro.compile_cache import use_persistent_cache

    global COMPILE_CACHE
    cache_dir = use_persistent_cache()
    COMPILE_CACHE = dict(
        dir=cache_dir,
        entries_at_start=(len(os.listdir(cache_dir))
                          if os.path.isdir(cache_dir) else 0))
    if args.failures:
        bench_failures(args.quick)
        return
    if args.trace:
        bench_trace(args.quick)
        return
    if args.experiment:
        bench_experiment(args.quick)
        return
    if args.fabric:
        bench_fabric(args.quick)
        return
    if args.serve:
        bench_serve(args.quick)
        return
    from repro import union

    sc = bench_scenario(args.quick)
    print(f"scenario={sc.name} members={members}")

    # the engine comes from the process-wide cache: the cold run of each
    # mode pays that mode's trace+compile, the warm run (fresh seeds, same
    # shape) hits the jit cache and measures steady-state members/sec.
    results = {}
    for mode in ("vmapped", "looped"):
        vm = mode == "vmapped"

        def campaign(base_seed):
            t0 = time.time()
            res = union.run(union.Experiment(
                name=sc.name, scenarios=[sc], members=members,
                base_seed=base_seed, vmapped=vm))
            wall = time.time() - t0
            summary = next(iter(res.summary["scenario_studies"].values()))
            return wall, summary

        cold_wall, _ = campaign(0)
        warm_wall, summary = campaign(100)
        results[mode] = dict(
            cold_wall_s=cold_wall,
            warm_wall_s=warm_wall,
            cold_members_per_sec=members / max(cold_wall, 1e-9),
            warm_members_per_sec=members / max(warm_wall, 1e-9),
            all_done=summary["all_done"],
            dropped=summary["dropped_total"],
        )
        print(f"  {mode:>8}: cold {cold_wall:6.1f}s "
              f"({members / max(cold_wall, 1e-9):.2f} members/s) | "
              f"warm {warm_wall:6.1f}s "
              f"({members / max(warm_wall, 1e-9):.2f} members/s)")

    entry = dict(
        bench="union_ensemble_throughput",
        members=members,
        provenance=provenance(),
        scenario=sc.to_dict(),
        **{f"{m}_{k}": v for m, r in results.items() for k, v in r.items()},
        warm_speedup_vmapped_over_looped=(
            results["looped"]["warm_wall_s"]
            / max(results["vmapped"]["warm_wall_s"], 1e-9)
        ),
    )
    print(f"speedup (warm, vmapped/looped): "
          f"{entry['warm_speedup_vmapped_over_looped']:.2f}x")
    _append_entry(entry)


if __name__ == "__main__":
    main()
