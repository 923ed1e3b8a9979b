"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus human-readable detail to
stderr-ish comment lines prefixed with '#'). Heavier parameter sweeps live
in benchmarks/sweep_netsim.py; this default run exercises every paper
artifact at CPU-container scale in minutes.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

import jax
import jax.numpy as jnp

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "bench")
_SIM_CACHE = {}


def _emit(name, us, derived):
    print(f"{name},{us:.3f},{derived}")


# ---------------------------------------------------------------------------
# Tables IV & V + Fig 6 — Union skeleton validation
# ---------------------------------------------------------------------------

def bench_table4_5_fig6_validation():
    from repro.core import workloads as W
    from repro.core.interp import skeleton_trace

    apps = ["cosmoflow", "alexnet", "nn", "milc", "nekbone", "lammps"]
    t0 = time.time()
    n_ok = 0
    detail = {}
    for app in apps:
        a = W.build_application(app, "paper")
        s = W.build_skeleton(app, "paper")
        ev = a.as_table() == s.event_counts()
        by = bool((a.bytes == s.bytes_per_rank()).all())
        cf = a.trace == skeleton_trace(s)
        n_ok += ev and by and cf
        detail[app] = dict(events=ev, bytes=by, controlflow=cf,
                           counts=s.event_counts())
    us = (time.time() - t0) / len(apps) * 1e6
    alex = detail["alexnet"]["counts"]
    print(f"# Table IV (alexnet, paper scale): {alex}")
    b = W.build_skeleton("alexnet", "paper").bytes_per_rank()
    print(f"# Table V (alexnet): rank0={b[0]:.3e} B, ranks1+={b[1]:.3e} B")
    _emit("table4_5_fig6_validation", us, f"{n_ok}/6_apps_match")
    _save("validation", detail)
    return n_ok == len(apps)


# ---------------------------------------------------------------------------
# shared small-scale hybrid simulations (figs 7/8/9, table VI)
# ---------------------------------------------------------------------------

def _sim(key_, **kw):
    from repro.launch.sim import run_sim

    if key_ not in _SIM_CACHE:
        t0 = time.time()
        rep = run_sim(**kw)
        rep["_wall_s"] = time.time() - t0
        _SIM_CACHE[key_] = rep
    return _SIM_CACHE[key_]


_COMMON = dict(workload="workload1", scale="small", seed=0,
               horizon_ms=500.0, tick_us=5.0, iters_override=2)


def bench_fig7_latency():
    t0 = time.time()
    rn = _sim("rn", topo_variant="1d", placement="RN", routing="ADP", **_COMMON)
    rg = _sim("rg", topo_variant="1d", placement="RG", routing="ADP", **_COMMON)
    us = (time.time() - t0) * 1e6
    for app in ("cosmoflow", "alexnet", "lammps", "nn"):
        a, b = rn["latency"][app], rg["latency"][app]
        print(f"# Fig7 {app}: avg latency RN={a['avg_us']:.1f}us "
              f"RG={b['avg_us']:.1f}us max RN={a['max_us']:.1f} RG={b['max_us']:.1f}")
    ratio = rn["latency"]["lammps"]["avg_us"] / max(rg["latency"]["lammps"]["avg_us"], 1e-9)
    _emit("fig7_latency_RNvsRG", us, f"lammps_RN/RG={ratio:.2f}")
    _save("fig7", {"RN": rn["latency"], "RG": rg["latency"]})
    return True


def bench_fig8_router_traffic():
    from repro.netsim.topology import dragonfly_1d_small

    t0 = time.time()
    rr = _sim("rr", topo_variant="1d", placement="RR", routing="ADP", **_COMMON)
    rg = _SIM_CACHE["rg"]
    us = (time.time() - t0) * 1e6
    # per-window peak traffic on the whole system, per app (small-scale proxy
    # for "routers serving alexnet")
    def peak(rep):
        return rep  # windows live in the engine state; report via saved json
    print(f"# Fig8: peak inject RR={rr['peak_inject_TiBps']:.4f} TiB/s "
          f"RG={rg['peak_inject_TiBps']:.4f} TiB/s")
    _emit("fig8_router_traffic_RRvsRG", us,
          f"peak_inject_RR/RG={rr['peak_inject_TiBps']/max(rg['peak_inject_TiBps'],1e-12):.2f}")
    _save("fig8", {"RR_peak": rr["peak_inject_TiBps"], "RG_peak": rg["peak_inject_TiBps"]})
    return True


def bench_fig9_commtime():
    t0 = time.time()
    rn, rg = _SIM_CACHE["rn"], _SIM_CACHE["rg"]
    us = (time.time() - t0) * 1e6 + 1
    hpc_ratio = rn["comm_time"]["lammps"]["max_ms"] / max(
        rg["comm_time"]["lammps"]["max_ms"], 1e-9)
    ml_ratio = rn["comm_time"]["cosmoflow"]["max_ms"] / max(
        rg["comm_time"]["cosmoflow"]["max_ms"], 1e-9)
    for app in ("cosmoflow", "alexnet", "lammps", "nn"):
        print(f"# Fig9 {app}: max comm RN={rn['comm_time'][app]['max_ms']:.1f}ms "
              f"RG={rg['comm_time'][app]['max_ms']:.1f}ms")
    _emit("fig9_commtime", us,
          f"lammps_RN/RG={hpc_ratio:.2f};cosmoflow_RN/RG={ml_ratio:.2f}")
    _save("fig9", {"RN": rn["comm_time"], "RG": rg["comm_time"]})
    return True


def bench_table6_linkload():
    t0 = time.time()
    d1 = _SIM_CACHE["rg"]
    d2 = _sim("rg2d", topo_variant="2d", placement="RG", routing="ADP", **_COMMON)
    us = (time.time() - t0) * 1e6
    l1, l2 = d1["link_load"], d2["link_load"]
    print(f"# TableVI 1D: glink/link={l1['global_per_link_bytes']/2**20:.2f}MB "
          f"llink/link={l1['local_per_link_bytes']/2**20:.2f}MB "
          f"frac_global={l1['frac_global']:.3f}")
    print(f"# TableVI 2D: glink/link={l2['global_per_link_bytes']/2**20:.2f}MB "
          f"llink/link={l2['local_per_link_bytes']/2**20:.2f}MB "
          f"frac_global={l2['frac_global']:.3f}")
    ratio = (l1["global_per_link_bytes"] / max(l2["global_per_link_bytes"], 1e-9))
    _emit("table6_linkload", us, f"glink_per_link_1D/2D={ratio:.2f}")
    _save("table6", {"1d": l1, "2d": l2})
    return True


# ---------------------------------------------------------------------------
# framework micro-benchmarks
# ---------------------------------------------------------------------------

def bench_union_translate():
    """Union compiler throughput (DSL -> skeleton), paper §III."""
    from repro.core import workloads as W

    t0 = time.time()
    n = 0
    for _ in range(3):
        for app in ("alexnet", "milc", "nekbone"):
            W.build_skeleton(app, "paper")
            n += 1
    us = (time.time() - t0) / n * 1e6
    _emit("union_translate", us, "paper_scale_skeletons")
    return True


def bench_engine_tick():
    """Simulator throughput: virtual-us per wall-us on a mixed workload."""
    rep = _SIM_CACHE.get("rg") or _sim(
        "rg", topo_variant="1d", placement="RG", routing="ADP", **_COMMON)
    vus = rep["virtual_time_ms"] * 1000
    wall_us = rep["_wall_s"] * 1e6
    _emit("engine_throughput", wall_us / max(vus, 1), "wall_us_per_virtual_us")
    print(f"# engine: {rep['virtual_time_ms']:.0f} virtual ms in "
          f"{rep['_wall_s']:.1f}s wall; peak inject {rep['peak_inject_TiBps']:.4f} TiB/s")
    return True


def bench_kernel_ssd():
    from repro.kernels.ssd_scan import ssd_scan

    key = jax.random.PRNGKey(1)
    BH, nc, Q, hd, ds = 16, 8, 128, 64, 64
    x = jax.random.normal(key, (BH, nc, Q, hd))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (BH, nc, Q)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (BH,)))
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (BH, nc, Q, ds))
    Cm = jax.random.normal(jax.random.fold_in(key, 4), (BH, nc, Q, ds))
    f = lambda: jax.block_until_ready(ssd_scan(x, dt, A, Bm, Cm, use_pallas=False))
    f()
    t0 = time.time()
    for _ in range(10):
        f()
    us = (time.time() - t0) / 10 * 1e6
    _emit("kernel_ssd_jnp", us, f"BHxS={BH}x{nc*Q}")
    return True


def bench_roofline_table():
    """Summarize the dry-run roofline records (EXPERIMENTS §Roofline)."""
    d = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")
    if not os.path.isdir(d):
        _emit("roofline_table", 0.0, "no_dryrun_records")
        return True
    recs = []
    for f in os.listdir(d):
        if f.endswith(".json") and "__single" in f:
            with open(os.path.join(d, f)) as fh:
                recs.append(json.load(fh))
    if not recs:
        _emit("roofline_table", 0.0, "no_dryrun_records")
        return True
    fr = sorted(recs, key=lambda r: r["roofline"]["roofline_fraction"])
    worst, best = fr[0], fr[-1]
    _emit("roofline_cells", float(len(recs)),
          f"worst={worst['arch']}:{worst['shape']}"
          f"@{worst['roofline']['roofline_fraction']:.3f};"
          f"best={best['arch']}:{best['shape']}"
          f"@{best['roofline']['roofline_fraction']:.3f}")
    return True


def _save(name, obj):
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, f"{name}.json"), "w") as f:
        json.dump(obj, f, indent=1, default=float)


def main() -> None:
    print("name,us_per_call,derived")
    ok = True
    for fn in (
        bench_table4_5_fig6_validation,
        bench_union_translate,
        bench_fig7_latency,
        bench_fig8_router_traffic,
        bench_fig9_commtime,
        bench_table6_linkload,
        bench_engine_tick,
        bench_kernel_ssd,
        bench_roofline_table,
    ):
        try:
            ok &= bool(fn())
        except Exception as e:  # keep the harness running
            import traceback
            traceback.print_exc()
            _emit(fn.__name__, -1.0, f"ERROR:{type(e).__name__}")
            ok = False
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
