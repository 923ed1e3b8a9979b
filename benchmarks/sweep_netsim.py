"""Full hybrid-workload sweep (paper §VI): placements × routing × topologies,
plus per-app baselines — a thin loop over `repro.union` scenarios.

  PYTHONPATH=src python -m benchmarks.sweep_netsim [--quick] [--members N]

With ``--members > 1`` each cell becomes a vmapped ensemble campaign
(seeds × placements) instead of a single run, and the JSON carries the
campaign summary; EXPERIMENTS.md summarizes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

OUT = os.path.join(os.path.dirname(__file__), "..", "results", "netsim")


def main() -> int:
    """Run every cell; returns 1 when any cell failed, after the rest."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--workload", default="workload1")
    ap.add_argument("--members", type=int, default=1,
                    help=">1: run each cell as a vmapped ensemble campaign")
    args = ap.parse_args()

    from repro import union
    from repro.union.scenario import MIXES, mix_scenario

    os.makedirs(OUT, exist_ok=True)
    combos = []
    placements = ["RN", "RR", "RG"]
    routings = ["MIN", "ADP"]
    topos = ["1d", "2d"]
    if args.quick:
        placements, routings, topos = ["RN", "RG"], ["ADP"], ["1d"]
    # baselines (exclusive network) per app
    for app in MIXES[args.workload]:
        for topo in topos:
            combos.append((f"baseline-{app}", topo, "RN", "ADP"))
    for topo in topos:
        for pl in placements:
            for rt in routings:
                combos.append((args.workload, topo, pl, rt))

    failed = []
    for wl, topo, pl, rt in combos:
        tag = f"{wl}__{topo}__{pl}__{rt}__small_s0"
        if args.members > 1:
            tag += f"_m{args.members}"
        path = os.path.join(OUT, tag + ".json")
        if os.path.exists(path):
            print(f"skip {tag}")
            continue
        t0 = time.time()
        try:
            sc = mix_scenario(wl, topo=topo, scale="small", placement=pl,
                              routing=rt, iters_override=2,
                              horizon_ms=500.0, tick_us=5.0)
            res = union.run(union.Experiment(
                name=sc.name, scenarios=[sc], members=args.members,
                base_seed=0, vmapped=args.members > 1))
            if args.members > 1:
                summary = next(iter(
                    res.summary["scenario_studies"].values()))
                rep = dict(scenario=sc.to_dict(), summary=summary,
                           members=[c.report for c in res.cells])
                virtual = summary["virtual_time_ms"]["mean"]
            else:
                rep = res.cells[0].report
                virtual = rep["virtual_time_ms"]
            with open(path, "w") as f:
                json.dump(rep, f, indent=1, default=float)
            print(f"{tag}: {time.time()-t0:.0f}s virtual={virtual:.0f}ms",
                  flush=True)
        except Exception as e:  # the other cells still run
            print(f"{tag}: FAIL {e}", flush=True)
            failed.append(tag)
    if failed:
        print(f"{len(failed)} of {len(combos)} cells failed: "
              f"{', '.join(failed)}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
