"""A cell small enough for the CPU: the paper's workload1 mix on the
Union code's small 1-D dragonfly (9 groups x 8 routers x 7 nodes), at
the rank counts that fabric holds."""
import copy
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GRIDS = {"lammps": ("16x16x8", "8x4x4", 128), "nn": ("8x8x8", "4x4x4", 64)}


def config():
    with open(os.path.join(BENCH, "configs", "df1d-paper.json")) as f:
        c = json.load(f)
    c.update(scale="small", fabric=dict(
        variant="1d", groups=9, routers_per_group=8, nodes_per_router=7,
        global_per_router=2))
    c["sizes"].update(nodes=504, routers=72, links=1656)
    return c


def traffic():
    with open(os.path.join(BENCH, "traffic", "workload1-ens2.json")) as f:
        t = json.load(f)
    t = copy.deepcopy(t)
    for j in t["jobs"]:
        if j["app"] in GRIDS:
            big, small, ranks = GRIDS[j["app"]]
            j["source"] = j["source"].replace(big, small)
            j["ranks"] = ranks
        else:
            j["ranks"] = 64
    t.update(name="workload1-small", pool_size=8192)
    t["ur"]["ranks"] = 128
    return t
