"""The benchmark's own arithmetic: the tick's byte count, the window's
rate, the trace reduction, and finding a cell's pieces by name."""
import importlib.util
import json
import os

import numpy as np
import pytest

import rates
import reduce as RD
import work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tick_bytes_at_known_shapes():
    # 62 bytes per pool slot at route width 10
    assert work.slot_bytes(10) == 62
    # cell df1d-paper.workload1: 2 members, pool 65,536, 53,856 links,
    # 1,056 routers, 5 applications
    b = work.tick_bytes(B=2, M=65536, K=10, L=53856, R=1056, apps=5)
    assert b == (2 * 65536 * 62 + 53857 * 8 + 2 * 53857 * 8
                 + 2 * 5 * 1056 * 8)
    assert work.least_tick_s(dict(B=2, M=65536, K=10, L=53856, R=1056,
                                  apps=5), 819e9) == pytest.approx(
        b / 819e9)


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_window_runs_past_seconds_by_one_call_and_counts_it():
    clock = Clock()

    def call(k):
        clock.t += 4.0  # each call takes 4 s of work 10
        return 10.0

    calls = rates.run_window(call, 10.0, clock)
    # calls start at 0, 4 and 8 (< 10); the third ends at 12 and counts
    assert [(s, e) for s, e, _ in calls] == [(0, 4), (4, 8), (8, 12)]
    assert rates.window_rate(calls) == pytest.approx(30.0 / 12.0)


def test_window_rate_counts_gaps_between_calls():
    calls = [(0.0, 1.0, 5.0), (2.0, 3.0, 5.0)]
    assert rates.window_rate(calls) == pytest.approx(10.0 / 3.0)


def test_one_call_longer_than_the_window():
    clock = Clock()

    def call(k):
        clock.t += 25.0
        return 1.0

    assert len(rates.run_window(call, 10.0, clock)) == 1


def test_reduction_busy_self_time_counts_and_gaps():
    window = (100, 200)
    events = [
        ("%while.1 = f32[4]{0} while(f32[4]{0} %p)", 90, 60),  # clipped
        ("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %a)", 110, 10),  # nested
        ("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %a)", 130, 10),  # nested
        ("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %a)", 170, 10),
        ("copy.3", 195, 20),  # clipped at 200
    ]
    red = RD.reduce_events(events, window)
    # busy: [100,150) + [170,180) + [195,200)
    assert red["busy_ns"] == 65
    assert red["idle_share"] == pytest.approx(1 - 65 / 100)
    assert red["ops"]["while.1"]["self_ns"] == 50 - 20
    assert red["ops"]["fusion.2"]["count"] == 3
    # every op here holds over 1% of the busy time
    assert red["ticks"] == 3
    assert red["gaps"] == [(150, 170), (180, 195)]
    assert RD.top_ops(red, 1) == [["while.1 while", pytest.approx(30e-9)]]


def test_gaps_are_named_by_the_innermost_host_span():
    gaps = [(150, 170), (180, 195)]
    spans = [("union.run", 0, 300), ("planner.plan", 155, 165)]
    assert RD.label_gaps(gaps, spans, offset_ns=0) == [
        ["planner.plan", pytest.approx(20e-9)],
        ["union.run", pytest.approx(15e-9)]]
    # a host clock 1,000 ns behind the trace's moves every span
    late = [(n, s - 1000, e - 1000) for n, s, e in spans]
    assert RD.label_gaps(gaps, late, offset_ns=1000)[0][0] == "planner.plan"
    assert RD.label_gaps(gaps, [], offset_ns=0)[0][0] == "outside union.run"


def test_no_device_op_in_the_window_is_an_error():
    with pytest.raises(ValueError):
        RD.reduce_events([("a", 0, 5)], (10, 20))


def test_a_new_traffic_file_is_found_by_name(tmp_path):
    """A cell added by data alone: a new entry in BENCHMARK.json and a new
    traffic file, with no other file edited."""
    run = load_run()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg = bench["configs"][0]
    os.makedirs(tmp_path / "bench" / "traffic")
    os.makedirs(os.path.dirname(tmp_path / cfg["file"]), exist_ok=True)
    with open(os.path.join(ROOT, cfg["file"])) as f:
        (tmp_path / cfg["file"]).write_text(f.read())
    with open(os.path.join(BENCH, "traffic", "workload1-ens2.json")) as f:
        mix = json.load(f)
    mix.update(members=4, member_seeds=[0, 1, 2, 3])
    (tmp_path / "bench" / "traffic" / "new-mix.json").write_text(
        json.dumps(mix))
    bench["workloads"].append(dict(
        name="new.cell", config=cfg["name"], traffic="new-mix", chips=1,
        why="a cell added by data alone"))
    like = "df1d-paper.workload1"  # the cell the new one is modelled on
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append("new.cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell, config, traffic, e2e, layer = run.cell_spec(
        "new.cell", root=str(tmp_path))
    assert traffic["members"] == 4 and traffic["name"] == "new-mix"
    assert config["topo"] == "1d"
    assert {m["name"] for m in e2e} == {"sim_us_per_s", "setup_s"}
    assert {m["name"] for m in layer} == {
        m["name"] for m in bench["per_layer"]
        if like in m.get("workloads", [])}
    # the driver and every per-layer reader are found by name
    assert os.path.isfile(os.path.join(
        BENCH, "drivers", traffic["driver"] + ".py"))
    for m in layer:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))


def test_an_unknown_cell_exits():
    run = load_run()
    with pytest.raises(SystemExit):
        run.cell_spec("no.such.cell")


def test_every_run_simulates_the_same_members_in_its_own_order():
    fixed = [0, 1, 2, 3]
    for seed in (1, 2**31 + 5):
        # calls of two walk through the set in passes
        calls = [rates.call_members(fixed, 2, seed, k) for k in range(4)]
        assert sorted(calls[0] + calls[1]) == fixed
        assert sorted(calls[2] + calls[3]) == fixed
        assert calls == [rates.call_members(fixed, 2, seed, k)
                         for k in range(4)]
    # a set of one call's size: every call runs all of it
    one = [rates.call_members([7, 9], 2, s, k) for s in range(8)
           for k in range(3)]
    assert all(sorted(c) == [7, 9] for c in one)
    assert len({tuple(c) for c in one}) == 2
    orders = {tuple(rates.call_members(fixed, 4, s, 0)) for s in range(8)}
    assert len(orders) > 1
    with pytest.raises(ValueError):
        rates.call_members([0, 1, 2], 2, 1, 0)


def test_ticks_leave_out_light_ops_that_run_more_often():
    # ten 500-ns passes of the loop, and twelve 1-ns copies (0.24% of busy)
    body = [("%fusion.1 = f32[4]{0} fusion(%a)", 1000 * i, 500)
            for i in range(10)]
    copies = [("%copy-start.2 = f32[4]{0} copy-start(%a)", 1000 * i + 600, 1)
              for i in range(12)]
    red = RD.reduce_events(body + copies, (0, 12_000))
    assert red["ops"]["copy-start.2"]["count"] == 12
    assert red["ticks"] == 10
