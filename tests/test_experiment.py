"""The Experiment facade: golden old-vs-new equivalence for all three CLI
modes, the shared engine cache, seed-derivation pins, strict validation,
Results round-trips, and the deprecation shims."""
import json
import os

import pytest

from repro import union
from repro.sched.trace import CatalogApp, Trace, synthetic_trace
from repro.union.scenario import Scenario, ScenarioJob

GOLDEN = os.path.join(os.path.dirname(__file__),
                      "data_experiment_golden.json")

PP = (
    "For 4 repetitions {\n"
    " task 0 sends a 1024 byte message to task 1 then\n"
    " task 1 sends a 1024 byte message to task 0 }"
)
AR = (
    "For 2 repetitions {\n"
    " all tasks allreduce a 65536 byte message then\n"
    " all tasks compute for 100 microseconds }"
)


def tiny_scenario():
    return Scenario(
        name="tiny",
        jobs=[
            ScenarioJob(app="pp0", source=PP, ranks=2),
            ScenarioJob(app="pp1", source=PP, ranks=2, start_us=200.0),
        ],
        placement="RN", tick_us=2.0, horizon_ms=50.0, pool_size=256,
    )


def sc_a():
    return Scenario(
        name="a", jobs=[ScenarioJob(app="pp0", source=PP, ranks=2)],
        placement="RN", tick_us=2.0, horizon_ms=50.0, pool_size=256)


def sc_b():
    return Scenario(
        name="b",
        jobs=[ScenarioJob(app="ar8", source=AR, ranks=8),
              ScenarioJob(app="pp1", source=PP, ranks=2, start_us=100.0)],
        placement="RN", tick_us=2.0, horizon_ms=50.0, pool_size=256)


def golden_trace():
    catalog = [
        CatalogApp(app="pp", ranks=2, est_runtime_us=1500.0, weight=2.0,
                   source=PP.replace("1024", "2048")),
        CatalogApp(app="ar", ranks=8, est_runtime_us=4000.0, weight=1.0,
                   source=AR),
    ]
    return synthetic_trace(
        8, arrival="poisson", mean_gap_us=400.0, seed=0, catalog=catalog,
        slots=3, tick_us=5.0, horizon_ms=60_000.0, pool_size=1024,
        name="golden-trace")


def small_trace_factory(seed):
    """Fresh 6-job draws per seed — exercises multi-trace batching where
    every member's job stream (and capacity envelope) differs."""
    catalog = [
        CatalogApp(app="pp", ranks=2, est_runtime_us=1500.0, weight=2.0,
                   source=PP.replace("1024", "2048")),
        CatalogApp(app="ar", ranks=8, est_runtime_us=4000.0, weight=1.0,
                   source=AR),
    ]
    return synthetic_trace(
        6, arrival="poisson", mean_gap_us=400.0, seed=seed, catalog=catalog,
        slots=3, tick_us=5.0, horizon_ms=60_000.0, pool_size=1024,
        name=f"grid-{seed}")


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


def assert_member_matches(rep, g):
    """One facade member report vs its pre-facade golden digest —
    bit-identical metrics, not approximate."""
    assert rep["virtual_time_ms"] == g["virtual_time_ms"]
    assert rep["dropped"] == g["dropped"]
    assert rep["config"]["envelope"] == g["envelope"]
    assert [float(s) for s in rep["config"]["start_us"]] == g["start_us"]
    for app, ga in g["apps"].items():
        assert rep["latency"][app]["count"] == ga["count"]
        assert rep["latency"][app]["avg_us"] == ga["avg_us"]
        assert rep["latency"][app]["max_us"] == ga["max_us"]
        assert rep["comm_time"][app]["max_ms"] == ga["max_comm_ms"]
        assert rep["comm_time"][app]["avg_ms"] == ga["avg_comm_ms"]


# ---------------------------------------------------------------------------
# golden old-vs-new: the facade reproduces the pre-facade entry points
# ---------------------------------------------------------------------------

def test_scenario_campaign_matches_golden(golden):
    """--scenario mode: union.run == the old run_campaign, bit-identical."""
    res = union.run(union.Experiment(
        name="tiny", scenarios=[tiny_scenario()], members=2))
    assert len(res.cells) == 2
    for cell, g in zip(res.cells, golden["scenario"]["members"]):
        assert cell.kind == "scenario" and cell.placement == "RN"
        assert_member_matches(cell.report, g)


def test_ragged_campaign_matches_golden(golden):
    """--scenario a b mode: one experiment over mixed job/rank shapes ==
    the old run_ragged_campaign, bit-identical, in input order."""
    res = union.run(union.Experiment(
        name="rag", scenarios=[sc_a(), sc_b()], members=1, seeds=[0, 1]))
    assert [c.name for c in res.cells] == ["a", "b"]
    for cell, g in zip(res.cells, golden["ragged"]["members"]):
        assert_member_matches(cell.report, g)


def test_keep_states_returns_each_cells_final_state(golden):
    """keep_states: the final engine states union.run itself produced,
    in scenario-cell order, never in the artifact; telemetry names the
    device that held them."""
    exp = union.Experiment(
        name="rag", scenarios=[sc_a(), sc_b()], members=1, seeds=[0, 1])
    res = union.run(exp, keep_states=True)
    assert len(res.states) == len(res.scenario_cells) == 2
    for cell, st, g in zip(res.cells, res.states, golden["ragged"]["members"]):
        assert_member_matches(cell.report, g)
        assert float(st.t) / 1000.0 == cell.report["virtual_time_ms"]
        assert int(st.pool.dropped) == cell.report["dropped"]
    assert res.telemetry["devices"] == [0]
    assert "states" not in res.to_dict()
    assert union.run(exp).states is None


def test_trace_study_matches_golden(golden):
    """--trace mode: a TraceStudy through union.run == the old
    sched.run_trace for both queue policies, per-job bit-identical."""
    res = union.run(union.Experiment(
        name="tr",
        trace=union.TraceStudy(trace=golden_trace(),
                               policies=["fcfs", "easy"], seeds=1)))
    assert [c.policy for c in res.cells] == ["fcfs", "easy"]
    for cell in res.cells:
        g = golden["trace"]["policies"][cell.policy]
        assert cell.kind == "trace"
        assert cell.report["windows"] == g["windows"]
        assert cell.report["makespan_ms"] == g["makespan_us"] / 1000.0
        assert cell.report["utilization"] == g["utilization"]
        for row, gj in zip(cell.report["per_job"], g["jobs"]):
            assert row["name"] == gj["name"]
            assert row["completed"] == gj["completed"]
            assert row["start_us"] == gj["start_us"]
            assert row["finish_us"] == gj["finish_us"]
            assert row["msgs"] == gj["msgs"]
            assert row["avg_latency_us"] == gj["avg_latency_us"]


def test_batched_trace_grid_matches_sequential():
    """The acceptance grid: a (4 seeds × 3 policies) TraceStudy through
    the lock-step WindowedBatchNode is bit-identical, cell by cell, to
    the sequential per-cell path (``batch=False``) — including window
    counts, per-job starts/finishes and message metrics."""
    from repro.union import planner as PLN

    def study(batch):
        return union.Experiment(
            name=f"grid-{batch}",
            trace=union.TraceStudy(
                factory=small_trace_factory, slots=3,
                policies=["fcfs", "easy", "conservative"],
                seeds=[0, 1, 2, 3], batch=batch))

    plan_b = PLN.plan(study(True))
    assert len(plan_b.windowed_batch_nodes) == 1
    assert len(plan_b.windowed_batch_nodes[0].cells) == 12
    assert "batched scheduler × 12 trace cells" in plan_b.describe()
    plan_s = PLN.plan(study(False))
    assert plan_s.windowed_batch_nodes == [] and len(
        plan_s.windowed_nodes[0].cells) == 12

    res_b = union.run(study(True))
    res_s = union.run(study(False))
    assert res_b.telemetry["node_kinds"].keys() == {"windowed_batch"}
    assert res_s.telemetry["node_kinds"].keys() == {"windowed"}
    assert len(res_b.cells) == len(res_s.cells) == 12
    for cb, cs in zip(res_b.cells, res_s.cells):
        assert (cb.seed, cb.policy, cb.name) == (cs.seed, cs.policy, cs.name)
        rb = {k: v for k, v in cb.report.items()
              if k not in ("wall_s", "jobs_per_sec")}
        rs = {k: v for k, v in cs.report.items()
              if k not in ("wall_s", "jobs_per_sec")}
        assert rb == rs, f"cell {cb.seed}/{cb.policy} diverged"


def test_batched_trace_observability_matches_sequential():
    """The PR 7 equality contract extended to instrumented runs: probe
    rings, latency histograms, and sim-time timelines are all functions
    of virtual time, so a WindowedBatchNode cell reports them
    bit-identically to the same cell run sequentially."""

    def study(batch):
        return union.Experiment(
            name=f"obsgrid-{batch}",
            trace=union.TraceStudy(
                factory=small_trace_factory, slots=3,
                policies=["fcfs", "easy"], seeds=[0, 1], batch=batch),
            probes=8, probe_every=4, hist=24, timeline=True)

    res_b = union.run(study(True))
    res_s = union.run(study(False))
    assert len(res_b.cells) == len(res_s.cells) == 4
    for cb, cs in zip(res_b.cells, res_s.cells):
        assert (cb.seed, cb.policy) == (cs.seed, cs.policy)
        for key in ("probes", "latency_hist", "timeline"):
            assert key in cb.report, f"{key} missing from batched report"
        assert cb.report["timeline"]["jobs"], "timeline recorded no jobs"
        rb = {k: v for k, v in cb.report.items()
              if k not in ("wall_s", "jobs_per_sec")}
        rs = {k: v for k, v in cs.report.items()
              if k not in ("wall_s", "jobs_per_sec")}
        assert rb == rs, f"cell {cb.seed}/{cb.policy} diverged"


# ---------------------------------------------------------------------------
# deprecation shims: old doors still work, warn, and match the facade
# ---------------------------------------------------------------------------

def test_old_entry_points_warn_and_match(golden):
    with pytest.warns(DeprecationWarning, match="run_campaign"):
        camp = union.run_campaign(tiny_scenario(), members=2, base_seed=0)
    for rep, g in zip(camp.reports, golden["scenario"]["members"]):
        assert_member_matches(rep, g)

    with pytest.warns(DeprecationWarning, match="run_scenario"):
        rep = union.run_scenario(tiny_scenario(), seed=0)
    assert_member_matches(rep, golden["scenario"]["members"][0])

    with pytest.warns(DeprecationWarning, match="run_ragged_campaign"):
        rag = union.run_ragged_campaign([sc_a(), sc_b()], seeds=[0, 1])
    assert rag.summary["ragged"]["buckets"] == 1
    for rep, g in zip(rag.reports, golden["ragged"]["members"]):
        assert_member_matches(rep, g)

    with pytest.warns(DeprecationWarning, match="run_sched_campaign"):
        camp = union.run_sched_campaign(
            golden_trace(), policies=("fcfs",), seeds=(0,))
    row = camp["runs"]["fcfs"][0]
    g = golden["trace"]["policies"]["fcfs"]
    assert row["makespan_ms"] == g["makespan_us"] / 1000.0
    assert row["windows"] == g["windows"]

    from repro.sched import run_trace

    with pytest.warns(DeprecationWarning, match="run_trace"):
        res = run_trace(golden_trace(), policy="easy", seed=0)
    assert res.makespan_us == golden["trace"]["policies"]["easy"]["makespan_us"]


# ---------------------------------------------------------------------------
# one engine cache serves every execution path
# ---------------------------------------------------------------------------

def test_engine_cache_shared_across_scenario_and_trace_paths():
    """A scenario study and a trace study deliberately shaped to the same
    envelope + system config share ONE compiled engine — the cache-hit
    counters prove both paths draw from the same process-wide cache."""
    # pool_size=257 makes this envelope + config unique to this test, so
    # the first run is a genuine compile even mid-suite
    pp = PP.replace("1024", "3333")
    sc = Scenario(
        name="cache-sc",
        jobs=[ScenarioJob(app="j0", source=pp, ranks=2),
              ScenarioJob(app="j1", source=pp, ranks=2)],
        placement="RN", tick_us=2.0, horizon_ms=50.0, pool_size=257)
    from repro.sched.trace import TraceJob

    trace = Trace(
        name="cache-tr", slots=2, placement="RN", routing="ADP",
        tick_us=2.0, horizon_ms=50.0, pool_size=257,
        jobs=[
            TraceJob(name="t0", app="j0", ranks=2, arrival_us=0.0,
                     est_runtime_us=500.0, source=pp),
            TraceJob(name="t1", app="j1", ranks=2, arrival_us=50.0,
                     est_runtime_us=500.0, source=pp),
        ],
    )

    res1 = union.run(union.Experiment(
        name="warmup", scenarios=[sc], members=1))
    assert res1.engine_cache["misses"] == 1  # first sight of this envelope
    assert res1.engine_cache["builds"] == 1  # a miss is a real build

    res2 = union.run(union.Experiment(
        name="mixed", scenarios=[sc], members=2,
        trace=union.TraceStudy(trace=trace, policies=["easy"], seeds=1)))
    # scenario node AND trace node both hit the engine compiled by res1
    assert res2.engine_cache == {"hits": 2, "misses": 0, "builds": 0}
    assert len(res2.cells) == 3
    # v4: the artifact's telemetry carries THIS run's deltas (no compile
    # happened during res2) plus the absolute cache size
    tel = res2.telemetry["engine_cache"]
    assert tel["hits"] == 2 and tel["misses"] == 0 and tel["builds"] == 0
    assert tel["size"] >= 1
    assert set(tel) >= {"hits", "misses", "builds", "size"}


# ---------------------------------------------------------------------------
# seed derivation: one module, bit-compatible with the historical values
# ---------------------------------------------------------------------------

def test_seed_streams_pinned():
    from repro.union.seeds import engine_seed, place_seed

    # the historical manager._engine_seed values
    assert engine_seed(0) == 1
    assert engine_seed(1) == 2654435762
    assert engine_seed(7) == 1401181144
    assert engine_seed(2**31) == ((2**31) * 2654435761 + 1) % (2**32)
    # the historical scheduler._place_seed values
    assert place_seed(0, 0) == 17
    assert place_seed(3, 11) == 3087135
    assert place_seed(123456, 789) == 1056050540
    # the old names keep working (now aliases)
    from repro.sched.scheduler import _place_seed
    from repro.union.manager import _engine_seed

    assert _engine_seed(7) == engine_seed(7)
    assert _place_seed(3, 11) == place_seed(3, 11)


# ---------------------------------------------------------------------------
# strict spec validation: offending paths in every message
# ---------------------------------------------------------------------------

def test_unknown_keys_raise_with_path():
    with pytest.raises(ValueError, match=r"scenario\.jobs\[1\]"):
        Scenario.from_dict({
            "name": "x",
            "jobs": [{"app": "nn"}, {"app": "pp", "startus": 3.0}],
        })
    with pytest.raises(ValueError, match=r"scenario\.ur"):
        Scenario.from_dict({
            "name": "x", "jobs": [{"app": "nn"}], "ur": {"rank": 8}})
    with pytest.raises(ValueError, match="unknown scenario keys at scenario"):
        Scenario.from_dict({"name": "x", "jobs": [{"app": "nn"}],
                            "tpo": "1d"})
    with pytest.raises(ValueError, match=r"experiment\.scenarios\[0\]"):
        union.Experiment.from_dict({
            "name": "e", "scenarios": [{"name": "s", "jbos": []}]})
    with pytest.raises(ValueError, match=r"experiment\.trace"):
        union.Experiment.from_dict({
            "name": "e", "trace": {"source": "poisson", "polcies": []}})
    with pytest.raises(ValueError, match=r"experiment\.grid"):
        union.Experiment.from_dict({
            "name": "e", "scenarios": [{"name": "s", "jobs": [{"app": "nn"}]}],
            "grid": {"placement": ["RN"]}})
    with pytest.raises(ValueError, match=r"trace\.jobs\[0\]"):
        Trace.from_dict({
            "name": "t",
            "jobs": [{"name": "j", "app": "nn", "arrive_us": 0.0}]})


def test_out_of_range_values_raise_with_path():
    with pytest.raises(ValueError, match=r"scenario\.jobs\[0\].*start_us"):
        Scenario.from_dict(
            {"name": "x", "jobs": [{"app": "nn", "start_us": -5.0}]})
    with pytest.raises(ValueError, match="experiment: experiment needs"):
        union.Experiment.from_dict({"name": "empty"})
    with pytest.raises(ValueError, match=r"experiment\.trace.*policy"):
        union.Experiment.from_dict({
            "name": "e", "trace": {"source": "poisson",
                                   "policies": ["sjf"]}})


def test_trace_factory_study_runs_and_serializes():
    """A factory-built TraceStudy (the synthetic-sweep escape hatch) runs
    through the facade and records '<callable>' in the artifact spec
    instead of crashing at serialization time."""
    with pytest.warns(DeprecationWarning, match="run_sched_campaign"):
        camp = union.run_sched_campaign(
            lambda seed: golden_trace(), policies=("fcfs",), seeds=(0,))
    assert camp["runs"]["fcfs"][0]["completed"] == 8
    res = union.run(union.Experiment(
        name="fac", trace=union.TraceStudy(
            factory=lambda seed: golden_trace(), policies=["fcfs"])))
    assert res.experiment["trace"]["factory"] == "<callable>"
    # ...and loading that recorded spec back fails with the path, not a
    # late TypeError mid-run
    with pytest.raises(ValueError, match=r"experiment\.trace.*callable"):
        union.Experiment.from_dict(res.experiment)


def test_experiment_file_refs_resolve_relative_to_spec(tmp_path):
    """Scenario/trace files named inside an experiment spec resolve
    against the spec file's directory, not the process cwd."""
    tiny_scenario().to_json(str(tmp_path / "mix.json"))
    golden_trace().to_json(str(tmp_path / "stream.json"))
    spec = dict(name="rel", scenarios=["mix.json"], members=1,
                trace=dict(source="stream.json", policies=["fcfs"]))
    path = str(tmp_path / "exp.json")
    with open(path, "w") as f:
        json.dump(spec, f)
    exp = union.Experiment.from_json(path)
    assert exp.scenarios[0].name == "tiny"
    assert exp.trace.trace_for(0).name == "golden-trace"


def test_experiment_json_roundtrip(tmp_path):
    exp = union.Experiment(
        name="rt", scenarios=[tiny_scenario()], members=3, base_seed=5,
        grid=union.StudyGrid(placements=["RN", "RG"]),
        trace=union.TraceStudy(source="poisson", jobs=4, policies=["easy"]),
    )
    path = str(tmp_path / "exp.json")
    exp.to_json(path)
    exp2 = union.Experiment.from_json(path)
    assert exp2.name == "rt" and exp2.members == 3
    assert exp2.grid.placements == ["RN", "RG"]
    assert exp2.scenarios[0] == exp.scenarios[0]
    assert exp2.trace.source == "poisson" and exp2.trace.jobs == 4


# ---------------------------------------------------------------------------
# the study grid
# ---------------------------------------------------------------------------

def test_grid_expansion_plans_variants():
    from repro.union.planner import plan

    exp = union.Experiment(
        name="g", scenarios=[tiny_scenario()], members=2,
        grid=union.StudyGrid(placements=["RN", "RG"]))
    pl = plan(exp)
    cells = [c for n in pl.batched_nodes for c in n.cells]
    assert len(cells) == 4  # 2 placements x 2 members
    assert {c.scenario.placement for c in cells} == {"RN", "RG"}
    assert pl.describe().startswith("plan for experiment 'g'")


def test_grid_results_grouped_by_coordinates():
    res = union.run(union.Experiment(
        name="g", scenarios=[tiny_scenario()], members=1,
        grid=union.StudyGrid(placements=["RN", "RG"])))
    keys = set(res.summary["scenario_studies"])
    assert keys == {"tiny/1d/RN/ADP", "tiny/1d/RG/ADP"}
    rows = res.records()
    assert {r["placement"] for r in rows} == {"RN", "RG"}
    assert all(r["kind"] == "scenario" for r in rows)


# ---------------------------------------------------------------------------
# the Results artifact
# ---------------------------------------------------------------------------

def test_results_roundtrip(tmp_path):
    res = union.run(union.Experiment(
        name="rt", scenarios=[sc_a()], members=2))
    path = str(tmp_path / "results.json")
    res.save(path)
    loaded = union.Results.load(path)
    assert loaded.schema_version == res.schema_version
    assert len(loaded.cells) == len(res.cells)
    assert [c.name for c in loaded.cells] == [c.name for c in res.cells]
    # the whole artifact survives the round trip bit-for-bit (as JSON)
    a = json.dumps(res.to_dict(), sort_keys=True, default=float)
    b = json.dumps(loaded.to_dict(), sort_keys=True, default=float)
    assert a == b
    # tidy records regenerate identically from the loaded artifact
    assert loaded.records() == res.records()
    # schema versioning: future artifacts are rejected, not misread
    bad = json.loads(a)
    bad["schema_version"] = 99
    with pytest.raises(ValueError, match="schema_version"):
        union.Results.from_dict(bad)


# ---------------------------------------------------------------------------
# CLI: flags are a thin translation onto the facade
# ---------------------------------------------------------------------------

def test_cli_experiment_mode(tmp_path, capsys):
    from repro.union.cli import main

    spec = dict(
        name="cli-smoke",
        scenarios=[tiny_scenario().to_dict()],
        members=1,
    )
    path = str(tmp_path / "exp.json")
    with open(path, "w") as f:
        json.dump(spec, f)
    out_dir = str(tmp_path / "out")
    main(["--experiment", path, "--out", out_dir])
    text = capsys.readouterr().out
    assert "experiment: cli-smoke" in text
    arts = os.listdir(out_dir)
    assert len(arts) == 1
    loaded = union.Results.load(os.path.join(out_dir, arts[0]))
    assert loaded.experiment["name"] == "cli-smoke"
    assert loaded.cells[0].kind == "scenario"


def test_cli_plan_and_list(tmp_path, capsys):
    from repro.union.cli import main

    spec = dict(name="plan-smoke", scenarios=[tiny_scenario().to_dict()],
                members=2)
    path = str(tmp_path / "exp.json")
    with open(path, "w") as f:
        json.dump(spec, f)
    main(["--experiment", path, "--plan"])
    text = capsys.readouterr().out
    assert "batched × 2 members" in text

    main(["--list"])
    text = capsys.readouterr().out
    assert "workload1" in text and "poisson" in text
