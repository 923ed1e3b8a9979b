"""repro.obs — spans, probes, exporters, and their engine/facade wiring.

The two contracts that matter most:

* **probes-on bit-identity**: the probed engine variant reproduces the
  seed goldens (`tests/data_engine_golden.json`) exactly — probe buffers
  are pure observers, and the *unprobed* engine contains no probe code;
* **disabled-overhead**: with tracing off, a span is one attribute check
  and a shared null handle — instrumenting hot host paths costs < 1% of
  a warm facade run.
"""
import json
import os
import time

import numpy as np
import pytest

import jax

from repro import obs, union
from repro.obs import ProbeConfig
from repro.obs.probes import ring_order
from repro.union import manager as MGR
from repro.union.scenario import Scenario, ScenarioJob

import test_engine_equivalence as EQ

GOLDEN = os.path.join(os.path.dirname(__file__), "data_engine_golden.json")

PP = (
    "For 4 repetitions {\n"
    " task 0 sends a 1024 byte message to task 1 then\n"
    " task 1 sends a 1024 byte message to task 0 }"
)


def tiny_scenario():
    return Scenario(
        name="tiny-obs",
        jobs=[
            ScenarioJob(app="pp0", source=PP, ranks=2),
            ScenarioJob(app="pp1", source=PP, ranks=2, start_us=200.0),
        ],
        placement="RN", tick_us=2.0, horizon_ms=50.0, pool_size=256,
    )


@pytest.fixture
def clean_tracer():
    """Leave the process-wide tracer exactly as found."""
    tr = obs.get_tracer()
    was_enabled = tr.enabled
    events = tr.events
    tr.events = []
    yield tr
    tr.enabled = was_enabled
    tr.events = events


# ---------------------------------------------------------------------------
# sim plane: probes
# ---------------------------------------------------------------------------

def test_probed_engine_bit_identical_to_golden():
    """Probes are observers: the probed engine variant reproduces the
    seed golden's integer trajectory exactly (same ticks, same rng
    schedule, same pool/latency counters) — while filling its rings."""
    with open(GOLDEN) as f:
        g = json.load(f)["equiv-mix"]["state"]
    sc = EQ.mixed_scenario()
    rs = MGR.resolve(sc, seed=3)
    eng = MGR.build(rs, probes=ProbeConfig(samples=32, every=4))
    st = jax.block_until_ready(eng.run(eng.init_state(
        seed=MGR._engine_seed(3))))

    assert float(st.t) == g["t"]
    assert int(st.rng) == g["rng"]
    assert int(st.pool.dropped) == g["dropped"]
    assert int(st.pool.free_top) == g["free_top"]
    assert int(st.metrics.win_idx) == g["win_idx"]
    np.testing.assert_array_equal(np.asarray(st.metrics.lat_cnt),
                                  g["lat_cnt"])

    # and the rings actually observed the run
    assert st.probes is not None
    assert int(st.probes.idx) > 0
    tl = obs.probe_timelines(
        st.probes, list(rs.topo.link_levels()),
        rs.padded_app_names(eng.capacity))
    assert tl["samples"] == min(int(st.probes.idx), 32)
    assert tl["t_us"] == sorted(tl["t_us"])  # chronological after unwrap
    assert set(tl["link_utilization"]) == set(rs.topo.link_levels())
    assert "ar8" in tl["queue_depth"] and "ur" in tl["inflight_latency_us"]
    assert any(v > 0 for v in tl["pool_occupancy"])
    assert any(v > 0 for vs in tl["link_utilization"].values() for v in vs)


def test_probe_sampling_cadence_and_values():
    """Samples land every `every` live ticks; occupancy/depth stay in
    range; a member that never wraps reports wrapped=False."""
    sc = tiny_scenario()
    rs = MGR.resolve(sc, seed=0)
    eng = MGR.build(rs, probes=ProbeConfig(samples=256, every=2))
    st = jax.block_until_ready(eng.run(eng.init_state(seed=1)))
    tl = obs.probe_timelines(
        st.probes, list(rs.topo.link_levels()),
        rs.padded_app_names(eng.capacity))
    idx = int(st.probes.idx)
    assert 0 < tl["samples"] <= 256
    assert tl["samples"] == min(idx, 256)
    assert tl["wrapped"] == (idx > 256)
    assert all(0.0 <= v <= 1.0 for v in tl["pool_occupancy"])
    assert all(d >= 0 for vs in tl["queue_depth"].values() for d in vs)
    # tick counter counted live ticks only; idx = ticks // every
    assert int(st.probes.idx) == int(st.metrics.ticks) // 2


def test_ring_order_basics():
    np.testing.assert_array_equal(ring_order(3, 8), [0, 1, 2])
    np.testing.assert_array_equal(ring_order(8, 8), range(8))
    # one past full: oldest surviving sample is at position 1
    np.testing.assert_array_equal(ring_order(9, 8),
                                  [1, 2, 3, 4, 5, 6, 7, 0])


def test_ring_wraparound_property():
    """hypothesis: replaying idx writes through a K-ring and reading it
    back via ring_order always yields the last min(idx, K) values in
    chronological order."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=200, deadline=None)
    @given(idx=st.integers(min_value=0, max_value=4096),
           K=st.integers(min_value=1, max_value=64))
    def check(idx, K):
        buf = np.full((K,), -1, np.int64)
        for i in range(idx):
            buf[i % K] = i  # the engine's one-hot write at idx % K
        order = ring_order(idx, K)
        n = min(idx, K)
        assert len(order) == n
        np.testing.assert_array_equal(buf[order], np.arange(idx - n, idx))

    check()


def test_probe_config_validation():
    with pytest.raises(ValueError, match="samples"):
        ProbeConfig(samples=0)
    with pytest.raises(ValueError, match="every"):
        ProbeConfig(every=0)
    with pytest.raises(ValueError, match="probes"):
        union.Experiment(name="x", scenarios=[tiny_scenario()],
                         probes=-1).validate()
    with pytest.raises(ValueError, match="probe_every"):
        union.Experiment(name="x", scenarios=[tiny_scenario()],
                         probes=4, probe_every=0).validate()


# ---------------------------------------------------------------------------
# facade: telemetry + schema v3
# ---------------------------------------------------------------------------

def test_results_telemetry_and_probe_reports(tmp_path, clean_tracer):
    clean_tracer.enable()
    res = union.run(union.Experiment(
        name="obs-smoke", scenarios=[tiny_scenario()], members=2,
        probes=8, probe_every=4))
    clean_tracer.disable()

    assert res.schema_version == 4
    tel = res.telemetry
    assert tel["probes"] == {"samples": 8, "every": 4}
    assert tel["hist"] == {} and tel["timeline"] is False
    assert set(tel["engine_cache"]) >= {"hits", "misses", "builds", "size"}
    by_name = tel["spans"]["by_name"]
    for expected in ("union.run", "planner.plan", "engine.run"):
        assert expected in by_name, by_name.keys()
    # union.run nests everything, so it never ranks among the top sinks
    assert all(name != "union.run" for name, _ in tel["spans"]["top"])

    for cell in res.cells:
        pr = cell.report["probes"]
        assert pr["samples"] > 0
        n = pr["samples"]
        assert len(pr["t_us"]) == n == len(pr["pool_occupancy"])
        for series in pr["link_utilization"].values():
            assert len(series) == n
        assert set(pr["queue_depth"]) == {"pp0", "pp1"}

    # artifact round-trip carries telemetry + per-cell probe timelines
    path = str(tmp_path / "res.json")
    res.save(path)
    loaded = union.Results.load(path)
    assert loaded.telemetry == json.loads(
        json.dumps(res.telemetry, default=float))
    assert loaded.cells[0].report["probes"]["t_us"] == pytest.approx(
        res.cells[0].report["probes"]["t_us"])

    # the formatted report surfaces the wall sinks + cache hit ratio
    text = union.format_results(res)
    assert "wall sink #1" in text and "hit)" in text


def test_unprobed_run_has_no_probe_report(clean_tracer):
    clean_tracer.disable()
    res = union.run(union.Experiment(
        name="obs-off", scenarios=[tiny_scenario()], members=1))
    assert "probes" not in res.cells[0].report
    assert res.telemetry["probes"] == {}
    assert res.telemetry["spans"] == {}  # tracing disabled


# ---------------------------------------------------------------------------
# host plane: spans + exporters
# ---------------------------------------------------------------------------

def test_span_records_and_chrome_export(tmp_path, clean_tracer):
    clean_tracer.enable()
    with obs.span("outer", cat="test", k=1) as sp:
        sp.set(extra="v")
        with obs.span("inner", cat="test"):
            time.sleep(0.001)
    obs.counter("pool", occ=0.5, per_chip=[3, 4])
    clean_tracer.disable()

    assert clean_tracer.n_events == 3
    names = [e["name"] for e in clean_tracer.events]
    assert names == ["inner", "outer", "pool"]  # spans close inner-first
    outer = clean_tracer.events[1]
    assert outer["args"] == {"k": 1, "extra": "v"}
    assert outer["dur_us"] >= clean_tracer.events[0]["dur_us"]

    path = str(tmp_path / "trace.json")
    obs.write_chrome_trace(path)
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert len(evs) == 3 and doc["displayTimeUnit"] == "ms"
    X = [e for e in evs if e["ph"] == "X"]
    C = [e for e in evs if e["ph"] == "C"]
    assert len(X) == 2 and len(C) == 1
    for e in X:
        assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)
    # a list value is kept as a list in the record, and becomes one
    # numeric counter per entry in the Chrome trace
    assert clean_tracer.events[2]["args"] == {"occ": 0.5,
                                              "per_chip": [3.0, 4.0]}
    assert C[0]["args"] == {"occ": 0.5, "per_chip[0]": 3.0,
                            "per_chip[1]": 4.0}

    jl = str(tmp_path / "trace.jsonl")
    obs.write_jsonl(jl)
    with open(jl) as f:
        lines = [json.loads(line) for line in f]
    assert len(lines) == 3 and lines[0]["name"] == "inner"


def test_summarize_aggregates_and_ranks():
    events = [
        dict(name="a", cat="x", ts_us=0.0, dur_us=1000.0, cpu_ms=0.5),
        dict(name="a", cat="x", ts_us=5.0, dur_us=3000.0, cpu_ms=1.0),
        dict(name="b", cat="x", ts_us=9.0, dur_us=2000.0, cpu_ms=0.1),
        dict(name="union.run", cat="run", ts_us=0.0, dur_us=9000.0,
             cpu_ms=2.0),
        dict(name="cnt", ph="C", ts_us=1.0, args={"v": 1.0}),
    ]
    s = obs.summarize(events, top=3)
    assert s["by_name"]["a"] == dict(
        count=2, total_ms=4.0, max_ms=3.0, cpu_ms=1.5, cat="x")
    assert [name for name, _ in s["top"]] == ["a", "b"]  # no union.run
    assert "cnt" not in s["by_name"]


def test_span_disabled_overhead_smoke(clean_tracer):
    """The instrumented-but-disabled path costs < 1% of a warm facade
    run: time as many disabled span entries as an enabled run actually
    records, against the warm facade wall."""
    clean_tracer.disable()
    exp = union.Experiment(
        name="overhead", scenarios=[tiny_scenario()], members=1)
    union.run(exp)  # pays any compile
    t0 = time.perf_counter()
    union.run(exp)
    warm_wall = time.perf_counter() - t0

    clean_tracer.enable()
    union.run(exp)
    n_spans = clean_tracer.n_events
    clean_tracer.disable()
    assert n_spans > 0

    assert not obs.tracing()
    t0 = time.perf_counter()
    for _ in range(n_spans):
        with obs.span("noop", cat="test"):
            pass
    disabled_wall = time.perf_counter() - t0
    assert disabled_wall < 0.01 * warm_wall, (
        f"{n_spans} disabled spans cost {disabled_wall * 1e3:.3f}ms "
        f"vs warm facade {warm_wall * 1e3:.1f}ms")


def test_logger_verbosity_levels():
    import logging

    from repro.obs import log, set_verbosity

    try:
        set_verbosity(0)
        assert log.level == logging.WARNING  # quiet by default
        set_verbosity(1)
        assert log.level == logging.INFO
        set_verbosity(2)
        assert log.level == logging.DEBUG
    finally:
        set_verbosity(0)


def test_log_to_jsonl_sink(tmp_path):
    from repro.obs import log, log_to_jsonl, set_verbosity

    path = str(tmp_path / "run.jsonl")
    h = log_to_jsonl(path)
    try:
        set_verbosity(1)
        log.info("hello %s", "world")
    finally:
        set_verbosity(0)
        log.removeHandler(h)
        h.close()
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert recs and recs[-1]["msg"] == "hello world"
    assert recs[-1]["level"] == "INFO"


# ---------------------------------------------------------------------------
# bench provenance contract
# ---------------------------------------------------------------------------

def test_bench_records_all_carry_provenance():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_union",
        os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                     "bench_union.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    # the checked-in file passes the strict check (no backfill needed)
    entries = bench.load_bench(backfill=False)
    assert entries, "BENCH_union.json should have records"
    for e in entries:
        assert isinstance(e["provenance"], dict)

    # a legacy record without provenance is rejected strictly and
    # backfilled (marked) otherwise
    with pytest.raises(ValueError, match="provenance"):
        bench._check_entry({"bench": "x"})
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump([{"bench": "legacy"}], f)
        tmp = f.name
    try:
        with pytest.raises(ValueError, match="provenance"):
            bench.load_bench(tmp, backfill=False)
        fixed = bench.load_bench(tmp, backfill=True)
        assert fixed[0]["provenance"] == {"backfilled": True}
    finally:
        os.unlink(tmp)


# ---------------------------------------------------------------------------
# sim plane: latency histograms
# ---------------------------------------------------------------------------

def test_hist_engine_bit_identical_to_golden():
    """Histograms are observers too: the histogrammed engine variant
    reproduces the seed golden exactly, and every message the metrics
    plane counted lands in exactly one histogram bucket (conservation)."""
    from repro.obs import HistConfig, hist_summary

    with open(GOLDEN) as f:
        g = json.load(f)["equiv-mix"]["state"]
    sc = EQ.mixed_scenario()
    rs = MGR.resolve(sc, seed=3)
    eng = MGR.build(rs, hist=HistConfig(bins=48))
    st = jax.block_until_ready(eng.run(eng.init_state(
        seed=MGR._engine_seed(3))))

    assert float(st.t) == g["t"]
    assert int(st.rng) == g["rng"]
    assert int(st.pool.dropped) == g["dropped"]
    assert int(st.metrics.win_idx) == g["win_idx"]
    np.testing.assert_array_equal(np.asarray(st.metrics.lat_cnt),
                                  g["lat_cnt"])

    # conservation: histogram totals == the metrics plane's per-app counts
    assert st.hist is not None
    counts = np.asarray(st.hist.counts)  # (A, NL, K)
    per_app = counts.sum(axis=(1, 2))
    np.testing.assert_array_equal(per_app[:len(g["lat_cnt"])],
                                  g["lat_cnt"])

    summ = hist_summary(st.hist, rs.padded_app_names(eng.capacity),
                        list(rs.topo.link_levels()))
    for name, a in summ["apps"].items():
        if not a["count"]:
            continue
        assert a["p50_us"] <= a["p95_us"] <= a["p99_us"]
        assert a["variation"] >= 0.0
        assert sum(a["levels"].values()) == a["count"]


def test_hist_matches_numpy_reference():
    """Tick-by-tick host replay: detect every delivery between
    consecutive states, recompute each message's latency in numpy, and
    check the in-engine accumulators bucket-for-bucket — then the
    summary's p50/p99 against exact percentiles (within one log bucket)."""
    from repro.obs import HistConfig, bucket_of, hist_summary

    sc = tiny_scenario()
    cfg = HistConfig(bins=40, lo_us=0.5, ratio=1.25)
    rs = MGR.resolve(sc, seed=0)
    eng = MGR.build(rs, hist=cfg)
    st = eng.init_state(seed=1)

    lats = {}  # app id -> [latency us]
    for _ in range(30_000):
        nxt = jax.block_until_ready(eng.tick(st))
        t1 = float(nxt.t)
        if t1 == float(st.t):
            break  # all jobs done: the member froze
        act0 = np.asarray(st.pool.active)
        act1 = np.asarray(nxt.pool.active)
        inj0 = np.asarray(st.pool.inject_t)
        job0 = np.asarray(st.pool.job)
        # deliveries land at tick end (t0 + tick_us) — NOT at nxt.t,
        # which may have jumped further via the PDES idle skip
        t_end = float(st.t) + sc.tick_us
        for m in np.nonzero(act0 & ~act1)[0]:
            lats.setdefault(int(job0[m]), []).append(t_end - float(inj0[m]))
        st = nxt
    else:
        pytest.fail("member never froze")
    assert int(st.pool.dropped) == 0

    counts = np.asarray(st.hist.counts)  # (A, NL, K)
    app_names = rs.padded_app_names(eng.capacity)
    summ = hist_summary(st.hist, app_names, list(rs.topo.link_levels()))
    assert lats, "host replay saw no deliveries"
    # conservation first: a missed host-side delivery fails loudly here
    assert int(counts.sum()) == sum(len(v) for v in lats.values())
    for ai, ls in lats.items():
        ref = np.zeros(cfg.bins, np.int64)
        np.add.at(ref, bucket_of(np.asarray(ls, np.float64), cfg), 1)
        np.testing.assert_array_equal(counts[ai].sum(axis=0), ref)

        a = summ["apps"][app_names[ai]]
        assert a["count"] == len(ls)
        assert a["max_us"] == pytest.approx(max(ls), rel=1e-5)
        assert a["mean_us"] == pytest.approx(np.mean(ls), rel=1e-5)
        # bucketed quantiles sit within one log bucket of the exact ones
        for p, key in ((50, "p50_us"), (99, "p99_us")):
            exact = np.percentile(ls, p)
            assert exact / cfg.ratio <= a[key] <= exact * cfg.ratio, (
                f"app {ai} p{p}: hist {a[key]} vs exact {exact}")


_HIST_BMANL = (2, 4, 3, 2)  # B members, M slots, A apps, NL levels


def _hist_stream_check(ticks, cut):
    """The histogram monoid contract on one latency stream: total bucket
    count == delivered messages (conservation), and accumulating the
    whole stream equals merging two half-stream accumulators — counts
    and maxima exactly, float moments to tolerance."""
    import jax.numpy as jnp

    from repro.obs import HistConfig, init_hist, merge_hist, update_hist

    B, M, A, NL = _HIST_BMANL
    cfg = HistConfig(bins=8, lo_us=0.5, ratio=2.0)

    def apply(hs, ticks):
        for lat, dlv, app, lvl in ticks:
            hs = update_hist(
                hs, cfg,
                lat=jnp.asarray(lat, jnp.float32).reshape(B, M),
                delivered=jnp.asarray(dlv).reshape(B, M),
                app=jnp.asarray(app, jnp.int32).reshape(B, M),
                level=jnp.asarray(lvl, jnp.int32).reshape(B, M))
        return hs

    def batched_init():
        one = init_hist(cfg, A, NL)
        return one._replace(
            counts=jnp.broadcast_to(one.counts, (B,) + one.counts.shape),
            sum=jnp.broadcast_to(one.sum, (B, A)),
            sumsq=jnp.broadcast_to(one.sumsq, (B, A)),
            max=jnp.broadcast_to(one.max, (B, A)))

    cut = min(cut, len(ticks))
    full = apply(batched_init(), ticks)
    merged = merge_hist(apply(batched_init(), ticks[:cut]),
                        apply(batched_init(), ticks[cut:]))
    n_delivered = sum(sum(d) for _, d, _, _ in ticks)
    assert int(np.asarray(full.counts).sum()) == n_delivered
    np.testing.assert_array_equal(np.asarray(full.counts),
                                  np.asarray(merged.counts))
    np.testing.assert_allclose(np.asarray(full.sum),
                               np.asarray(merged.sum), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(full.sumsq),
                               np.asarray(merged.sumsq), rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(full.max),
                                  np.asarray(merged.max))


def test_hist_conservation_and_merge_fixed_streams():
    """Deterministic fallback for environments without hypothesis: the
    monoid contract on seeded random streams, including the empty one."""
    B, M, A, NL = _HIST_BMANL
    _hist_stream_check([], 0)
    for seed in range(8):
        rng = np.random.default_rng(seed)
        n_ticks = int(rng.integers(1, 7))
        ticks = [
            (list(np.exp(rng.uniform(np.log(1e-3), np.log(1e7), B * M))),
             list(map(bool, rng.integers(0, 2, B * M))),
             list(map(int, rng.integers(0, A, B * M))),
             list(map(int, rng.integers(0, NL, B * M))))
            for _ in range(n_ticks)
        ]
        _hist_stream_check(ticks, int(rng.integers(0, n_ticks + 1)))


def test_hist_conservation_and_merge_property():
    """hypothesis: the same monoid contract over arbitrary latency
    streams (latency values, delivered masks, app/level ids)."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as hst

    B, M, A, NL = _HIST_BMANL
    tick = hst.tuples(
        hst.lists(hst.floats(min_value=1e-3, max_value=1e7,
                             allow_nan=False), min_size=B * M,
                  max_size=B * M),
        hst.lists(hst.booleans(), min_size=B * M, max_size=B * M),
        hst.lists(hst.integers(min_value=0, max_value=A - 1),
                  min_size=B * M, max_size=B * M),
        hst.lists(hst.integers(min_value=0, max_value=NL - 1),
                  min_size=B * M, max_size=B * M),
    )

    @settings(max_examples=50, deadline=None)
    @given(ticks=hst.lists(tick, min_size=0, max_size=6),
           cut=hst.integers(min_value=0, max_value=6))
    def check(ticks, cut):
        _hist_stream_check(ticks, cut)

    check()


def test_hist_config_validation():
    from repro.obs import HistConfig

    with pytest.raises(ValueError, match="bins"):
        HistConfig(bins=1)
    with pytest.raises(ValueError, match="lo_us"):
        HistConfig(lo_us=0.0)
    with pytest.raises(ValueError, match="ratio"):
        HistConfig(ratio=1.0)
    with pytest.raises(ValueError, match="hist"):
        union.Experiment(name="x", scenarios=[tiny_scenario()],
                         hist=1).validate()


# ---------------------------------------------------------------------------
# sim plane: job lifecycle timelines
# ---------------------------------------------------------------------------

def test_timeline_reports_and_sim_trace_export(tmp_path):
    """A timelined trace study reports a lifecycle record per job, and
    the sim-time Chrome trace carries one thread track per engine slot
    plus one span per admitted job."""
    import test_experiment as TE

    res = union.run(union.Experiment(
        name="tl", timeline=True,
        trace=union.TraceStudy(trace=TE.golden_trace(),
                               policies=["fcfs", "easy"], seeds=1)))
    assert res.telemetry["timeline"] is True
    named = []
    for cell in res.cells:
        tl = cell.report["timeline"]
        assert tl["slots"] == 3
        assert len(tl["jobs"]) == 8
        for job in tl["jobs"]:
            assert job["arrival_us"] >= 0.0
            if job["completed"]:
                assert job["start_us"] is not None
                assert job["finish_us"] >= job["start_us"]
                assert job["retire_us"] >= job["finish_us"]
                assert 0 <= job["slot"] < tl["slots"]
        assert tl["queue_depth"], "no queue-depth samples"
        named.append((cell.key, tl))

    path = str(tmp_path / "sim.json")
    obs.write_sim_trace(path, named)
    with open(path) as f:
        doc = json.load(f)
    assert doc["otherData"]["time_domain"] == "sim_us"
    evs = doc["traceEvents"]
    for pid, (key, tl) in enumerate(named):
        procs = [e for e in evs if e["ph"] == "M" and e["pid"] == pid
                 and e["name"] == "process_name"]
        assert [e["args"]["name"] for e in procs] == [key]
        tracks = [e for e in evs if e["ph"] == "M" and e["pid"] == pid
                  and e["name"] == "thread_name"]
        assert [e["args"]["name"] for e in tracks] == [
            f"slot{s}" for s in range(tl["slots"])]
        spans = [e for e in evs if e["ph"] == "X" and e["pid"] == pid]
        started = [j for j in tl["jobs"] if j["start_us"] is not None]
        assert len(spans) == len(started) == 8  # a span for every job
        assert {e["args"]["jid"] for e in spans} == {
            j["jid"] for j in started}
        for e in spans:
            assert e["dur"] >= 0.0 and e["ts"] >= 0.0


def test_untimelined_trace_has_no_timeline():
    import test_experiment as TE

    res = union.run(union.Experiment(
        name="tl-off",
        trace=union.TraceStudy(trace=TE.golden_trace(),
                               policies=["fcfs"], seeds=1)))
    assert "timeline" not in res.cells[0].report
    assert res.telemetry["timeline"] is False


# ---------------------------------------------------------------------------
# process plane: metrics registry + OpenMetrics
# ---------------------------------------------------------------------------

OM_SAMPLE = __import__("re").compile(
    r"^[A-Za-z_:][A-Za-z0-9_:]*(\{[^}]*\})? -?[0-9.eE+na-]+$")


def _lint_openmetrics(text):
    """Minimal OpenMetrics format lint: typed families, parseable
    samples, '# EOF' terminator."""
    lines = text.strip().splitlines()
    assert lines[-1] == "# EOF"
    assert any(line.startswith("# TYPE ") for line in lines)
    for line in lines[:-1]:
        if line.startswith("#"):
            assert line.startswith(("# TYPE ", "# HELP ")), line
        else:
            assert OM_SAMPLE.match(line), f"unparseable sample: {line!r}"


def test_metrics_registry_and_openmetrics(tmp_path):
    from repro.obs import MetricsRegistry

    reg = MetricsRegistry()
    c = reg.counter("union_test_cells", "cells done")
    c.inc()
    c.inc(2, kind="trace")
    assert c.value() == 1 and c.value(kind="trace") == 2
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)
    g = reg.gauge("union_test_wall_seconds", "wall")
    g.set(1.5)
    h = reg.histogram("union_test_node_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    # idempotent re-registration returns the same instrument...
    assert reg.counter("union_test_cells") is c
    # ...but a kind clash is an error, not a silent shadow
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("union_test_cells")

    text = reg.render_openmetrics()
    _lint_openmetrics(text)
    assert "union_test_cells_total 1" in text
    assert 'union_test_cells_total{kind="trace"} 2' in text
    assert "union_test_wall_seconds 1.5" in text
    assert 'union_test_node_seconds_bucket{le="+Inf"} 2' in text
    assert "union_test_node_seconds_count 2" in text

    from repro.obs import write_openmetrics

    path = write_openmetrics(str(tmp_path / "m.txt"), reg)
    with open(path) as f:
        assert f.read() == text


def test_run_populates_metrics_registry(tmp_path):
    from repro.obs import get_registry, write_openmetrics

    reg = get_registry()
    cells0 = reg.counter("union_cells_completed").value()
    runs0 = reg.counter("union_experiments").value()
    union.run(union.Experiment(
        name="metrics-smoke", scenarios=[tiny_scenario()], members=1))
    assert reg.counter("union_cells_completed").value() == cells0 + 1
    assert reg.counter("union_experiments").value() == runs0 + 1
    assert reg.gauge("union_last_run_wall_seconds").value() > 0.0
    _lint_openmetrics(open(write_openmetrics(
        str(tmp_path / "m.txt"))).read())


def test_progress_line():
    import io

    from repro.obs import Progress

    buf = io.StringIO()
    p = Progress(total=2, enabled=True, stream=buf)
    p.advance()
    p.advance()
    p.close()
    out = buf.getvalue()
    assert "1/2" in out and "2/2" in out and out.endswith("\n")
    # disabled: no writes at all
    buf2 = io.StringIO()
    p2 = Progress(total=2, enabled=False, stream=buf2)
    p2.advance()
    p2.close()
    assert buf2.getvalue() == ""


# ---------------------------------------------------------------------------
# bench regression gate
# ---------------------------------------------------------------------------

def test_check_bench_gate():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_bench",
        os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                     "check_bench.py"))
    cb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cb)

    prov = dict(git_commit="old", jax_version="0", backend="cpu",
                device_count=1)
    base = dict(bench="union_trace_batched", jobs=8, slots=3, seeds=2,
                policies=["fcfs"], grid_cells=2, total_jobs=16,
                provenance=prov)
    ok = [dict(base, batched_jobs_per_sec=100.0),
          dict(base, batched_jobs_per_sec=85.0)]  # -15%: within 20%
    assert cb.compare(ok, 0.2, out=lambda *a: None) == []
    bad = [dict(base, batched_jobs_per_sec=100.0),
           dict(base, batched_jobs_per_sec=70.0)]  # -30%: regression
    regs = cb.compare(bad, 0.2, out=lambda *a: None)
    assert regs and "batched_jobs_per_sec" in regs[0]
    # wall-clock benches compare inverted (lower is better)
    wall = [dict(bench="union_experiment_facade", members=2,
                 provenance=prov, warm_facade_wall_s=1.0),
            dict(bench="union_experiment_facade", members=2,
                 provenance=prov, warm_facade_wall_s=1.5)]
    regs = cb.compare(wall, 0.2, out=lambda *a: None)
    assert regs and "warm_facade_wall_s" in regs[0]
    # shape mismatch (quick vs full) never gates
    mixed = [dict(base, batched_jobs_per_sec=100.0),
             dict(base, jobs=32, batched_jobs_per_sec=10.0)]
    assert cb.compare(mixed, 0.2, out=lambda *a: None) == []
    # the checked-in ledger passes end to end
    assert cb.main([]) == 0
