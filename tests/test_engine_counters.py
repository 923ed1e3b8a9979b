"""The engine's always-on tick counters, its named tick phases, and the
host-plane tracing that reads them.

* ``Metrics.ticks`` / ``inject_ticks`` / ``active_slot_ticks`` /
  ``peak_active`` equal a host replay that steps ``eng.tick`` one tick
  at a time, for a batch whose members finish on different ticks;
* ``Metrics.drain_rung_ticks`` counts each member's live ticks at the
  drain width it took, as a host replay of the chosen widths does, and
  ``report["engine"]["mean_drain_width"]`` reads it;
* ``Metrics.inject_passes`` counts the passes each member's injecting
  ticks routed their candidates in, as a host replay of the fired
  counts does, and ``report["engine"]["mean_inject_width"]`` reads it;
* ``Engine.phase_map`` names every ``tick/<phase>`` scope;
* spans mirror onto a ``jax.profiler`` trace's host plane only while
  tracing, and the facade emits ``engine.counters`` only then.
"""
import glob
import os
import re

import numpy as np
import pytest

import jax

from repro import obs, union
from repro.kernels.ops import drain_widths
from repro.netsim import engine as ENG
from repro.netsim.engine import (
    MAXE, hlo_phases, inject_width, member_state, routed_candidates,
    stack_members,
)
from repro.obs import ProbeConfig
from repro.union import manager as MGR
from repro.union.scenario import Scenario, ScenarioJob, URDecl

import test_engine_equivalence as EQ
import test_inject_live as INJ
import test_obs as OBS

HORIZON_US = EQ.mixed_scenario().horizon_ms * 1000.0
PHASES = {"loop", "faults", "emit", "inject", "drain", "latency", "deliver",
          "advance", "window", "skip", "counters", "probes"}


@pytest.fixture(scope="module")
def mixed():
    """The golden mix (staggered jobs, UR traffic) as a B=2 batch whose
    second member's ping-pong job arrives later, so it finishes later."""
    rs = MGR.resolve(EQ.mixed_scenario(), seed=3)
    eng = MGR.build(rs)
    s0 = eng.init_state(seed=MGR._engine_seed(3))
    s1 = eng.init_state(seed=11, start_us=[0.0, 2300.0])
    return eng, stack_members([s0, s1])


def _replay(eng, state):
    """Step ``eng.tick`` until every member stops; count on the host what
    each member's counters should read."""
    tick = jax.jit(eng.tick)
    B = state.t.shape[0]
    ticks = np.zeros(B, np.int64)
    inject = np.zeros(B, np.int64)
    active = np.zeros(B, np.float64)
    peak = np.zeros(B, np.int64)
    s = jax.device_get(state)
    while True:
        done = s.vms.done.all(axis=(1, 2)) & ~s.pool.active.any(axis=1)
        live = (s.t < HORIZON_US) & ~done
        if not live.any():
            return s, ticks, inject, active, peak
        nxt = jax.device_get(tick(s))
        # the rng advances by 1 a live tick, plus P*MAXE for each fired
        # job and Pu when the UR source fired
        drng = (nxt.rng.astype(np.int64) - s.rng.astype(np.int64)) % 2**32
        assert (drng >= 1).tolist() == live.tolist()
        # slots the drain saw = active after the tick + delivered in it
        delivered = nxt.metrics.lat_cnt.sum(1) - s.metrics.lat_cnt.sum(1)
        seen = nxt.pool.active.sum(1) + delivered
        ticks += live
        inject += live & (drng > 1)
        active += np.where(live, seen, 0)
        peak = np.maximum(peak, np.where(live, seen, 0))
        s = nxt


def test_counters_equal_a_host_replay(mixed):
    eng, state = mixed
    final = jax.device_get(eng.run(state))
    s, ticks, inject, active, peak = _replay(eng, state)
    m = final.metrics
    # the members stop on different ticks, and the replay ends where run does
    assert ticks[0] != ticks[1]
    np.testing.assert_array_equal(s.t, final.t)
    np.testing.assert_array_equal(m.ticks, ticks)
    np.testing.assert_array_equal(m.inject_ticks, inject)
    np.testing.assert_array_equal(m.peak_active, peak)
    np.testing.assert_allclose(m.active_slot_ticks, active, rtol=1e-6)
    assert (inject > 0).all() and (inject < ticks).all()
    assert (peak > 0).all()


def test_report_engine_block(mixed):
    eng, state = mixed
    rs = MGR.resolve(EQ.mixed_scenario(), seed=3)
    final = jax.device_get(eng.run(state))
    st = jax.tree_util.tree_map(lambda x: x[0], final)
    blk = MGR.member_report(st, rs)["engine"]
    M = st.pool.active.shape[-1]
    assert blk["ticks"] == int(st.metrics.ticks)
    assert blk["inject_ticks"] == int(st.metrics.inject_ticks)
    assert blk["mean_pool_occupancy"] == pytest.approx(
        float(st.metrics.active_slot_ticks) / (blk["ticks"] * M))
    assert 0.0 < blk["mean_pool_occupancy"] <= blk["peak_pool_occupancy"]
    assert blk["peak_pool_occupancy"] == int(st.metrics.peak_active) / M


def test_drain_rung_ticks_count_live_ticks(mixed):
    """Each member counts one rung a live tick: the member that stops
    first stops counting while the loop runs on for the other."""
    eng, state = mixed
    m = jax.device_get(eng.run(state)).metrics
    assert m.ticks[0] != m.ticks[1]
    np.testing.assert_array_equal(m.drain_rung_ticks.sum(axis=1), m.ticks)


def _bursty_scenario():
    """A UR source of 480 ranks firing 256 KiB every 200 us over a pool of
    1,024 slots: the live count crosses both narrower rungs (512, 256)."""
    return Scenario(
        name="drain-rungs",
        jobs=[ScenarioJob(app="ar8", source=EQ.AR, ranks=8)],
        placement="RN", routing="ADP",
        ur=URDecl(ranks=480, size_bytes=262144.0, interval_us=200.0),
        tick_us=2.0, horizon_ms=0.6, pool_size=1024,
    )


def test_mean_drain_width_equals_a_host_replay():
    rs = MGR.resolve(_bursty_scenario(), seed=3)
    eng = MGR.build(rs)
    state = stack_members([eng.init_state(seed=1),
                           eng.init_state(seed=2, start_us=[300.0])])
    widths = drain_widths(1024)
    tick = jax.jit(eng.tick)
    s = jax.device_get(state)
    rungs = np.zeros((2, len(widths)), np.int64)
    horizon = rs.scenario.horizon_ms * 1000.0
    while True:
        done = s.vms.done.all(axis=(1, 2)) & ~s.pool.active.any(axis=1)
        live = (s.t < horizon) & ~done
        if not live.any():
            break
        nxt = jax.device_get(tick(s))
        # slots the drain saw = active after the tick + delivered in it;
        # the batch drains at the narrowest width holding its fullest member
        delivered = nxt.metrics.lat_cnt.sum(1) - s.metrics.lat_cnt.sum(1)
        seen = (nxt.pool.active.sum(1) + delivered).max()
        rung = max(i for i, w in enumerate(widths) if seen <= w)
        rungs[:, rung] += live
        s = nxt
    final = jax.device_get(eng.run(state))
    np.testing.assert_array_equal(final.metrics.drain_rung_ticks, rungs)
    assert (rungs > 0).sum(axis=1).min() >= 2  # the width changes
    for b in range(2):
        blk = MGR.member_report(member_state(final, b), rs)["engine"]
        ticks = int(final.metrics.ticks[b])
        assert blk["mean_drain_width"] == pytest.approx(
            float(rungs[b] @ np.asarray(widths)) / (ticks * 1024), rel=1e-12)
        assert blk["mean_pool_occupancy"] <= blk["mean_drain_width"] < 1.0


def test_inject_passes_count_injecting_ticks(mixed):
    """A member's injecting ticks each take a pass or more, and only they
    take any (the mix drops nothing, so each one allocates)."""
    eng, state = mixed
    m = jax.device_get(eng.run(state)).metrics
    assert (m.inject_ticks > 0).all()
    assert (m.inject_passes >= m.inject_ticks).all()
    assert (m.inject_passes < 2 * m.inject_ticks).all()


@pytest.mark.parametrize("width", [1024, 256])
def test_mean_inject_width_equals_a_host_replay(monkeypatch, width):
    """The bursty mix of ``test_inject_live``: 216 ranks fire 1,296, 864
    and up to 216 candidates a tick, with 64 UR candidates, so passes of
    1,024 route a tick in one or two and passes of 256 in one to six."""
    monkeypatch.setattr(ENG, "INJECT_WIDTH", width)
    rs = MGR.resolve(INJ.bursty_scenario(), seed=3)
    eng = INJ.uncached_engine(rs)
    state = stack_members([eng.init_state(seed=1),
                           eng.init_state(seed=2, start_us=[90.0, 0.0])])
    n = 2 * 256 * MAXE + 64
    assert inject_width(n, 4096) == width
    tick = jax.jit(eng.tick)
    s = jax.device_get(state)
    passes = np.zeros(2, np.int64)
    taken = set()
    horizon = rs.scenario.horizon_ms * 1000.0
    while True:
        done = s.vms.done.all(axis=(1, 2)) & ~s.pool.active.any(axis=1)
        live = (s.t < horizon) & ~done
        if not live.any():
            break
        nxt = jax.device_get(tick(s))
        # a job candidate fires for each message a rank enters its
        # (op, round) with, so the fired count is what the ranks' send
        # needs grew by, and a UR rank's count grows when it fires; the
        # rng moves by more than 1 when a job or the UR source fired. The
        # batch takes as many passes as its fullest member allocates.
        fired = (nxt.vms.send_need.sum(axis=(1, 2))
                 - s.vms.send_need.sum(axis=(1, 2))
                 + nxt.ur.count.sum(axis=1) - s.ur.count.sum(axis=1))
        drng = (nxt.rng.astype(np.int64) - s.rng.astype(np.int64)) % 2**32
        injecting = live & (drng > 1)
        k = -(-np.minimum(fired, s.pool.free_top).max() // width)
        passes += injecting * k
        if injecting.any():
            taken.add(int(k))
        s = nxt
    final = jax.device_get(eng.run(state))
    np.testing.assert_array_equal(final.metrics.inject_passes, passes)
    assert len(taken) >= (2 if width == 1024 else 3)
    routed = passes * width
    np.testing.assert_array_equal(routed_candidates(final), routed)
    for b in range(2):
        blk = MGR.member_report(member_state(final, b), rs)["engine"]
        ticks = int(final.metrics.inject_ticks[b])
        assert blk["inject_ticks"] == ticks
        assert blk["mean_inject_width"] == pytest.approx(
            routed[b] / (ticks * n), rel=1e-12)
        assert 0.0 < blk["mean_inject_width"] < 1.0


def test_phase_map_names_every_phase():
    """A probed engine with a UR source compiles every phase; each mapped
    instruction carries a ``tick/`` scope or feeds one."""
    rs = MGR.resolve(EQ.mixed_scenario(), seed=3)
    eng = MGR.build(rs, probes=ProbeConfig(samples=8, every=4))
    pm = eng.phase_map(eng.init_state(seed=1))
    assert set(pm.values()) == PHASES
    text = eng._lower(eng.init_state(seed=1)).compile().as_text()
    assert hlo_phases(text) == pm
    for line in text.splitlines():
        m = re.match(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        op = re.search(r'op_name="([^"]*)"', line)
        if m and op and m.group(1) in pm and " fusion(" not in line:
            assert f"tick/{pm[m.group(1)]}" in op.group(1)


def test_hlo_phases_rules():
    text = "\n".join([
        "%fused_computation.1 (p: f32[4]) -> f32[4] {",
        '  %a.1 = f32[4]{0} add(%p, %p), metadata={op_name="jit(f)/while/'
        'body/tick/drain/add"}',
        "  ROOT %c.1 = f32[4]{0} concatenate(%a.1)",
        "}",
        "%body (s: f32[4]) -> f32[4] {",
        "  %g = f32[4]{0} get-tuple-element(%s), index=0",
        "  %cp = f32[4]{0} copy(%g)",
        "  %fusion.7 = f32[4]{0} fusion(%cp), kind=kLoop, "
        "calls=%fused_computation.1",
        '  %m = f32[4]{0} multiply(%fusion.7, %fusion.7), metadata={op_name='
        '"jit(f)/while/body/tick/emit/cond/branch_1_fun/mul"}',
        '  ROOT %x = f32[4]{0} add(%m, %m), metadata={op_name="jit(f)/add"}',
        "}",
        "%loop_body (s: f32[4]) -> f32[4] {",
        "  %lg = f32[4]{0} get-tuple-element(%s), index=0",
        '  %rw = f32[4]{0} reduce-window(%lg), metadata={op_name='
        '"jit(f)/shard_map/reduce-window.35"}',
        '  ROOT %d = f32[4]{0} add(%rw, %rw), metadata={op_name="jit(f)/'
        'shard_map/jit(g)/while/body/tick/deliver/add"}',
        "}",
        "%main (p0: f32[4]) -> f32[4] {",
        '  %w = f32[4]{0} while(%p0), condition=%cond, body=%loop_body',
        '  ROOT %out = f32[4]{0} copy(%w), metadata={op_name="jit(f)/'
        'shard_map"}',
        "}",
    ])
    pm = hlo_phases(text)
    # the fusion's root has no metadata: the scope nearest the root
    assert pm["fusion.7"] == "drain"
    # a copy the compiler added takes the phase of what it feeds
    assert pm["cp"] == "drain"
    assert pm["m"] == "emit"
    assert "x" not in pm and "g" not in pm
    # an op of a while loop's body left with no tick scope (as the SPMD
    # partitioner leaves what it rebuilds) takes the phase of what it
    # feeds; one outside the loop stays unnamed
    assert pm["rw"] == "deliver" and pm["d"] == "deliver"
    assert "out" not in pm


def _host_events(path):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return {e.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


@pytest.mark.parametrize("on", [True, False])
def test_span_on_the_profile_host_plane(tmp_path, tracer, on):
    if on:
        tracer.enable()
    else:
        tracer.disable()
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("probe.mirrored", cat="test"):
            jax.block_until_ready(jax.numpy.ones(8) + 1)
    tracer.disable()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    assert ("probe.mirrored" in _host_events(path)) == on


@pytest.fixture
def tracer():
    tr = obs.get_tracer()
    was_enabled, events = tr.enabled, tr.events
    programs, phases = tr.programs, tr._phases
    tr.events, tr.programs, tr._phases = [], {}, {}
    yield tr
    tr.enabled, tr.events = was_enabled, events
    tr.programs, tr._phases = programs, phases


def _tiny_experiment(members=2, vmapped=True):
    return union.Experiment(name="tiny-counters",
                            scenarios=[OBS.tiny_scenario()],
                            members=members, vmapped=vmapped)


@pytest.mark.parametrize("vmapped", [True, False])
def test_engine_counters_event_only_with_spans(tracer, vmapped):
    tr = tracer
    tr.disable()
    union.run(_tiny_experiment(vmapped=vmapped))
    assert not [e for e in tr.events if e["name"] == "engine.counters"]
    assert not tr.programs

    tr.enable()
    res = union.run(_tiny_experiment(vmapped=vmapped))
    tr.disable()
    evs = [e["args"] for e in tr.events if e["name"] == "engine.counters"]
    ticks = [c.report["engine"]["ticks"] for c in res.cells]
    # one event per engine run: one batched run, or one run a member
    assert len(evs) == (1 if vmapped else len(ticks))
    assert sum(e["member_ticks"] for e in evs) == sum(ticks)
    assert sum(e["iterations"] for e in evs) == (
        max(ticks) if vmapped else sum(ticks))
    assert all(e["active_slot_ticks"] > 0 for e in evs)
    # the drain's slot-ticks: Σ ticks x width, whole numbers, one a run
    M = OBS.tiny_scenario().pool_size
    assert all(e["drain_slot_ticks"] == int(e["drain_slot_ticks"]) > 0
               for e in evs)
    assert sum(e["drain_slot_ticks"] for e in evs) == round(sum(
        c.report["engine"]["mean_drain_width"] * c.report["engine"]["ticks"]
        * M for c in res.cells))
    # the injection's candidate-ticks: Σ injecting ticks x width, against
    # a member's full candidate width
    assert all(e["inject_cand_ticks"] == int(e["inject_cand_ticks"]) > 0
               for e in evs)
    assert all(e["inject_cand_width"] == 2 * 2 * 8 for e in evs)
    assert sum(e["inject_cand_ticks"] for e in evs) == round(sum(
        c.report["engine"]["mean_inject_width"]
        * c.report["engine"]["inject_ticks"] * 2 * 2 * 8
        for c in res.cells))
    names = {e["name"] for e in tr.events}
    assert {"planner.resolve", "engine.report"} <= names
    pm = obs.device_phases()
    assert pm and set(pm.values()) <= PHASES
    assert len(tr.programs) == 1  # one program for every run of the shape
