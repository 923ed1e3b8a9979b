"""The drain over the live pool slots (``ops.drain_tick_live``) against
the full-pool pass (``ops.drain_tick``).

The live drain packs each member's live slots, in slot order, into the
smallest rung of ``ops.drain_widths(M)`` that holds the fullest member,
drains them there and puts the per-slot results back. Every output must
equal the full-pool pass bit for bit on CPU, whatever the live counts,
wherever the live slots sit, whatever the inactive slots still hold.
So must the engine's latency metrics (``engine.add_latencies``), run on
the rung's packed slots as the engine runs them.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops
from repro.netsim.config import NetConfig
from repro.netsim.engine import LAT_FIELDS, add_latencies

M, K, L, A, R = 4096, 6, 96, 3, 20
WIDTHS = ops.drain_widths(M)
DT = np.float32(5.0)


def _pool(counts, seed, M=M, K=K, L=L):
    """A (B, M) pool whose member b holds ``counts[b]`` live slots spread
    over the whole pool; every inactive slot keeps a stale route, stale
    bytes and a stale app id, as a freed slot does."""
    rng = np.random.default_rng(seed)
    B = len(counts)
    active = np.zeros((B, M), bool)
    for b, n in enumerate(counts):
        active[b, rng.choice(M, n, replace=False)] = True
    routes = rng.integers(-1, L, (B, M, K)).astype(np.int32)
    rem = (rng.uniform(0.0, 2.0e4, (B, M))
           * (rng.uniform(size=(B, M)) > 0.1)).astype(np.float32)
    job = rng.integers(0, A, (B, M)).astype(np.int32)
    mina = rng.uniform(0.0, 12.0, (B, M)).astype(np.float32)
    t = np.linspace(4.0, 9.0, B).astype(np.float32)
    ldr = np.concatenate([rng.integers(0, R, L), [0]]).astype(np.int32)
    return routes, rem, active, job, mina, t, ldr, rng


def _bandwidth(rng, B, per_member):
    bw = np.concatenate([rng.uniform(1e9, 2e9, L), [1.0]]).astype(np.float32)
    if not per_member:
        return bw
    bw = np.tile(bw, (B, 1))
    bw[-1, 7] = 0.0  # the last member's link 7 is dead
    bw[0, 11] *= np.float32(0.25)  # and the first's link 11 degraded
    return bw


def _assert_same(got, want):
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


def _rung(n):
    """The smallest rung that holds ``n`` live slots."""
    return max(i for i, w in enumerate(WIDTHS) if n <= w)


LIVE = sorted({0, 1, M} | set(WIDTHS) | {w + 1 for w in WIDTHS[1:]})


@pytest.mark.parametrize("per_member_bw", [False, True])
@pytest.mark.parametrize("n", LIVE)
def test_live_drain_equals_full_pool(n, per_member_bw):
    # two members with different live counts; the fuller one sets the rung
    counts = [n, n // 3]
    routes, rem, active, job, mina, t, ldr, rng = _pool(counts, seed=n)
    bw = _bandwidth(rng, len(counts), per_member_bw)
    want = ops.drain_tick(routes, rem, active, job, mina, t, DT, bw, ldr,
                          n_apps=A, n_routers=R)
    *got, _, rung = ops.drain_tick_live(routes, rem, active, job, mina, t, DT,
                                     bw, ldr, n_apps=A, n_routers=R)
    assert int(rung) == _rung(n)
    _assert_same(got, want)
    # the drain did something whenever a slot was live
    assert (np.asarray(got[0]) < rem).any() == (n > 0)


def test_widths_follow_the_pool_size():
    assert WIDTHS == (4096, 2048, 1024, 512, 256)
    assert ops.drain_widths(65536) == (65536, 32768, 16384, 8192, 4096)
    assert ops.drain_widths(512) == (512, 256)
    assert ops.drain_widths(257) == (257,)
    assert ops.drain_widths(64) == (64,)


@pytest.mark.parametrize("n", [0, 5, 64])
def test_one_rung_pool(n):
    """A pool below two rungs drains whole, every tick."""
    routes, rem, active, job, mina, t, ldr, rng = _pool(
        [n], seed=7, M=64, L=L)
    bw = _bandwidth(rng, 1, False)
    want = ops.drain_tick(routes, rem, active, job, mina, t, DT, bw, ldr,
                          n_apps=A, n_routers=R)
    *got, _, rung = ops.drain_tick_live(routes, rem, active, job, mina, t, DT,
                                     bw, ldr, n_apps=A, n_routers=R)
    assert int(rung) == 0
    _assert_same(got, want)


def test_live_slots_at_the_back_of_the_pool():
    """Live slots packed at the pool's end, one member empty."""
    routes, rem, active, job, mina, t, ldr, rng = _pool([0, 0], seed=3)
    active[0, -300:] = True
    bw = _bandwidth(rng, 2, True)
    want = ops.drain_tick(routes, rem, active, job, mina, t, DT, bw, ldr,
                          n_apps=A, n_routers=R)
    *got, _, rung = ops.drain_tick_live(routes, rem, active, job, mina, t, DT,
                                     bw, ldr, n_apps=A, n_routers=R)
    assert WIDTHS[int(rung)] == 512
    _assert_same(got, want)


def _latency_start(rng, B, bins):
    """Latency metrics as a run part-way through holds them: counts and
    sums for some apps, none yet for the last."""
    cnt = rng.integers(0, 50, (B, A)).astype(np.int32)
    cnt[:, -1] = 0
    lat = rng.uniform(1.0, 40.0, (B, A)).astype(np.float32)
    hist = np.zeros((B, A, bins), np.int32)
    hist[:, :, 3] = cnt
    return {f: jnp.asarray(x) for f, x in dict(
        lat_hist=hist, lat_sum=np.where(cnt > 0, lat * cnt, 0.0)
        .astype(np.float32),
        lat_cnt=cnt, lat_min=np.where(cnt > 0, lat, np.inf)
        .astype(np.float32),
        lat_max=np.where(cnt > 0, lat * 2, -np.inf).astype(np.float32),
    ).items()}


@pytest.mark.parametrize("frozen", [False, True])
@pytest.mark.parametrize("n", LIVE)
def test_latency_at_the_rung_equals_full_pool(n, frozen):
    """The latency pass on the rung's packed slots, inside the drain's
    branch, equals the full-pool pass over the drain's full-pool
    deliveries bit for bit: each delivered slot adds the same value at
    the same index in the same order, and the slots left out added 0,
    +inf or -inf. Inactive slots hold stale apps and injection times;
    ``frozen`` freezes the second member (``live_m``)."""
    counts = [n, n // 3]
    routes, rem, active, job, mina, t, ldr, rng = _pool(counts, seed=n + 1)
    # a fifth of the pool has drained already: those live slots deliver
    # wherever their earliest arrival has passed
    rem = np.where(rng.uniform(size=rem.shape) < 0.2, 0.0, rem).astype(
        np.float32)
    inject_t = (mina - rng.uniform(0.5, 3.0, mina.shape)).astype(np.float32)
    bw = _bandwidth(rng, len(counts), True)
    net = NetConfig()
    lat0 = _latency_start(rng, len(counts), net.latency_hist_bins)
    live_m = np.array([True, not frozen])

    def latency(delivered, app_of, inj):
        return add_latencies(lat0, net, t, DT, delivered, live_m, app_of,
                             inj)

    delivered = ops.drain_tick(routes, rem, active, job, mina, t, DT, bw, ldr,
                               n_apps=A, n_routers=R)[2]
    want = latency(delivered, job, inject_t)
    *_, got, rung = ops.drain_tick_live(
        routes, rem, active, job, mina, t, DT, bw, ldr, n_apps=A,
        n_routers=R,
        then=lambda take, d: latency(d, take(job), take(inject_t)))
    assert int(rung) == _rung(n)
    assert sorted(got) == sorted(LAT_FIELDS)
    for f in LAT_FIELDS:
        g, w = np.asarray(got[f]), np.asarray(want[f])
        assert g.dtype == w.dtype and g.shape == w.shape, f
        assert g.tobytes() == w.tobytes(), f
    # several deliveries for each app of the live member, none counted
    # for a frozen one
    added = np.asarray(got["lat_cnt"]) - lat0["lat_cnt"]
    if n >= 512:
        assert (added[0] >= 2).all()
    assert (added[1] == 0).all() if frozen else (added >= 0).all()
