"""The drain over the live pool slots (``ops.drain_tick_live``) against
the full-pool reference (``ref.drain_tick_ref``).

The live drain packs each member's live slots, in slot order, into the
smallest rung of ``ops.drain_widths(M)`` that holds the fullest member,
drains them there and puts the per-slot results back. Every output must
equal the full-pool pass bit for bit on CPU, whatever the live counts,
wherever the live slots sit, whatever the inactive slots still hold.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

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
    want = ref.drain_tick_ref(routes, rem, active, job, mina, t, DT, bw,
                              ldr, A, R)
    *got, rung = ops.drain_tick_live(routes, rem, active, job, mina, t, DT,
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
    want = ref.drain_tick_ref(routes, rem, active, job, mina, t, DT, bw,
                              ldr, A, R)
    *got, rung = ops.drain_tick_live(routes, rem, active, job, mina, t, DT,
                                     bw, ldr, n_apps=A, n_routers=R)
    assert int(rung) == 0
    _assert_same(got, want)


def test_live_slots_at_the_back_of_the_pool():
    """Live slots packed at the pool's end, one member empty."""
    routes, rem, active, job, mina, t, ldr, rng = _pool([0, 0], seed=3)
    active[0, -300:] = True
    bw = _bandwidth(rng, 2, True)
    want = ref.drain_tick_ref(routes, rem, active, job, mina, t, DT, bw,
                              ldr, A, R)
    *got, rung = ops.drain_tick_live(routes, rem, active, job, mina, t, DT,
                                     bw, ldr, n_apps=A, n_routers=R)
    assert WIDTHS[int(rung)] == 512
    _assert_same(got, want)


def test_live_drain_through_the_pallas_kernel():
    """``use_pallas=True`` (interpret mode) at a packed width: the kernel
    pads the rung to its block and agrees with the full-pool kernel."""
    Mp = 1024
    routes, rem, active, job, mina, t, ldr, rng = _pool(
        [300, 40], seed=5, M=Mp, K=10)
    bw = _bandwidth(rng, 2, True)
    want = ops.drain_tick(routes, rem, active, job, mina, t, DT, bw, ldr,
                          n_apps=A, n_routers=R, use_pallas=True)
    *got, rung = ops.drain_tick_live(routes, rem, active, job, mina, t, DT,
                                     bw, ldr, n_apps=A, n_routers=R,
                                     use_pallas=True)
    assert ops.drain_widths(Mp)[int(rung)] == 512
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), rtol=1e-6)
    assert jnp.any(got[2]) or jnp.any(got[0] < rem)
