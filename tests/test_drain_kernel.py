"""The drain tick (``kernels.ops.drain_tick``) against a numpy loop.

The drain tick is the engine's per-tick hot loop (steps 2-3): link demand
-> fair-share rate -> per-message drain -> delivery mask + per-link byte
counters, with an explicit member batch dim. The oracle below walks the
same steps one message at a time, in float32 and in pool-slot order, as
the drain's scatter-adds sum on CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops


def _inputs(B, M, K, L, A, R, seed, frac=0.5):
    key = jax.random.PRNGKey(seed)
    routes = jax.random.randint(key, (B, M, K), -1, L)
    rem = jax.random.uniform(jax.random.fold_in(key, 1), (B, M)) * 1e5
    act = jax.random.bernoulli(jax.random.fold_in(key, 2), frac, (B, M))
    job = jax.random.randint(jax.random.fold_in(key, 3), (B, M), 0, A)
    mina = jax.random.uniform(jax.random.fold_in(key, 4), (B, M)) * 10.0
    t = jnp.linspace(4.0, 9.0, B)
    bw = jnp.concatenate([
        jax.random.uniform(jax.random.fold_in(key, 5), (L,)) * 1e3 + 1.0,
        jnp.ones((1,)),
    ])
    ldr = jnp.concatenate([
        jax.random.randint(jax.random.fold_in(key, 6), (L,), 0, R),
        jnp.zeros((1,), jnp.int32),
    ])
    return routes, rem, act, job, mina, t, bw, ldr


def _oracle(routes, rem, act, job, mina, t, dt, bw, ldr, A, R):
    """One drain tick, a message at a time."""
    routes, rem, act, job, mina, t, ldr = (
        np.asarray(x) for x in (routes, rem, act, job, mina, t, ldr))
    B, M, K = routes.shape
    Lp = np.shape(bw)[-1]
    bw = np.broadcast_to(np.asarray(bw, np.float32), (B, Lp))
    dt, eps = np.float32(dt), np.float32(1e-6)
    new_rem = rem.astype(np.float32)
    rate = np.zeros((B, M), np.float32)
    lb = np.zeros((B, Lp), np.float32)
    rw = np.zeros((B, A, R), np.float32)
    for b in range(B):
        count = np.zeros(Lp, np.float32)  # messages per link
        for m in range(M):
            for link in routes[b, m]:
                if act[b, m] and link >= 0:
                    count[link] += 1
        share = bw[b] / np.maximum(count, np.float32(1)) * eps
        for m in range(M):
            links = [link for link in routes[b, m] if link >= 0]
            if not act[b, m] or not links:
                continue
            rate[b, m] = min(share[link] for link in links)
            drain = min(rate[b, m] * dt, rem[b, m])
            new_rem[b, m] = rem[b, m] - drain
            for link in links:
                lb[b, link] += drain
                rw[b, job[b, m], ldr[link]] += drain
    delivered = act & (new_rem <= eps) & (t[:, None] >= mina)
    return new_rem, rate, delivered, lb, rw


def _bandwidth(bw, B, L, kind):
    """``shared``: one (L+1,) row for every member; ``per_member``: a row
    each, the first member's link 3 dead and the last's link 5 at a
    quarter; ``member_dead``: the last member's every link dead."""
    if kind == "shared":
        return bw
    rows = np.tile(np.asarray(bw), (B, 1))
    if kind == "per_member":
        rows[0, 3] = 0.0
        rows[-1, 5] *= np.float32(0.25)
    else:
        rows[-1, :L] = 0.0
    return jnp.asarray(rows)


@pytest.mark.parametrize("kind", ["shared", "per_member", "member_dead"])
@pytest.mark.parametrize("B,M,L,A,R", [
    (1, 256, 64, 2, 16),
    (3, 512, 300, 4, 24),
    (2, 300, 70, 3, 12),  # M not a power of two
])
def test_drain_matches_numpy_oracle(B, M, L, A, R, kind):
    routes, rem, act, job, mina, t, bw, ldr = _inputs(B, M, 10, L, A, R, M + L)
    # up to 100 bytes a message and 1e6-1e9 bytes/s a link: in one tick
    # some messages finish, some run out of bytes before their
    # min_arrive and the rest drain part of theirs
    rem, bw = rem * 1e-3, _bandwidth(bw * 1e6, B, L, kind)
    got = ops.drain_tick(routes, rem, act, job, mina, t, 2.0, bw, ldr,
                         n_apps=A, n_routers=R)
    want = _oracle(routes, rem, act, job, mina, t, 2.0, bw, ldr, A, R)
    for g, w in zip(got, want):
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)
    # every member moved bytes and delivered but one whose links are dead
    dead = kind == "member_dead"
    moved = np.asarray(got[3]).sum(axis=1)
    assert (moved[:-1] > 0).all() and (moved[-1] == 0) == dead
    assert np.asarray(got[2]).any(axis=1).tolist() == [True] * (B - dead) + [
        False] * dead


def test_drain_reference_invariants():
    """Fair share: a link carrying n messages gives each bw/n; a message
    drains at its bottleneck link; byte conservation holds per member."""
    routes = jnp.asarray([[[0, 1, -1], [0, 2, -1]]], jnp.int32)  # (1,2,3)
    rem = jnp.asarray([[100.0, 100.0]])
    act = jnp.ones((1, 2), bool)
    job = jnp.zeros((1, 2), jnp.int32)
    mina = jnp.zeros((1, 2))
    t = jnp.asarray([1.0])
    # bw 20/2/100 -> both messages share link 0 (10 each); msg0 bottleneck
    # is link 1 (2), msg1 bottleneck is link 0 (10)
    bw = jnp.asarray([20.0, 2.0, 100.0, 1.0]) * 1e6
    ldr = jnp.asarray([0, 1, 2, 0], jnp.int32)
    new_rem, rate, delivered, lb, rw = ops.drain_tick(
        routes, rem, act, job, mina, t, 1.0, bw, ldr, n_apps=1, n_routers=3)
    assert float(rate[0, 0]) == 2.0
    assert float(rate[0, 1]) == 10.0
    # link_bytes delta == total drained bytes, split per traversed link
    drained = float((rem - new_rem).sum())
    assert drained > 0
    np.testing.assert_allclose(float(lb.sum()), 2 * drained - 0, rtol=1e-6)
    np.testing.assert_allclose(float(rw.sum()), float(lb[0, :3].sum()), rtol=1e-6)


def test_drain_per_member_bandwidth():
    """Per-member effective bandwidth (the runtime fault masks): a
    (B, L+1) bw matrix — each member's own degraded fabric — matches
    running each member alone with its 1-D bw row; and a (B, L+1) matrix
    of identical rows matches the broadcast 1-D call bit-for-bit."""
    B, M, K, L, A, R = 3, 256, 10, 64, 2, 16
    routes, rem, act, job, mina, t, bw, ldr = _inputs(B, M, K, L, A, R, 11)
    key = jax.random.PRNGKey(99)
    factors = jnp.where(
        jax.random.bernoulli(key, 0.15, (B, L)), 0.0,
        jax.random.uniform(jax.random.fold_in(key, 1), (B, L)) * 0.9 + 0.1)
    bw_m = jnp.concatenate(
        [bw[None, :L] * factors, jnp.ones((B, 1))], axis=1)  # (B, L+1)

    full = ops.drain_tick(routes, rem, act, job, mina, t, 2.0, bw_m, ldr,
                          n_apps=A, n_routers=R)
    for b in range(B):
        solo = ops.drain_tick(
            routes[b:b + 1], rem[b:b + 1], act[b:b + 1], job[b:b + 1],
            mina[b:b + 1], t[b:b + 1], 2.0, bw_m[b], ldr,
            n_apps=A, n_routers=R)
        for x, y in zip(full, solo):
            np.testing.assert_array_equal(np.asarray(x[b]), np.asarray(y[0]))

    # identical rows == the healthy 1-D broadcast, bitwise
    tiled = jnp.broadcast_to(bw, (B, L + 1))
    a = ops.drain_tick(routes, rem, act, job, mina, t, 2.0, tiled, ldr,
                       n_apps=A, n_routers=R)
    c = ops.drain_tick(routes, rem, act, job, mina, t, 2.0, bw, ldr,
                       n_apps=A, n_routers=R)
    for x, y in zip(a, c):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_drain_member_batch_is_independent():
    """Member b of a batched call equals its own B=1 call (the flat-scatter
    batching must not couple members)."""
    routes, rem, act, job, mina, t, bw, ldr = _inputs(4, 256, 8, 40, 3, 10, 7)
    full = ops.drain_tick(routes, rem, act, job, mina, t, 3.0, bw, ldr,
                          n_apps=3, n_routers=10)
    for b in range(4):
        solo = ops.drain_tick(
            routes[b:b + 1], rem[b:b + 1], act[b:b + 1], job[b:b + 1],
            mina[b:b + 1], t[b:b + 1], 3.0, bw, ldr,
            n_apps=3, n_routers=10)
        for x, y in zip(full, solo):
            np.testing.assert_array_equal(np.asarray(x[b]), np.asarray(y[0]))
