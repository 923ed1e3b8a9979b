"""Injection over the candidates that fired (``engine.inject_fired``)
against the full-width pass it replaces.

The packed injection puts each member's fired candidates, in emission
order, into consecutive columns and routes and allocates them
``engine.inject_width`` columns a pass, as many passes as the fullest
member allocates. Every pool leaf, the allocated mask and the counts
must equal the full-width pass (every candidate routed, the masked ones
written nowhere) bit for bit on CPU, in one pass and in several, on
every fabric's router, under a dead link, with a pool too full to take
them all. The whole engine must run the same trajectory in passes as
with every candidate routed in one.
"""
import functools
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.netsim import engine as ENG
from repro.netsim.engine import (
    Cands, PoolState, _flat_set, inject_fired, inject_width, stack_members,
)
from repro.netsim.fabric import get_fabric, routing_tables
from repro.union import manager as MGR
from repro.union.scenario import Scenario, ScenarioJob, URDecl

HOP_US = 0.5
FABRICS = ("1d", "2d", "fat_tree", "torus")


def _full_width(pool, t, mask, c, route, hop_latency_us):
    """The injection as it ran before packing: every one of the ``n``
    candidates routed, the fired ones allocated in emission order."""
    B, n = mask.shape
    M = pool.active.shape[1]
    k = jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1
    n_emit = mask.sum(axis=1)
    can = (k < pool.free_top[:, None]) & mask
    slot_pos = jnp.clip(pool.free_top[:, None] - 1 - k, 0, M - 1)
    slot = jnp.take_along_axis(pool.free_stack, slot_pos, axis=1)
    slot = jnp.where(can, slot, M)
    routes, hops = route(c.src_node, c.dst_node, c.rand)
    row_idx = jnp.where(
        can, slot + (jnp.arange(B, dtype=jnp.int32) * M)[:, None], B * M)
    n_alloc = jnp.minimum(n_emit, pool.free_top)
    pool = pool._replace(
        active=_flat_set(pool.active, slot, True, valid=can),
        src_rank=_flat_set(pool.src_rank, slot, c.src_rank, valid=can),
        dst_rank=_flat_set(pool.dst_rank, slot, c.dst_rank, valid=can),
        job=_flat_set(pool.job, slot, c.app, valid=can),
        size=_flat_set(pool.size, slot, c.size, valid=can),
        bytes_rem=_flat_set(pool.bytes_rem, slot, c.size, valid=can),
        inject_t=_flat_set(pool.inject_t, slot, t[:, None], valid=can),
        min_arrive=_flat_set(
            pool.min_arrive, slot,
            t[:, None] + hops.astype(jnp.float32) * hop_latency_us,
            valid=can),
        routes=pool.routes.reshape(B * M, -1).at[row_idx.reshape(-1)].set(
            routes.reshape(B * n, -1), mode="drop").reshape(pool.routes.shape),
        free_top=pool.free_top - n_alloc,
        dropped=pool.dropped + (n_emit - n_alloc),
    )
    return pool, can


def _pool(rng, M, K, L, free):
    """A (B, M) pool with ``free[b]`` free slots, their ids shuffled on
    the free stack; every slot holds stale values, as a freed one does."""
    B = len(free)
    active = np.ones((B, M), bool)
    stack = rng.integers(0, M, (B, M)).astype(np.int32)
    for b, f in enumerate(free):
        ids = rng.permutation(M)[:f]
        active[b, ids] = False
        stack[b, :f] = ids
    return PoolState(
        active=jnp.asarray(active),
        src_rank=jnp.asarray(rng.integers(0, 50, (B, M)), jnp.int32),
        dst_rank=jnp.asarray(rng.integers(0, 50, (B, M)), jnp.int32),
        job=jnp.asarray(rng.integers(0, 4, (B, M)), jnp.int32),
        size=jnp.asarray(rng.uniform(1, 1e5, (B, M)), jnp.float32),
        bytes_rem=jnp.asarray(rng.uniform(0, 1e5, (B, M)), jnp.float32),
        inject_t=jnp.asarray(rng.uniform(0, 50, (B, M)), jnp.float32),
        min_arrive=jnp.asarray(rng.uniform(0, 50, (B, M)), jnp.float32),
        routes=jnp.asarray(rng.integers(-1, L, (B, M, K)), jnp.int32),
        free_stack=jnp.asarray(stack),
        free_top=jnp.asarray(free, jnp.int32),
        dropped=jnp.asarray(rng.integers(0, 3, B), jnp.int32),
    )


def _candidates(rng, n, counts, n_nodes):
    """``(B, n)`` candidate fields and a mask with ``counts[b]`` fired
    candidates at random positions of member ``b``."""
    B = len(counts)
    mask = np.zeros((B, n), bool)
    for b, c in enumerate(counts):
        mask[b, rng.choice(n, c, replace=False)] = True
    c = Cands(
        src_rank=jnp.asarray(rng.integers(0, 512, (B, n)), jnp.int32),
        dst_rank=jnp.asarray(rng.integers(0, 512, (B, n)), jnp.int32),
        src_node=jnp.asarray(rng.integers(0, n_nodes, (B, n)), jnp.int32),
        dst_node=jnp.asarray(rng.integers(0, n_nodes, (B, n)), jnp.int32),
        size=jnp.asarray(rng.uniform(8, 1e6, (B, n)), jnp.float32),
        app=jnp.asarray(rng.integers(0, 5, (B, n)), jnp.int32),
        rand=jnp.asarray(rng.integers(0, 2**32, (B, n), dtype=np.uint64),
                         jnp.uint32),
    )
    return jnp.asarray(mask), c


def _router(topo, demand, adaptive):
    """The engine's ``route`` over a flattened per-member demand."""
    T, route_fn = routing_tables(topo)
    B, L1 = demand.shape

    def route(src, dst, rand):
        W = src.shape[1]
        routes, hops = route_fn(
            T, src.reshape(-1), dst.reshape(-1),
            rand.reshape(-1).astype(jnp.int32) & 0x7FFFFFFF,
            demand.reshape(-1), adaptive,
            demand_offsets=jnp.repeat(jnp.arange(B, dtype=jnp.int32) * L1, W))
        return routes.reshape(B, W, -1), hops.reshape(B, W)

    return route


# (candidates a member, pool slots, fired per member, free slots per
# member): members with different fired counts in every case; passes of
# 1,024 columns but where the pool is narrower
CASES = {
    "nothing": (4096, 4096, [0, 0], [4096, 4096]),
    "one-pass": (4096, 4096, [200, 7], [4000, 4096]),
    "one-pass-full": (4096, 4096, [1024, 1024], [4096, 3000]),
    "two-passes": (4096, 4096, [1025, 900], [4096, 4096]),
    "two-passes-one-member": (4096, 4096, [1, 2000], [4096, 4096]),
    "every-candidate": (4096, 4096, [4096, 4096], [4096, 4096]),
    "nearly-full-pool": (4096, 4096, [900, 600], [700, 13]),
    # as many passes as the fullest member allocates, not fires
    "passes-full-pool": (4096, 4096, [3000, 1500], [2100, 4096]),
    # candidates past the pool find no slot
    "past-the-pool": (8192, 4096, [6000, 300], [4096, 3900]),
    "many-passes": (16384, 16384, [9000, 5000], [16384, 8500]),
    # a UR source of 1,024 ranks, every one firing
    "ur-every-rank": (1024, 4096, [1024, 1024], [4096, 1500]),
    # a pool narrower than a pass: one pass of the pool
    "narrow-pool": (4096, 512, [700, 40], [512, 300]),
}
# every case on the 1-D dragonfly's adaptive router; two cases on each
# other adaptive router; minimal routing on every fabric
GRID = ([("1d", case, True) for case in CASES]
        + [(f, case, True) for f in FABRICS[1:]
           for case in ("two-passes", "nearly-full-pool")]
        + [(f, "two-passes", False) for f in FABRICS])


def _run(fabric, case, adaptive):
    n, M, counts, free = CASES[case]
    topo = get_fabric(fabric, "small")
    L = topo.n_links
    rng = np.random.default_rng(
        zlib.crc32(f"{fabric}/{case}/{adaptive}".encode()))
    pool = _pool(rng, M, topo.route_width, L, free)
    mask, c = _candidates(rng, n, counts, topo.n_nodes)
    demand = rng.uniform(0, 1e6, (2, L + 1)).astype(np.float32)
    demand[:, L] = 0.0
    # the second member's busiest links are dead: infinite demand, as the
    # engine gives a failed link, steers the adaptive routes
    demand[1, np.argsort(demand[1, :L])[-8:]] = 1e18
    route = _router(topo, jnp.asarray(demand), adaptive)
    t = jnp.asarray([3.0, 17.5], jnp.float32)
    W = inject_width(n, M)

    def cands(idx):
        at = jnp.minimum(idx, n - 1)
        return Cands(*(jnp.take_along_axis(x, at, axis=1) for x in c))

    got, got_can, passes = jax.jit(functools.partial(
        inject_fired, W=W, cands=cands, route=route,
        hop_latency_us=HOP_US))(pool, t, mask)
    want, want_can = jax.jit(functools.partial(
        _full_width, c=c, route=route, hop_latency_us=HOP_US))(pool, t, mask)
    return W, passes, (got, got_can), (want, want_can), counts, free


@pytest.mark.parametrize("fabric,case,adaptive", GRID)
def test_packed_equals_full_width(fabric, case, adaptive):
    W, passes, got, want, counts, free = _run(fabric, case, adaptive)
    alloc = np.minimum(counts, free)
    assert int(passes) == -(-alloc.max() // W)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)
    pool, can = got
    np.testing.assert_array_equal(np.asarray(can).sum(axis=1), alloc)
    np.testing.assert_array_equal(
        np.asarray(pool.free_top), np.asarray(free) - alloc)


def test_inject_width():
    # cell 1: 4 jobs x 2,048 ranks x 8 and 4,096 UR ranks; cell 2: 5 x
    # 4,096 x 8; both over a 65,536-slot pool
    assert inject_width(65536 + 4096, 65536) == 1024
    assert inject_width(163840, 65536) == 1024
    assert inject_width(8192, 512) == 512
    assert inject_width(128, 512) == 128


BURSTS = (
    "For 2 repetitions {\n"
    " all tasks exchange a 2048 byte message with their neighbors"
    " in a 6x6x6 grid then\n"
    " all tasks compute for 20 microseconds then\n"
    " all tasks exchange a 1024 byte message with their neighbors"
    " in a 36x6 grid then\n"
    " all tasks allreduce a 512 byte message then\n"
    " all tasks compute for 30 microseconds }"
)


def bursty_scenario(pool_size=4096):
    """216 ranks whose bursts fire 1,296, 864 and up to 216 candidates a
    tick, a job of 8 and a UR source of 64, in an envelope of 2 x 256
    ranks: 4,096 job candidates a member and 64 UR, so the bursts take
    one, two or more passes."""
    return Scenario(
        name="inject-rungs",
        jobs=[ScenarioJob(app="grid216", source=BURSTS, ranks=216),
              ScenarioJob(app="ar8", source=(
                  "For 3 repetitions {\n"
                  " all tasks allreduce a 65536 byte message then\n"
                  " all tasks compute for 40 microseconds }"), ranks=8)],
        placement="RN", routing="ADP",
        ur=URDecl(ranks=64, size_bytes=8192.0, interval_us=150.0),
        tick_us=2.0, horizon_ms=0.5, pool_size=pool_size,
        reserve={"jobs": 2, "ranks": 256, "ops": 16},
    )


def uncached_engine(rs):
    """``rs``'s engine built anew, as ``engine.INJECT_WIDTH`` stands now
    (the process-wide cache does not key on it)."""
    return MGR.bind_jobs(ENG.build_engine(
        rs.topo, [], routing=rs.scenario.routing, ur=rs.ur, net=rs.net,
        pool_size=rs.pool_size, horizon_us=rs.horizon_us,
        capacity=rs.capacity), rs)


@pytest.mark.parametrize("pool_size,width", [
    (4096, 1024), (1024, 1024), (4096, 256)])
def test_engine_in_passes_equals_one_pass(monkeypatch, pool_size, width):
    """Two members, the second's big job arriving later, run the whole
    engine in passes of ``width`` and with every candidate routed in one
    pass: every leaf but the pass count is the same. Some ticks take
    several passes; a 1,024-slot pool drops part of each big burst."""
    monkeypatch.setattr(ENG, "INJECT_WIDTH", width)
    rs = MGR.resolve(bursty_scenario(pool_size), seed=3)

    def final(eng):
        return jax.device_get(eng.run(stack_members([
            eng.init_state(seed=1),
            eng.init_state(seed=2, start_us=[90.0, 0.0])])))

    passes = final(uncached_engine(rs))
    monkeypatch.setattr(ENG, "inject_width", lambda n, M: min(n, M))
    one = final(uncached_engine(rs))
    assert (one.metrics.inject_passes <= one.metrics.inject_ticks).all()
    # below the pool's width, the big bursts take several passes
    several = passes.metrics.inject_passes > passes.metrics.inject_ticks
    assert several.all() if width < pool_size else not several.any()
    dropped = passes.pool.dropped
    assert (dropped > 0).all() if pool_size == 1024 else (dropped == 0).all()
    flat, _ = jax.tree_util.tree_flatten_with_path(passes)
    other = dict(jax.tree_util.tree_flatten_with_path(one)[0])
    for path, leaf in flat:
        if jax.tree_util.keystr(path) != ".metrics.inject_passes":
            np.testing.assert_array_equal(leaf, other[path],
                                          err_msg=jax.tree_util.keystr(path))
