"""jit'd public wrappers for the Pallas kernels.

``use_pallas`` selects the kernel path (interpret mode on CPU). The
engine uses the pure-jnp drain on every backend — Mosaic cannot lower
the drain kernel (``kernels/drain_tick.py``) — and these wrappers are
exercised by the per-kernel allclose sweeps in tests/.

The engine drains through :func:`drain_tick_live`: each tick it packs
the pool's live slots, in slot order, into the smallest width of a
ladder (:func:`drain_widths`: M, M/2, ..., M/16) that holds every
member's live slots, runs :func:`drain_tick` there, and puts the
per-slot results back. The top rung, M, is the full-pool pass itself.
The engine's injection packs the candidates that fired the same way
(:func:`pack_columns`).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.drain_tick import drain_tick_pallas
from repro.kernels.drain_tick import BLOCK_M as DRAIN_BLOCK_M
from repro.kernels.router_tick import BLOCK_M, router_rate_drain_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def router_rate_drain(routes, bytes_rem, active, share, dt,
                      use_pallas: bool = False, interpret: bool = True):
    if not use_pallas:
        return ref.router_rate_drain_ref(routes, bytes_rem, active, share, dt)
    M = routes.shape[0]
    pad = (-M) % BLOCK_M
    if pad:
        routes = jnp.pad(routes, ((0, pad), (0, 0)), constant_values=-1)
        bytes_rem = jnp.pad(bytes_rem, (0, pad))
        active = jnp.pad(active, (0, pad))
    new_rem, rate, drained = router_rate_drain_pallas(
        routes, bytes_rem, active, share, dt, interpret=interpret
    )
    return new_rem[:M], rate[:M], drained[:M]


@functools.partial(
    jax.jit,
    static_argnames=("n_apps", "n_routers", "use_pallas", "interpret"),
)
def drain_tick(routes, bytes_rem, active, job, min_arrive, t, dt, bw_eff,
               link_dst_router, *, n_apps: int, n_routers: int,
               use_pallas: bool = False, interpret: bool = True):
    """Fused drain tick (engine steps 2-3) over an explicit member batch.

    See `ref.drain_tick_ref` for shapes/semantics — ``bw_eff`` is
    ``(L+1,)`` or per-member ``(B, L+1)`` (runtime fault factors). The
    jnp path is the engine's drain on every backend: its scatters fold
    the member index into one flat index, which is what fixes the
    vmapped-campaign regression.
    """
    if not use_pallas:
        return ref.drain_tick_ref(
            routes, bytes_rem, active, job, min_arrive, t, dt, bw_eff,
            link_dst_router, n_apps, n_routers,
        )
    B, M, K = routes.shape
    if bw_eff.ndim == 1:
        bw_eff = jnp.broadcast_to(bw_eff, (B, bw_eff.shape[0]))
    pad = (-M) % DRAIN_BLOCK_M
    if pad:
        routes = jnp.pad(routes, ((0, 0), (0, pad), (0, 0)), constant_values=-1)
        bytes_rem = jnp.pad(bytes_rem, ((0, 0), (0, pad)))
        active = jnp.pad(active, ((0, 0), (0, pad)))
        job = jnp.pad(job, ((0, 0), (0, pad)))
        min_arrive = jnp.pad(min_arrive, ((0, 0), (0, pad)))
    new_rem, rate, delivered, lb, rw = drain_tick_pallas(
        routes, bytes_rem, active, job, min_arrive, t, dt, bw_eff,
        link_dst_router, n_apps, n_routers, interpret=interpret,
    )
    return new_rem[:, :M], rate[:, :M], delivered[:, :M], lb, rw


# the drain's ladder of widths: the pool size, then halvings down to a
# sixteenth, none narrower than DRAIN_MIN_WIDTH slots (M always stays)
DRAIN_RUNGS = 5
DRAIN_MIN_WIDTH = 256


def drain_widths(M: int) -> Tuple[int, ...]:
    """Drain widths for a pool of ``M`` slots, widest first."""
    return (M,) + tuple(M >> i for i in range(1, DRAIN_RUNGS)
                        if M >> i >= DRAIN_MIN_WIDTH)


def pack_columns(mask, col, W: int):
    """``(B, W)`` int32: the position ``i`` of each of member ``b``'s set
    ``mask`` entries at column ``col[b, i]``, where that is below ``W``;
    ``n`` (past the end of the ``(B, n)`` mask) in every other column.
    ``col`` is ``cumsum(mask) - 1``, so the set entries keep their order
    and fill the first columns."""
    B, n = mask.shape
    to = jnp.where(mask & (col < W),
                   jnp.arange(B, dtype=jnp.int32)[:, None] * W + col, B * W)
    return (jnp.full((B * W,), n, jnp.int32)
            .at[to.reshape(-1)]
            .set(jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32),
                                  (B, n)).reshape(-1), mode="drop")
            .reshape(B, W))


@functools.partial(
    jax.jit,
    static_argnames=("n_apps", "n_routers", "use_pallas", "interpret"),
)
def drain_tick_live(routes, bytes_rem, active, job, min_arrive, t, dt,
                    bw_eff, link_dst_router, *, n_apps: int, n_routers: int,
                    use_pallas: bool = False, interpret: bool = True):
    """:func:`drain_tick` over the live pool slots only.

    Picks the smallest width ``W`` of ``drain_widths(M)`` that holds the
    most live slots of any member, gathers each member's live slots in
    ascending slot order into ``(B, W)`` (padding slots inactive), drains
    them, and scatters ``new_rem``, ``rate`` and ``delivered`` back to
    ``(B, M)``; every other slot keeps its ``bytes_rem``, rate 0 and is
    not delivered. The link and router deltas keep their full shapes.
    Every real link sees the same valid entries in the same order, and
    the dropped entries added 0 to the dummy link, so the outputs equal
    the full-pool pass (bit for bit where scatter-adds run in index
    order, as on CPU) for any pool whose ``bytes_rem`` are >= 0.

    Returns ``drain_tick``'s five outputs and the rung taken, an int32
    index into ``drain_widths(M)``. The top rung is ``drain_tick`` on
    the whole pool, with nothing gathered or scattered around it.
    """
    B, M, K = routes.shape
    widths = drain_widths(M)
    drain = functools.partial(
        drain_tick, t=t, dt=dt, bw_eff=bw_eff,
        link_dst_router=link_dst_router, n_apps=n_apps,
        n_routers=n_routers, use_pallas=use_pallas, interpret=interpret)

    def packed(W):
        # live slot m of member b goes to column cumsum(active)[b, m] - 1
        # (below W: the rung holds every member's live slots); columns
        # past a member's live count hold the index M (no slot)
        col = jnp.cumsum(active, axis=1, dtype=jnp.int32) - 1
        slot = pack_columns(active, col, W)
        live = slot < M
        at = jnp.minimum(slot, M - 1)

        def rows(x):
            ix = at.reshape(at.shape + (1,) * (x.ndim - 2))
            return jnp.take_along_axis(x, ix, axis=1)

        new_rem, rate, delivered, lb, rw = drain(
            rows(routes), rows(bytes_rem), live, rows(job), rows(min_arrive))
        back = jnp.where(
            live, jnp.arange(B, dtype=jnp.int32)[:, None] * M + slot,
            B * M).reshape(-1)

        def put(base, x):
            return (base.reshape(-1).at[back].set(x.reshape(-1), mode="drop")
                    .reshape(B, M))

        return (put(bytes_rem, new_rem), put(jnp.zeros_like(bytes_rem), rate),
                put(jnp.zeros_like(active), delivered), lb, rw)

    n_live = jnp.max(jnp.sum(active, axis=1, dtype=jnp.int32))
    rung = jnp.sum(n_live <= jnp.asarray(widths[1:], jnp.int32),
                   dtype=jnp.int32)
    branches = [lambda _: drain(routes, bytes_rem, active, job, min_arrive)]
    branches += [(lambda _, W=W: packed(W)) for W in widths[1:]]
    return jax.lax.switch(rung, branches, None) + (rung,)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, use_pallas: bool = False, interpret: bool = True):
    """Head-flattened SSD scan: see ssd_scan_pallas for shapes."""
    if not use_pallas:
        y, h = jax.vmap(ref.ssd_chunk_ref)(
            x, dt, A, Bm, Cm,
            jnp.zeros((x.shape[0], Bm.shape[-1], x.shape[-1]), jnp.float32),
        )
        return y, h
    return ssd_scan_pallas(x, dt, A, Bm, Cm, interpret=interpret)
