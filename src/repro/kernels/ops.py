"""The engine's drain tick (engine steps 2-3), in jnp, on every backend.

:func:`drain_tick` is the fluid fair-share drain over an explicit member
batch: link demand (messages per link) -> fair-share rate -> per-message
drain -> delivery mask, plus the per-link and per-(app, router) byte
counters the paper's router windows need.

The engine drains through :func:`drain_tick_live`: each tick it packs
the pool's live slots, in slot order, into the smallest width of a
ladder (:func:`drain_widths`: M, M/2, ..., M/16) that holds every
member's live slots, runs :func:`drain_tick` there, and puts the
per-slot results back. The top rung, M, is the full-pool pass itself.
Work on the delivered slots (the engine's latency pass) runs in the
same branch, at the same width (``then``).
The engine's injection packs the candidates that fired the same way
(:func:`pack_columns`).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n_apps", "n_routers"))
def drain_tick(routes, bytes_rem, active, job, min_arrive, t, dt, bw_eff,
               link_dst_router, *, n_apps: int, n_routers: int):
    """One drain tick over every member x message of a batch.

    All scatters fold the member index into a single flat index, so XLA
    emits one scatter for the batch, not a serialized scatter a member.

    routes: (B, M, K) int32 link ids (-1 pad); bytes_rem: (B, M) f32;
    active: (B, M) bool; job: (B, M) int32 app ids (< n_apps);
    min_arrive: (B, M) f32; t: (B,) f32; dt: scalar f32;
    bw_eff: (L+1,) or (B, L+1) f32 effective per-link bandwidth (0 for
    failed links, dummy last) — per-**member** rows let one compiled
    engine drain an ensemble of different failure patterns
    (repro.netsim.faults); a 1-D vector broadcasts to every member;
    link_dst_router: (L+1,) int32 destination router per link (dummy last).

    A message's rate is the least fair share (bandwidth ÷ messages, in
    bytes/us) over its route's links, 0 for an inactive or routeless
    message; it drains ``min(rate * dt, bytes_rem)`` and is delivered
    once active, with at most 1e-6 bytes left, at ``t >= min_arrive``.

    Returns (new_rem (B,M), rate (B,M), delivered (B,M) bool,
             link_bytes_delta (B, L+1), router_win_delta (B, n_apps, R)).
    """
    B, M, K = routes.shape
    Lp = bw_eff.shape[-1]
    valid = (routes >= 0) & active[:, :, None]
    lidx = jnp.where(valid, routes, Lp - 1)
    boff = (jnp.arange(B, dtype=jnp.int32) * Lp)[:, None, None]
    flat = (lidx + boff).reshape(-1)

    n_l = (
        jnp.zeros((B * Lp,), jnp.float32)
        .at[flat].add(valid.reshape(-1).astype(jnp.float32))
    )
    bw2 = jnp.broadcast_to(bw_eff, (B, Lp))
    share = bw2 / jnp.maximum(n_l.reshape(B, Lp), 1.0) * 1e-6
    per_link = jnp.where(valid, share.reshape(-1)[flat].reshape(B, M, K), jnp.inf)
    rate = jnp.min(per_link, axis=2)
    rate = jnp.where(active & jnp.isfinite(rate), rate, 0.0)
    drain = jnp.minimum(rate * dt, bytes_rem)
    new_rem = bytes_rem - drain

    drain_b = jnp.where(valid, drain[:, :, None], 0.0)
    link_bytes_delta = (
        jnp.zeros((B * Lp,), jnp.float32)
        .at[flat].add(drain_b.reshape(-1))
        .reshape(B, Lp)
    )
    rtr = link_dst_router[lidx]  # (B, M, K)
    appidx = jnp.broadcast_to(job[:, :, None], lidx.shape)
    rw_flat = (
        appidx * n_routers + rtr
        + (jnp.arange(B, dtype=jnp.int32) * n_apps * n_routers)[:, None, None]
    )
    router_win_delta = (
        jnp.zeros((B * n_apps * n_routers,), jnp.float32)
        .at[rw_flat.reshape(-1)].add(drain_b.reshape(-1))
        .reshape(B, n_apps, n_routers)
    )
    delivered = active & (new_rem <= 1e-6) & (t[:, None] >= min_arrive)
    return new_rem, rate, delivered, link_bytes_delta, router_win_delta


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


@functools.partial(jax.jit, static_argnames=("n_apps", "n_routers", "then"))
def drain_tick_live(routes, bytes_rem, active, job, min_arrive, t, dt,
                    bw_eff, link_dst_router, *, n_apps: int, n_routers: int,
                    then=None):
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

    ``then(take, delivered)``, where given, runs in the same branch on
    the packed slots: ``take(x)`` gives a ``(B, M, ...)`` array at the
    rung's ``(B, W)`` columns, and ``delivered`` is the drain's
    ``(B, W)`` delivery mask there (padding columns not delivered). Work
    that only reads the delivered slots thus runs at the rung's width,
    on the same slots in the same order as over the whole pool.
    ``then`` is static: a closure over the caller's traced values, traced
    once inside the caller's jit. This function stays a call of its own
    there: inlined into the engine's loop, the v5e compiler moved the
    VMs' op tables out of VMEM and the emission's gathers from them
    took ~0.4 ms more a tick (workload1, 1-D dragonfly at paper size).

    Returns ``drain_tick``'s five outputs, ``then``'s result (None
    without ``then``) and the rung taken, an int32 index into
    ``drain_widths(M)``. The top rung is ``drain_tick`` and ``then`` on
    the whole pool, with nothing gathered or scattered around them.
    """
    B, M, K = routes.shape
    widths = drain_widths(M)
    drain = functools.partial(
        drain_tick, t=t, dt=dt, bw_eff=bw_eff,
        link_dst_router=link_dst_router, n_apps=n_apps,
        n_routers=n_routers)
    then = then or (lambda take, delivered: None)

    def whole(_):
        out = drain(routes, bytes_rem, active, job, min_arrive)
        return out + (then(lambda x: x, out[2]),)

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
                put(jnp.zeros_like(active), delivered), lb, rw,
                then(rows, delivered))

    n_live = jnp.max(jnp.sum(active, axis=1, dtype=jnp.int32))
    rung = jnp.sum(n_live <= jnp.asarray(widths[1:], jnp.int32),
                   dtype=jnp.int32)
    branches = [whole] + [(lambda _, W=W: packed(W)) for W in widths[1:]]
    return jax.lax.switch(rung, branches, None) + (rung,)
