"""jit'd public wrappers for the Pallas kernels.

``use_pallas`` selects the kernel path (interpret mode on CPU). The
engine uses the pure-jnp drain on every backend — Mosaic cannot lower
the drain kernel (``kernels/drain_tick.py``) — and these wrappers are
exercised by the per-kernel allclose sweeps in tests/.
"""
from __future__ import annotations

import functools

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
