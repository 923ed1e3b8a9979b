"""Pallas TPU kernel: the fused drain tick (engine steps 2-3).

One kernel per tick replaces the engine's five scatter/gather passes —
link-demand count, fair-share gather+row-min, per-message drain, per-link
byte accounting, and the delivery mask — with an **explicit member batch
dimension** so ensemble campaigns drain every member in one launch
instead of a serialized batch of scatters (the vmap regression
BENCH_union.json documented).

Layout:

* grid = (B, 2, nb): members outer, then a two-phase sweep over message
  blocks. Phase 0 accumulates the per-link message count into a VMEM
  scratch table; phase 1 turns it into the fair-share rate table once,
  then drains every block against it. TPU grids iterate sequentially, so
  the scratch table carries state across phases of one member.
* the share/count tables stay resident in VMEM across the whole sweep
  (links ≤ ~74k × 4 B ≈ 296 KiB for the paper's 2-D dragonfly — far
  under the ~16 MiB VMEM budget); route width K=10 is a static lane dim.
* per-link scatters inside the kernel use the accumulate pattern
  (`ref[...] = ref[...] + zeros.at[idx].add(v)`).

**The kernel does not lower for a TPU.** Under jax 0.9.0, Mosaic refuses
those in-kernel scatters: compiling it for a v5e raises
``NotImplementedError: Unimplemented primitive in Pallas TPU lowering
for KernelType.TC: scatter-add`` (pinned by tests/test_tpu_compile.py).
So the engine's drain tick is the jnp path (`ref.drain_tick_ref`) on
every backend; the kernel is an explicit opt-in (``use_pallas=True``)
and runs only in interpret mode, on CPU, where
tests/test_drain_kernel.py validates it against `ref.drain_tick_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_M = 256


def _make_kernel(n_apps: int, n_routers: int, Lp: int):
    def _kernel(routes_ref, rem_ref, act_ref, job_ref, mina_ref, t_ref,
                dt_ref, bw_ref, ldr_ref,
                out_rem_ref, out_rate_ref, out_del_ref, out_lb_ref,
                out_rw_ref, nl_ref, share_ref):
        phase = pl.program_id(1)
        mb = pl.program_id(2)
        routes = routes_ref[0]  # (BLOCK_M, K) int32
        act = act_ref[0] > 0  # (BLOCK_M,)
        valid = (routes >= 0) & act[:, None]
        lidx = jnp.where(valid, routes, Lp - 1)

        @pl.when((phase == 0) & (mb == 0))
        def _init():
            nl_ref[...] = jnp.zeros_like(nl_ref)
            out_lb_ref[...] = jnp.zeros_like(out_lb_ref)
            out_rw_ref[...] = jnp.zeros_like(out_rw_ref)

        @pl.when(phase == 0)
        def _count():
            nl_ref[...] = nl_ref[...] + (
                jnp.zeros((Lp,), jnp.float32)
                .at[lidx.reshape(-1)]
                .add(valid.reshape(-1).astype(jnp.float32))
            )

        @pl.when((phase == 1) & (mb == 0))
        def _share():
            # bw_ref holds this member's (1, Lp) effective-bandwidth row
            # (runtime fault factors applied by the engine tick).
            share_ref[...] = (
                bw_ref[0] / jnp.maximum(nl_ref[...], 1.0) * 1e-6
            )

        @pl.when(phase == 1)
        def _drain():
            share = share_ref[...]
            rem = rem_ref[0]
            per_link = jnp.where(valid, share[lidx], jnp.inf)
            rate = jnp.min(per_link, axis=1)
            rate = jnp.where(act & jnp.isfinite(rate), rate, 0.0)
            drain = jnp.minimum(rate * dt_ref[0], rem)
            new_rem = rem - drain
            out_rem_ref[0] = new_rem
            out_rate_ref[0] = rate
            out_del_ref[0] = (
                act & (new_rem <= 1e-6) & (t_ref[0] >= mina_ref[0])
            ).astype(jnp.int8)

            drain_b = jnp.where(valid, drain[:, None], 0.0)
            out_lb_ref[0] = out_lb_ref[0] + (
                jnp.zeros((Lp,), jnp.float32)
                .at[lidx.reshape(-1)]
                .add(drain_b.reshape(-1))
            )
            rtr = ldr_ref[...][lidx]  # (BLOCK_M, K)
            rw_idx = job_ref[0][:, None] * n_routers + rtr
            out_rw_ref[0] = out_rw_ref[0] + (
                jnp.zeros((n_apps * n_routers,), jnp.float32)
                .at[rw_idx.reshape(-1)]
                .add(drain_b.reshape(-1))
            )

    return _kernel


def drain_tick_pallas(routes, bytes_rem, active, job, min_arrive, t, dt,
                      bw_eff, link_dst_router, n_apps, n_routers,
                      *, interpret: bool = True):
    """routes (B,M,K) int32, bytes_rem/min_arrive (B,M) f32, active (B,M)
    bool, job (B,M) int32, t (B,) f32, dt scalar, bw_eff (B, L+1) f32
    per-member effective bandwidth (runtime fault factors),
    link_dst_router (L+1,) -> (new_rem, rate, delivered,
    link_bytes_delta (B, L+1), router_win_delta (B, n_apps, R))."""
    B, M, K = routes.shape
    Lp = bw_eff.shape[-1]
    assert bw_eff.shape == (B, Lp), "bw_eff must carry the member dim"
    assert M % BLOCK_M == 0, f"pool size {M} must be a multiple of {BLOCK_M}"
    nb = M // BLOCK_M
    act8 = active.astype(jnp.int8)
    dt_arr = jnp.asarray([dt], jnp.float32)

    out_shapes = (
        jax.ShapeDtypeStruct((B, M), jnp.float32),  # new_rem
        jax.ShapeDtypeStruct((B, M), jnp.float32),  # rate
        jax.ShapeDtypeStruct((B, M), jnp.int8),  # delivered
        jax.ShapeDtypeStruct((B, Lp), jnp.float32),  # link_bytes_delta
        jax.ShapeDtypeStruct((B, n_apps * n_routers), jnp.float32),
    )
    msg_spec = pl.BlockSpec((1, BLOCK_M), lambda b, p, m: (b, m))
    new_rem, rate, delivered, lb, rw = pl.pallas_call(
        _make_kernel(n_apps, n_routers, Lp),
        grid=(B, 2, nb),
        in_specs=[
            pl.BlockSpec((1, BLOCK_M, K), lambda b, p, m: (b, m, 0)),
            msg_spec,  # bytes_rem
            msg_spec,  # active
            msg_spec,  # job
            msg_spec,  # min_arrive
            pl.BlockSpec((1,), lambda b, p, m: (b,)),  # t
            pl.BlockSpec((1,), lambda b, p, m: (0,)),  # dt
            pl.BlockSpec((1, Lp), lambda b, p, m: (b, 0)),  # bw_eff rows
            pl.BlockSpec((Lp,), lambda b, p, m: (0,)),  # link_dst_router
        ],
        out_specs=(
            msg_spec, msg_spec, msg_spec,
            pl.BlockSpec((1, Lp), lambda b, p, m: (b, 0)),
            pl.BlockSpec((1, n_apps * n_routers), lambda b, p, m: (b, 0)),
        ),
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((Lp,), jnp.float32),  # n_l counts
            pltpu.VMEM((Lp,), jnp.float32),  # share table
        ],
        interpret=interpret,
    )(routes, bytes_rem, act8, job, min_arrive, t, dt_arr, bw_eff,
      link_dst_router)
    return (
        new_rem, rate, delivered.astype(bool), lb,
        rw.reshape(B, n_apps, n_routers),
    )
