"""Pallas TPU kernel: Mamba-2 SSD chunk scan (one head per grid row).

Grid: (B*NH, nc) with `nc` iterated sequentially (state carried in a VMEM
scratch accumulator across chunk steps — the TPU grid is minor-to-major
sequential, the standard Pallas carry idiom). Per step the block computes

    y = (C Bᵀ ∘ L) (x·dt)  +  (C h) ∘ exp(cs)       (intra + inter chunk)
    h ← h·exp(cs[-1]) + Bᵀ ((x·dt) ∘ exp(cs[-1]-cs))

with Q×Q and ds×hd matmuls on the MXU. Block shapes: x (Q, hd), B/C
(Q, ds), dt (Q,) — with Q=128, hd=64, ds=128 the working set is
~0.4 MiB « VMEM. B/C blocks are shared across heads (index_map drops the
head coordinate).

Oracle: :func:`ssd_chunk_ref`; :func:`ssd_scan` runs either. The model's
jnp path (models/mamba2.py) is the production fallback on non-TPU
backends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_scr):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0]  # (Q, hd)
    dt = dt_ref[0, 0]  # (Q,)
    A = a_ref[0]  # scalar (negative)
    Bm = b_ref[0, 0]  # (Q, ds)
    Cm = c_ref[0, 0]  # (Q, ds)
    Q = x.shape[0]

    dA = dt * A
    cs = jnp.cumsum(dA)
    seg = jnp.exp(cs[-1])
    tri = jnp.tril(jnp.ones((Q, Q), jnp.float32))
    L = jnp.exp(cs[:, None] - cs[None, :]) * tri
    CB = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)
    xdt = x * dt[:, None]
    y_intra = jnp.dot(CB * L, xdt, preferred_element_type=jnp.float32)
    h = h_scr[...]
    y_inter = jnp.dot(Cm, h, preferred_element_type=jnp.float32) * jnp.exp(cs)[:, None]
    y_ref[0, 0] = y_intra + y_inter
    decay_out = jnp.exp(cs[-1] - cs)[:, None]
    h_scr[...] = h * seg + jnp.dot(Bm.T, xdt * decay_out,
                                   preferred_element_type=jnp.float32)
    hout_ref[0] = h_scr[...]


def ssd_scan_pallas(x, dt, A, Bm, Cm, *, interpret: bool = True):
    """x: (BH, nc, Q, hd) f32; dt: (BH, nc, Q); A: (BH,);
    Bm/Cm: (BH, nc, Q, ds) — returns (y (BH, nc, Q, hd), h (BH, ds, hd)).

    BH = batch × heads (head-major flattening done by the caller; B/C may
    be broadcast across heads by the caller or passed per-BH here).
    """
    BH, nc, Q, hd = x.shape
    ds = Bm.shape[-1]
    grid = (BH, nc)

    y, h = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, hd), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1,), lambda b, c: (b,)),
            pl.BlockSpec((1, 1, Q, ds), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, ds), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, Q, hd), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, ds, hd), lambda b, c: (b, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((BH, nc, Q, hd), jnp.float32),
            jax.ShapeDtypeStruct((BH, ds, hd), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((ds, hd), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, Bm, Cm)
    return y, h


def ssd_chunk_ref(x, dt, A, Bm, Cm, h0):
    """Reference for one head's SSD over all chunks (sequential).

    x: (nc, Q, hd) f32 — pre-multiplied by nothing (raw inputs)
    dt: (nc, Q) f32, A: scalar (negative), Bm/Cm: (nc, Q, ds) f32
    h0: (ds, hd) initial state.
    Returns (y (nc, Q, hd), h_final (ds, hd)).
    """
    nc, Q, hd = x.shape
    ds = Bm.shape[-1]

    def chunk(h, inp):
        xc, dtc, Bc, Cc = inp
        dA = dtc * A  # (Q,)
        cs = jnp.cumsum(dA)
        seg = jnp.exp(cs[-1])
        L = jnp.where(
            jnp.tril(jnp.ones((Q, Q), bool)),
            jnp.exp(cs[:, None] - cs[None, :]),
            0.0,
        )
        CB = Cc @ Bc.T  # (Q, Q)
        xdt = xc * dtc[:, None]
        y_intra = (CB * L) @ xdt
        decay_in = jnp.exp(cs)[:, None]
        y_inter = (Cc @ h) * decay_in
        decay_out = jnp.exp(cs[-1] - cs)[:, None]
        h_new = h * seg + Bc.T @ (xdt * decay_out)
        return h_new, y_intra + y_inter

    h, y = jax.lax.scan(chunk, h0, (x, dt, Bm, Cm))
    return y, h


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, use_pallas: bool = False, interpret: bool = True):
    """Head-flattened SSD scan: see ssd_scan_pallas for shapes."""
    if not use_pallas:
        y, h = jax.vmap(ssd_chunk_ref)(
            x, dt, A, Bm, Cm,
            jnp.zeros((x.shape[0], Bm.shape[-1], x.shape[-1]), jnp.float32),
        )
        return y, h
    return ssd_scan_pallas(x, dt, A, Bm, Cm, interpret=interpret)
