"""Sim-plane probes: time-windowed ring buffers inside ``SimState``.

The host-plane tracer (:mod:`repro.obs.spans`) sees *where wall-clock
goes*; these probes see *what the simulated network is doing over
virtual time* — the time-resolved counters interference studies need
(per-level link utilization, per-app in-flight latency, pool occupancy,
queue depth), sampled every ``every`` live ticks into fixed-size ring
buffers that ride along as ordinary runtime data in the engine state.

Probing is a **static build-time choice** (:class:`ProbeConfig` is part
of the engine cache key): a probed engine is a separate compiled entry,
and the unprobed engine contains no probe code at all — its tick math is
byte-identical to the goldens. Within a probed engine the buffers are
just more pytree leaves, so batching, windowed scheduler runs, and
``vmap`` all work unchanged.

Sampling math mirrors the engine's own write discipline: every update is
gated member-wise by ``live_m`` (frozen batch members never advance the
engine's live-tick counter ``Metrics.ticks`` or touch their buffers), and
ring writes are one-hot ``where`` selects at ``idx % K`` — no
data-dependent shapes.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import numpy as np

import jax.numpy as jnp


@dataclass(frozen=True)
class ProbeConfig:
    """Static probe plan — hashable, part of the engine cache key.

    ``samples``: ring-buffer capacity K (oldest samples overwritten).
    ``every``: sampling period in *live* ticks (a frozen batch member's
    ordinal clock pauses with it, so its sample spacing is unaffected by
    batch-mates).
    """

    samples: int = 64
    every: int = 8

    def __post_init__(self):
        if self.samples < 1:
            raise ValueError(f"probes: samples must be >= 1, got {self.samples}")
        if self.every < 1:
            raise ValueError(f"probes: every must be >= 1, got {self.every}")


class ProbeState(NamedTuple):
    """Per-member probe buffers (leading ``B`` dim when batched).

    Ring buffers are written at ``idx % K``; ``idx`` counts samples ever
    taken (monotonic), so ``idx > K`` means the ring wrapped and
    :func:`ring_order` recovers chronological order.
    """

    t: jnp.ndarray            # (K,) f32 — virtual time of each sample (us)
    link_util: jnp.ndarray    # (K, n_levels) f32 — per-level utilization 0..1
    inflight_lat: jnp.ndarray  # (K, n_apps) f32 — mean in-flight age (us)
    queue_depth: jnp.ndarray  # (K, n_apps) int32 — in-flight msgs per app
    pool_occ: jnp.ndarray     # (K,) f32 — pool slot occupancy 0..1
    idx: jnp.ndarray          # () int32 — samples ever written (monotonic)
    last_level_bytes: jnp.ndarray  # (n_levels,) f32 — bytes at last sample
    last_t: jnp.ndarray       # () f32 — virtual time of last sample


def init_probes(cfg: ProbeConfig, n_levels: int, n_apps: int) -> ProbeState:
    """One member's empty probe buffers."""
    K = cfg.samples
    return ProbeState(
        t=jnp.full((K,), -1.0, jnp.float32),
        link_util=jnp.zeros((K, n_levels), jnp.float32),
        inflight_lat=jnp.zeros((K, n_apps), jnp.float32),
        queue_depth=jnp.zeros((K, n_apps), jnp.int32),
        pool_occ=jnp.zeros((K,), jnp.float32),
        idx=jnp.int32(0),
        last_level_bytes=jnp.zeros((n_levels,), jnp.float32),
        last_t=jnp.float32(0.0),
    )


def sample_probes(
    ps: ProbeState,
    cfg: ProbeConfig,
    *,
    t_new: jnp.ndarray,        # (B,) f32 — post-tick virtual time
    live_m: jnp.ndarray,       # (B,) bool — member freeze mask
    ticks: jnp.ndarray,        # (B,) int32 — live ticks, this one included
    link_bytes: jnp.ndarray,   # (B, L+1) f32 — cumulative per-link bytes
    pool_active: jnp.ndarray,  # (B, M) bool
    pool_job: jnp.ndarray,     # (B, M) int32 app ids (UR == n_apps-1)
    pool_inject_t: jnp.ndarray,  # (B, M) f32
    free_top: jnp.ndarray,     # (B,) int32 — free pool slots
    level_mask: jnp.ndarray,   # (L, n_levels) f32 — link -> level one-hot
    level_bw: jnp.ndarray,     # (n_levels,) f32 — aggregate bytes/us
    n_apps: int,
    pool_size: int,
) -> ProbeState:
    """One tick's probe update (runs inside the jitted engine tick).

    Frozen members (``live_m`` false) neither advance their ordinal clock
    (the engine's ``Metrics.ticks``) nor write — a member's sample
    trajectory is identical whether it runs solo or stacked with
    stragglers.
    """
    K = cfg.samples
    B = t_new.shape[0]
    do = live_m & (ticks % cfg.every == 0)  # (B,)
    oh = (jnp.arange(K, dtype=jnp.int32)[None, :] == (ps.idx % K)[:, None]) \
        & do[:, None]  # (B, K) one-hot ring write mask

    # per-level utilization: byte delta since last sample over the level's
    # aggregate capacity for that virtual-time span.
    L = level_mask.shape[0]
    lev_bytes = link_bytes[:, :L] @ level_mask  # (B, n_levels)
    d_t = t_new - ps.last_t  # (B,) us
    util = jnp.where(
        (d_t[:, None] > 0.0) & (level_bw[None, :] > 0.0),
        (lev_bytes - ps.last_level_bytes)
        / (level_bw[None, :] * jnp.maximum(d_t[:, None], 1e-9)),
        0.0,
    )  # (B, n_levels)

    # per-app in-flight stats from the live message pool: mean age of
    # active messages and their count (network queue depth). Inactive
    # slots scatter to a dummy app row.
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]  # (B, 1)
    app = jnp.where(pool_active, pool_job, n_apps)  # (B, M)
    cnt = jnp.zeros((B, n_apps + 1), jnp.float32).at[rows, app].add(1.0)
    age = jnp.where(pool_active, t_new[:, None] - pool_inject_t, 0.0)
    age_sum = jnp.zeros((B, n_apps + 1), jnp.float32).at[rows, app].add(age)
    cnt = cnt[:, :n_apps]
    mean_lat = age_sum[:, :n_apps] / jnp.maximum(cnt, 1.0)  # (B, n_apps)

    occ = (pool_size - free_top).astype(jnp.float32) / float(pool_size)

    w2 = oh[:, :, None]  # (B, K, 1) for per-level / per-app buffers
    return ProbeState(
        t=jnp.where(oh, t_new[:, None], ps.t),
        link_util=jnp.where(w2, util[:, None, :], ps.link_util),
        inflight_lat=jnp.where(w2, mean_lat[:, None, :], ps.inflight_lat),
        queue_depth=jnp.where(
            w2, cnt.astype(jnp.int32)[:, None, :], ps.queue_depth),
        pool_occ=jnp.where(oh, occ[:, None], ps.pool_occ),
        idx=ps.idx + do.astype(jnp.int32),
        last_level_bytes=jnp.where(
            do[:, None], lev_bytes, ps.last_level_bytes),
        last_t=jnp.where(do, t_new, ps.last_t),
    )


def ring_order(idx: int, K: int) -> np.ndarray:
    """Buffer positions oldest -> newest for a ring written ``idx`` times.

    Before wraparound (``idx <= K``) that is simply ``0..idx-1``; after,
    the oldest surviving sample sits at ``idx % K`` and the order walks
    the ring from there.
    """
    n = min(int(idx), int(K))
    return np.arange(int(idx) - n, int(idx), dtype=np.int64) % int(K)


def probe_timelines(
    ps: ProbeState,
    level_names: Sequence[str],
    app_names: Sequence[Optional[str]],
) -> Dict[str, Any]:
    """Unwrap one member's rings into chronological JSON-ready timelines.

    ``app_names`` follows the padded app axis (vacant job slots are
    ``None`` and are skipped); ``level_names`` follows the fabric's
    ``link_levels()`` order.
    """
    idx = int(np.asarray(ps.idx))
    K = int(np.asarray(ps.t).shape[0])
    order = ring_order(idx, K)
    t = np.asarray(ps.t)[order]
    util = np.asarray(ps.link_util)[order]
    lat = np.asarray(ps.inflight_lat)[order]
    depth = np.asarray(ps.queue_depth)[order]
    occ = np.asarray(ps.pool_occ)[order]
    out: Dict[str, Any] = dict(
        samples=len(order),
        wrapped=idx > K,
        t_us=[float(x) for x in t],
        pool_occupancy=[float(x) for x in occ],
        link_utilization={
            str(name): [float(x) for x in util[:, li]]
            for li, name in enumerate(level_names)
        },
        inflight_latency_us={},
        queue_depth={},
    )
    for ai, name in enumerate(app_names):
        if name is None or ai >= lat.shape[1]:
            continue
        out["inflight_latency_us"][str(name)] = [float(x) for x in lat[:, ai]]
        out["queue_depth"][str(name)] = [int(x) for x in depth[:, ai]]
    return out
