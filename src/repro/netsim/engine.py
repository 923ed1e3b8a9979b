"""Tensor-timestepped co-simulation engine (the CODES/ROSS adaptation).

One `tick` advances Δt of virtual time:
  1. **Rank VMs** (stacked over jobs, vectorized over ranks — the
     Argobots-thread replacement): ranks entering an (op, round) emit
     messages and bump their cumulative send/recv thresholds; collectives
     are expanded algorithmically (ring / recursive-doubling / binomial,
     §DESIGN).
  2. **Injection**: emitted messages get pool slots (stack allocator),
     routes (MIN or adaptive, live link demand) and latency floors.
  3. **Network**: fluid fair-share wormhole model — the fused drain tick
     (`kernels/drain_tick.py`): link demand → fair-share rate →
     per-message drain → delivery mask in one pass, over the live pool
     slots packed into the smallest of M, M/2, ..., M/16 slots that holds
     them (`kernels.ops.drain_tick_live`).
  4. **Bookkeeping**: deliveries unblock VMs (cumulative counting — see
     DESIGN §9 for the matching relaxation); latency histograms, per-app
     router-window counters (paper's 0.5 ms packet counters), link loads.

**Stacked layout** (the one-engine-per-envelope design): all jobs' VM
state lives in `(J, Pmax)` padded tensors and the job *programs* are
runtime data — a :class:`JobTable` of `(J, OPmax, 4)` op/grid tables with
per-job rank counts — carried inside :class:`SimState`. The engine
compiles once per **capacity envelope** `(Jmax, Pmax, OPmax)` (plus
topology/net config) and serves any job set that fits: different
scenarios, different placements, different arrival schedules, all without
re-tracing. Padded ranks/jobs are born `done` and never emit.

**Explicit member batch**: every state leaf has a leading member
dimension `B`. `run`/`tick` accept a single member state (auto-promoted
to `B=1`) or a stacked batch; all scatters fold the member index into one
flat index so an 8-member campaign costs one scatter per pass, not eight
serialized ones. Member *i* of a batched run is bit-identical to its own
`B=1` run, and to the historical per-job-loop engine (the equivalence
goldens in tests/ assert this). Across several local devices,
:meth:`Engine.run_sharded` runs the same loop under ``shard_map`` over a
one-axis ``members`` mesh (:func:`member_mesh`): each device steps its
own members, with no collectives.

**Slot recycling** (the online-scheduler substrate, `repro.sched`): job
slots are a reusable resource. `run_window(state, t_stop)` advances until
the next scheduling event — virtual time reaching ``t_stop`` (the next
trace arrival) or a job slot completing — and returns control to the
host; :func:`admit_job` writes a new program into a vacant slot and
:func:`retire_job` vacates a finished one, so a trace of hundreds of jobs
streams through one compiled ``(Jmax, Pmax, OPmax)`` envelope across
chained windows with full state carry-over. A chained-window run is
bit-identical to one uninterrupted ``run`` over the same job set as long
as every window boundary coincides with a job arrival (the window cap
clamps the PDES time skip exactly like a pending job's ``start`` does).
"""
from __future__ import annotations

import math
import os
import re
import warnings
import weakref
from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.skeleton import OP, SkeletonProgram
from repro.kernels import ops as KOPS
from repro.netsim.config import NetConfig
from repro.netsim.fabric import Fabric, fabric_key, routing_tables
from repro.netsim.faults import FaultState
from repro.obs.hist import (
    HistConfig, HistState, init_hist, update_hist,
)
from repro.obs.probes import (
    ProbeConfig, ProbeState, init_probes, sample_probes,
)

MAXE = 8  # max emissions per rank per (op, round)


class JobTable(NamedTuple):
    """The job set as runtime data: stacked, padded program/placement tables.

    Leaves are `(J, ...)` for a member state and `(B, J, ...)` when
    batched. Padded jobs have ``P=1``, an END-only program, and
    ``start=inf``; padded ranks (``p >= P[j]``) are born done.
    """

    ops: jnp.ndarray  # (J, OPmax, 4) int32, END-padded
    grid: jnp.ndarray  # (J, OPmax, 4) int32 cartesian dims for XCHG
    P: jnp.ndarray  # (J,) int32 actual ranks per job (>= 1)
    logp: jnp.ndarray  # (J,) int32 ceil(log2(max(P, 2)))
    r2n: jnp.ndarray  # (J, Pmax) int32 rank -> node (0-padded)
    slowdown: jnp.ndarray  # (J, Pmax) f32 per-rank COMPUTE stretch
    start: jnp.ndarray  # (J,) f32 arrival offset (inf for padded jobs)


class VMState(NamedTuple):
    pc: jnp.ndarray  # (J, Pmax) int32
    rnd: jnp.ndarray  # (J, Pmax) int32 round within current op
    emitted: jnp.ndarray  # (J, Pmax) bool — entered current (op, round)
    busy_until: jnp.ndarray  # (J, Pmax) f32 us
    send_need: jnp.ndarray  # (J, Pmax) int32 cumulative deliveries required
    send_done: jnp.ndarray
    recv_need: jnp.ndarray
    recv_done: jnp.ndarray
    comm_time: jnp.ndarray  # (J, Pmax) f32 us blocked on communication
    done: jnp.ndarray  # (J, Pmax) bool


class URState(NamedTuple):
    next_t: jnp.ndarray  # (Pu,) f32
    count: jnp.ndarray  # (Pu,) int32


class PoolState(NamedTuple):
    active: jnp.ndarray  # (M,) bool
    src_rank: jnp.ndarray  # (M,) int32
    dst_rank: jnp.ndarray
    job: jnp.ndarray  # (M,) int32 (== app id; UR uses id Jmax)
    size: jnp.ndarray  # (M,) f32
    bytes_rem: jnp.ndarray  # (M,) f32
    inject_t: jnp.ndarray
    min_arrive: jnp.ndarray
    routes: jnp.ndarray  # (M, route_width) int32 (fabric-declared width)
    free_stack: jnp.ndarray  # (M,) int32
    free_top: jnp.ndarray  # scalar int32 (number of free slots)
    dropped: jnp.ndarray  # scalar int32 (allocation failures; must stay 0)


class Metrics(NamedTuple):
    lat_hist: jnp.ndarray  # (n_apps, BINS) int32
    lat_sum: jnp.ndarray  # (n_apps,) f32
    lat_min: jnp.ndarray
    lat_max: jnp.ndarray
    lat_cnt: jnp.ndarray
    link_bytes: jnp.ndarray  # (L+1,) f32 cumulative per link
    router_win: jnp.ndarray  # (n_apps, R) f32 current window (recv bytes)
    router_wins: jnp.ndarray  # (W, n_apps, R) f32 snapshots
    win_idx: jnp.ndarray
    peak_inject: jnp.ndarray  # f32 max bytes injected in one (tick, app)
    # always-on tick counters, each gated by the member freeze mask so a
    # member counts what its own B=1 run would (docs/obs.md):
    ticks: jnp.ndarray  # int32 live ticks taken
    inject_ticks: jnp.ndarray  # int32 live ticks with a candidate or UR fire
    # f32 sum over live ticks of the pool slots active in the drain pass;
    # exact while the total is below 2**24, then each tick's add rounds to
    # float32 (relative error at most 2**-24 per tick; int32 would
    # overflow at 65,536 slots x 32,768 ticks)
    active_slot_ticks: jnp.ndarray
    peak_active: jnp.ndarray  # int32 most pool slots active at once
    # (n_rungs,) int32 live ticks drained at each width of
    # ``kernels.ops.drain_widths(M)`` (widest, the whole pool, first)
    drain_rung_ticks: jnp.ndarray
    # int32 passes of ``inject_width(inject_candidates(state), M)``
    # candidates routed over the injecting live ticks (a tick takes as
    # many as its fullest member allocates)
    inject_passes: jnp.ndarray


class SimState(NamedTuple):
    t: jnp.ndarray  # (B,) f32 us ((,) for a member state)
    vms: VMState
    ur: Optional[URState]
    pool: PoolState
    metrics: Metrics
    rng: jnp.ndarray  # uint32 counter
    # runtime per-member inputs: the whole job set (programs, placements,
    # arrival schedule) lives in the state, so one jitted engine batches
    # members that differ in any of them — including different job sets,
    # as long as they fit the engine's (Jmax, Pmax, OPmax) envelope.
    jobs: JobTable
    ur_nodes: Optional[jnp.ndarray]  # (Pu,) int32 (None when no UR source)
    # sim-plane probe rings (repro.obs): None (an empty pytree subtree,
    # like ``ur``) unless the engine was built with a ProbeConfig — the
    # unprobed state layout is unchanged, so goldens stay bit-identical.
    probes: Optional[ProbeState] = None
    # full-fidelity per-(app, link-level) latency histograms (repro.obs):
    # None unless built with a HistConfig, same discipline as ``probes``.
    hist: Optional[HistState] = None
    # runtime fault mask (repro.netsim.faults): per-link bandwidth factors
    # and per-router health factors, ``(L,)``/``(R,)`` per member. Always
    # populated by ``init_state`` — which links are dead (and how degraded)
    # is runtime data like the job tables, so one compiled engine serves
    # every failure pattern. Healthy factors are exact 1.0 multiplies /
    # +0.0 demand adds, keeping healthy runs bit-identical to the goldens.
    faults: Optional[FaultState] = None


@dataclass
class JobSpec:
    name: str
    skeleton: SkeletonProgram
    rank2node: np.ndarray  # (P,) node ids
    start_us: float = 0.0  # arrival offset (staggered co-scheduling)


@dataclass
class URSpec:
    name: str
    rank2node: np.ndarray
    size_bytes: float = 10 * 1024
    interval_us: float = 1000.0
    start_us: float = 0.0


@dataclass(frozen=True)
class EngineCapacity:
    """The envelope one compiled engine serves: any job set with
    ``n_jobs <= Jmax``, every job's ``n_ranks <= Pmax`` and
    ``n_ops <= OPmax`` runs through the same jit cache entry."""

    Jmax: int
    Pmax: int
    OPmax: int

    @staticmethod
    def of_jobs(jobs: Sequence[JobSpec]) -> "EngineCapacity":
        return EngineCapacity(
            Jmax=max(len(jobs), 1),
            Pmax=max((j.skeleton.n_ranks for j in jobs), default=1),
            OPmax=max((j.skeleton.n_ops for j in jobs), default=1),
        )

    def union(self, other: "EngineCapacity") -> "EngineCapacity":
        return EngineCapacity(
            max(self.Jmax, other.Jmax), max(self.Pmax, other.Pmax),
            max(self.OPmax, other.OPmax),
        )


@dataclass
class Engine:
    """The compiled engine bundle for one capacity envelope.

    Unpacks like the historical ``(init_state, run, tick)`` triple
    (``init, run, tick = build_engine(...)`` keeps working); the online
    scheduler additionally uses :attr:`run_window` — run until virtual
    time reaches ``t_stop`` *or* a job slot completes, whichever is first
    — plus :attr:`capacity` for envelope bookkeeping.
    """

    init_state: Callable
    run: Callable
    tick: Callable
    run_window: Callable
    capacity: EngineCapacity
    _lower: Optional[Callable] = None

    def __iter__(self):
        return iter((self.init_state, self.run, self.tick))

    def run_sharded(self, mesh: Mesh) -> Callable:
        """``run`` over a batch whose every leaf is sharded on its leading
        member axis over ``mesh`` (:func:`member_mesh`): each device
        runs the while loop over its own members, with no collectives.
        Built once per (``run``, mesh), so every engine wrapper that
        shares this ``run`` shares one program."""
        progs = _SHARDED_RUNS.setdefault(self.run, {})
        if mesh not in progs:
            spec = PartitionSpec(MEMBER_AXIS)
            progs[mesh] = jax.jit(jax.shard_map(
                self.run, mesh=mesh, in_specs=(spec,), out_specs=spec,
                check_vma=False))
        return progs[mesh]

    def phase_map(self, state, t_stop=None) -> Dict[str, str]:
        """``{HLO instruction name: tick phase}`` of the run program
        compiled for the shapes of ``state`` (``run_window``'s when
        ``t_stop`` is given; :meth:`run_sharded`'s when ``state``'s
        leaves carry a sharding over a member mesh). Compiling again
        hits the compile cache. A profile's device ops carry these
        instruction names, so the map labels each op with the
        ``tick/<phase>`` scope it was traced in (docs/obs.md)."""
        mesh = getattr(getattr(state.t, "sharding", None), "mesh", None)
        if t_stop is None and mesh is not None and mesh.size > 1:
            lowered = self.run_sharded(mesh).lower(state)
        else:
            lowered = self._lower(state, t_stop)
        return hlo_phases(lowered.compile().as_text())


MEMBER_AXIS = "members"
# run -> {mesh: sharded program}; an entry goes with its engine's run
_SHARDED_RUNS: "weakref.WeakKeyDictionary[Callable, Dict]" = (
    weakref.WeakKeyDictionary())


def member_mesh(n_members: int) -> Optional[Mesh]:
    """The one-axis ``members`` mesh of the local devices over which a
    batch of ``n_members`` is sharded, or None where it runs on one
    device: there is one local device, or the members do not divide
    evenly among them."""
    devs = jax.local_devices()
    if len(devs) > 1 and n_members % len(devs) == 0:
        return Mesh(np.asarray(devs), (MEMBER_AXIS,))
    return None


def _ceil_log2(P: int) -> int:
    return max(1, math.ceil(math.log2(max(P, 2))))


def pack_jobs(
    jobs: Sequence[JobSpec],
    cap: EngineCapacity,
    *,
    placements: Optional[Sequence[np.ndarray]] = None,
    start_us: Optional[Sequence[float]] = None,
    job_start_us: Optional[Sequence[float]] = None,
    rank_slowdown: Optional[Sequence[Optional[np.ndarray]]] = None,
) -> JobTable:
    """Stack a job list into the padded (Jmax, Pmax/OPmax) runtime tables.

    ``start_us`` *replaces* each job's arrival offset outright (a member's
    actual schedule); ``job_start_us`` provides build-time defaults that
    are maxed with each job's own ``start_us`` attribute.
    """
    J, Pmax, OPmax = cap.Jmax, cap.Pmax, cap.OPmax
    if len(jobs) > J:
        raise ValueError(f"{len(jobs)} jobs exceed engine capacity Jmax={J}")
    ops = np.zeros((J, OPmax, 4), np.int32)
    ops[:, :, 0] = OP["END"]
    grid = np.zeros((J, OPmax, 4), np.int32)
    P = np.ones((J,), np.int32)
    r2n = np.zeros((J, Pmax), np.int32)
    slow = np.ones((J, Pmax), np.float32)
    start = np.full((J,), np.inf, np.float32)
    for ji, j in enumerate(jobs):
        sk = j.skeleton
        if sk.n_ranks > Pmax or sk.n_ops > OPmax:
            raise ValueError(
                f"job {j.name!r} ({sk.n_ranks} ranks, {sk.n_ops} ops) exceeds "
                f"engine capacity (Pmax={Pmax}, OPmax={OPmax})"
            )
        ops[ji, : sk.n_ops] = sk.ops
        grid[ji, : sk.n_ops] = sk.grid
        P[ji] = sk.n_ranks
        pl = placements[ji] if placements is not None else j.rank2node
        r2n[ji, : sk.n_ranks] = np.asarray(pl, np.int32)
        if rank_slowdown is not None and rank_slowdown[ji] is not None:
            slow[ji, : sk.n_ranks] = np.asarray(rank_slowdown[ji], np.float32)
        s = float(j.start_us)
        if job_start_us is not None and job_start_us[ji] is not None:
            s = max(s, float(job_start_us[ji]))
        if start_us is not None and start_us[ji] is not None:
            s = float(start_us[ji])
        start[ji] = s
    logp = np.asarray([_ceil_log2(int(p)) for p in P], np.int32)
    return JobTable(
        ops=jnp.asarray(ops), grid=jnp.asarray(grid), P=jnp.asarray(P),
        logp=jnp.asarray(logp), r2n=jnp.asarray(r2n),
        slowdown=jnp.asarray(slow), start=jnp.asarray(start),
    )


def _hash(x):
    x = (x ^ (x >> 16)) * jnp.uint32(0x45D9F3B)
    x = (x ^ (x >> 16)) * jnp.uint32(0x45D9F3B)
    return x ^ (x >> 16)


# ---------------------------------------------------------------------------
# flat-index batched scatters: fold the member index into the scatter index
# so XLA sees ONE scatter over (B * size,) instead of B serialized ones.
# ---------------------------------------------------------------------------

def _flat(target, idx, valid=None):
    """target (B, *S); idx member-local flat index (B, ...). Returns the
    flattened view, the globalized index, and the original shape."""
    B = target.shape[0]
    size = int(np.prod(target.shape[1:]))
    off = (jnp.arange(B, dtype=jnp.int32) * size).reshape(
        (B,) + (1,) * (idx.ndim - 1)
    )
    gidx = idx + off
    if valid is not None:
        gidx = jnp.where(valid, gidx, B * size)  # dropped
    return target.reshape(-1), gidx.reshape(-1), target.shape


def _flat_add(target, idx, vals, valid=None):
    flat, gidx, shape = _flat(target, idx, valid)
    return flat.at[gidx].add(vals.reshape(-1), mode="drop").reshape(shape)


def _flat_set(target, idx, vals, valid=None):
    flat, gidx, shape = _flat(target, idx, valid)
    vals = jnp.broadcast_to(vals, idx.shape)
    return flat.at[gidx].set(vals.reshape(-1), mode="drop").reshape(shape)


def _flat_min(target, idx, vals):
    flat, gidx, shape = _flat(target, idx)
    return flat.at[gidx].min(vals.reshape(-1), mode="drop").reshape(shape)


def _flat_max(target, idx, vals):
    flat, gidx, shape = _flat(target, idx)
    return flat.at[gidx].max(vals.reshape(-1), mode="drop").reshape(shape)


# ---------------------------------------------------------------------------
# injection over the candidates that fired: packed, in emission order, into
# passes of a fixed width, then routed and allocated there
# ---------------------------------------------------------------------------

# the width an injecting tick routes its fired candidates at; a tick whose
# fullest member allocates more routes them in several passes of it. One
# width, because every width compiles its own ``route_fn``: its TPU code
# grows with the width and each one adds tracing to every start.
INJECT_WIDTH = 1024


def inject_width(n_cands: int, M: int) -> int:
    """The width a member's ``n_cands`` candidates are routed at, a pass:
    ``min(n_cands, M, INJECT_WIDTH)`` (a candidate at emission index
    ``M`` or later never finds a free slot)."""
    return min(n_cands, M, INJECT_WIDTH)


def inject_candidates(state) -> int:
    """A member's injection candidates a tick, ``Jmax * Pmax * MAXE`` job
    candidates and the UR source's ``Pu``, from a member or batched
    state's shapes."""
    J, Pmax = state.vms.pc.shape[-2:]
    Pu = 0 if state.ur is None else state.ur.next_t.shape[-1]
    return int(J) * int(Pmax) * MAXE + int(Pu)


def routed_candidates(state) -> np.ndarray:
    """Each member's candidates routed over its injecting ticks (passes x
    width), float64, flat over any leading axes."""
    W = inject_width(inject_candidates(state), state.pool.active.shape[-1])
    passes = jax.device_get(state.metrics.inject_passes)
    return np.asarray(passes, np.float64).reshape(-1) * W


class Cands(NamedTuple):
    """The fields of packed candidates, ``(B, W)`` each."""

    src_rank: jnp.ndarray  # int32
    dst_rank: jnp.ndarray  # int32
    src_node: jnp.ndarray  # int32
    dst_node: jnp.ndarray  # int32
    size: jnp.ndarray  # f32 bytes
    app: jnp.ndarray  # int32 app id
    rand: jnp.ndarray  # uint32 routing draw


def inject_fired(pool: PoolState, t, mask, W: int, cands: Callable,
                 route: Callable, hop_latency_us: float):
    """Allocate pool slots to the candidates that fired and route them,
    batched over members.

    ``mask`` ``(B, n)`` marks the candidates that fired, in emission
    order. They are packed, in that order, into consecutive columns and
    routed ``W`` columns a pass, as many passes as the fullest member
    allocates (``lax.fori_loop``). ``cands(idx)`` gives a :class:`Cands`
    at the ``(B, W)`` candidate indices ``idx`` (``n`` in the padding
    columns), and ``route(src_node, dst_node, rand)`` their ``(B, W, K)``
    routes and ``(B, W)`` hop counts. A candidate's column is its
    emission index ``k``: it takes slot ``free_stack[free_top - 1 - k]``
    while ``k < free_top`` and is dropped after. The counts (``n_emit``,
    allocated, ``dropped``) are taken over all ``n``.

    Returns the pool, the ``(B, n)`` mask of the candidates allocated
    and the passes (int32).
    """
    B, n = mask.shape
    M = pool.active.shape[1]
    free_top = pool.free_top[:, None]
    col = jnp.cumsum(mask, axis=1, dtype=jnp.int32) - 1  # emission order
    n_emit = jnp.sum(mask, axis=1, dtype=jnp.int32)
    n_alloc = jnp.minimum(n_emit, pool.free_top)
    # a column at or past min(n, M) never finds a slot
    idx = KOPS.pack_columns(mask, col, -(-min(n, M) // W) * W)

    def one_pass(i, leaves):
        """Route and allocate columns ``i * W`` to ``(i + 1) * W``."""
        active, src_rank, dst_rank, job, size, rem, inj, mina, rts = leaves
        at = jax.lax.dynamic_slice_in_dim(idx, i * W, W, axis=1)
        k = i * W + jnp.arange(W, dtype=jnp.int32)[None, :]
        can = (at < n) & (k < free_top)
        slot = jnp.take_along_axis(
            pool.free_stack, jnp.clip(free_top - 1 - k, 0, M - 1), axis=1)
        slot = jnp.where(can, slot, M)  # M = dummy row
        c = cands(at)
        routes, hops = route(c.src_node, c.dst_node, c.rand)
        # route rows: scatter whole (K,) rows per slot
        row = jnp.where(
            can, slot + (jnp.arange(B, dtype=jnp.int32) * M)[:, None], B * M)
        return (
            _flat_set(active, slot, True, valid=can),
            _flat_set(src_rank, slot, c.src_rank, valid=can),
            _flat_set(dst_rank, slot, c.dst_rank, valid=can),
            _flat_set(job, slot, c.app, valid=can),
            _flat_set(size, slot, c.size, valid=can),
            _flat_set(rem, slot, c.size, valid=can),
            _flat_set(inj, slot, t[:, None], valid=can),
            _flat_set(
                mina, slot,
                t[:, None] + hops.astype(jnp.float32) * hop_latency_us,
                valid=can),
            rts.reshape(B * M, -1).at[row.reshape(-1)].set(
                routes.reshape(B * W, -1), mode="drop",
            ).reshape(rts.shape),
        )

    passes = (jnp.max(n_alloc) + W - 1) // W
    (active, src_rank, dst_rank, job, size, rem, inj, mina,
     rts) = jax.lax.fori_loop(
        0, passes, one_pass,
        (pool.active, pool.src_rank, pool.dst_rank, pool.job, pool.size,
         pool.bytes_rem, pool.inject_t, pool.min_arrive, pool.routes))
    pool = pool._replace(
        active=active, src_rank=src_rank, dst_rank=dst_rank, job=job,
        size=size, bytes_rem=rem, inject_t=inj, min_arrive=mina,
        routes=rts, free_top=pool.free_top - n_alloc,
        dropped=pool.dropped + (n_emit - n_alloc),
    )
    return pool, mask & (col < free_top), passes


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_HLO_INSTRUCTION = re.compile(
    r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\(([^)]*)\)")
_HLO_SUBCOMPUTATION = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
# computations a while loop or a conditional runs
_HLO_CONTROL = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
    r"|\bbranch_computations=\{([^}]*)\}")
_HLO_OPERAND = re.compile(r"%?([\w.\-]+)")
_HLO_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_TICK_PHASE = re.compile(r"(?:^|/)tick/(\w+)")
# instructions that hold no device time of their own
_HLO_STRUCTURAL = {"parameter", "constant", "get-tuple-element", "tuple",
                   "while", "conditional"}


def hlo_phases(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: phase}`` of a compiled module's text.

    An instruction's phase is the ``tick/<phase>`` scope of its
    ``op_name`` metadata. A fusion takes its root instruction's scope;
    where the compiler left the root without metadata (index arithmetic
    it added), the fusion's own, else that of the scoped instruction
    nearest the root. Data movement the compiler inserted with no
    metadata (copies, prefetches, layout changes outside any fusion)
    takes the phase of the first instruction it feeds, else of the one
    that feeds it; so does an instruction of a while loop's body (or of
    a branch it runs) whose metadata names no tick scope, as the SPMD
    partitioner leaves what it rebuilds under a ``shard_map``.
    """
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    roots: Dict[str, str] = {}
    last: Dict[str, str] = {}  # computation -> its last scoped phase
    comp_of: Dict[str, str] = {}
    opcode: Dict[str, str] = {}
    operands: Dict[str, list] = {}
    bare = set()  # instructions with no op_name: the compiler added them
    inner = set()  # computations called by fusions and reductions
    control: Dict[str, set] = {}  # computation -> loop bodies, branches
    loops = set()  # computations run as a while loop's body or condition
    comp = None
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m is None:
            c = _HLO_COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name = m.group(2)
        comp_of[name], opcode[name] = comp, m.group(3)
        operands[name] = _HLO_OPERAND.findall(m.group(4))
        if m.group(1):
            roots[comp] = name
        op = _HLO_OP_NAME.search(line)
        if op is None:
            bare.add(name)
        ph = _TICK_PHASE.search(op.group(1)) if op else None
        own[name] = ph.group(1) if ph else None
        if ph is not None:
            last[comp] = ph.group(1)
        for sub in _HLO_SUBCOMPUTATION.findall(line):
            inner.add(sub)
            if m.group(3) == "fusion":
                calls[name] = sub
        for one, many in _HLO_CONTROL.findall(line):
            subs = [one] if one else _HLO_OPERAND.findall(many)
            control.setdefault(comp, set()).update(subs)
            if m.group(3) == "while":
                loops.update(subs)

    def phase(name, depth=0):
        callee = calls.get(name)
        if callee in roots and depth < 8:
            return (phase(roots[callee], depth + 1) or own.get(name)
                    or last.get(callee))
        return own.get(name)

    out = {n: p for n in own if (p := phase(n)) is not None}
    users: Dict[str, list] = {}
    for n, ops in operands.items():
        for o in ops:
            users.setdefault(o, []).append(n)
    todo = list(loops)
    while todo:  # every computation a loop runs, through its branches
        for sub in control.get(todo.pop(), ()):
            if sub not in loops:
                loops.add(sub)
                todo.append(sub)
    loose = [n for n in own if (n in bare or comp_of[n] in loops)
             and n not in out and comp_of[n] not in inner
             and opcode[n] not in _HLO_STRUCTURAL]
    for near in (users, operands):  # what it feeds first, then its source
        while loose:
            left = []
            for n in loose:
                got = [x for x in near.get(n, ()) if x in out]
                if got:
                    out[n] = out[got[0]]
                else:
                    left.append(n)
            if len(left) == len(loose):
                break
            loose = left
    return out


def _member_batched(fn):
    """Promote a member state (scalar t) to a B=1 batch around ``fn``."""

    def wrapper(state: SimState):
        if state.t.ndim == 0:
            batched = jax.tree_util.tree_map(lambda x: x[None], state)
            out = fn(batched)
            return jax.tree_util.tree_map(lambda x: x[0], out)
        return fn(state)

    return wrapper


def build_engine(
    topo: Fabric,
    jobs: Sequence[JobSpec],
    *,
    routing: str = "ADP",
    ur: Optional[URSpec] = None,
    net: Optional[NetConfig] = None,
    pool_size: Optional[int] = None,
    horizon_us: float = 500_000.0,
    link_down: Optional[np.ndarray] = None,  # (L,) bool — failed links
    rank_slowdown: Optional[Sequence[np.ndarray]] = None,  # per job (P,) f32
    job_start_us: Optional[Sequence[float]] = None,  # per job arrival offsets
    capacity: Optional[EngineCapacity] = None,
    use_pallas: bool = False,
    probes: Optional[ProbeConfig] = None,
    hist: Optional[HistConfig] = None,
):
    """Returns an :class:`Engine` — unpacks as ``(init_state, run, tick)``;
    ``run``: state -> final state (jit); ``engine.run_window`` additionally
    serves the online scheduler (stop at ``t_stop`` or slot completion).

    ``jobs`` provides the *default* job set and sizes the capacity envelope
    when ``capacity`` is not given; ``init_state(jobs=...)`` swaps in any
    other job set that fits the envelope without re-tracing. ``run`` and
    ``tick`` accept a member state or a stacked batch of members (leading
    ``B`` dim) — the whole campaign is one call either way.

    Fault/straggler injection (DESIGN.md §4, docs/faults.md): failed or
    degraded links/routers are **runtime data** — pass
    ``init_state(faults=...)`` a :class:`repro.netsim.faults.FaultState`.
    Dead links carry no traffic (adaptive routing steers around them via
    the demand estimate; minimal routing stalls on them — the realistic
    asymmetry). The ``link_down`` kwarg is a deprecated bit-compatible
    shim that seeds the default fault mask. ``rank_slowdown`` multiplies
    each rank's COMPUTE durations (straggler model — collectives make the
    whole job wait).

    Staggered arrivals: each job's ranks idle until ``max(job_start_us[ji],
    jobs[ji].start_us)`` of virtual time — dynamic co-scheduling, where a
    job lands on a network already carrying traffic.

    The drain tick is the jnp fused path (``kernels/ref.drain_tick_ref``)
    on every backend, run each tick over the live pool slots at the
    smallest width of ``kernels.ops.drain_widths(M)`` that holds them
    (the top rung, ``M``, is the full-pool pass). ``use_pallas=True``
    opts into the Pallas kernel: interpret mode off-TPU (the CPU
    tests), Mosaic on a TPU, where jax 0.9.0 refuses its in-kernel
    scatter-add (``kernels/drain_tick.py``).

    Injecting ticks route and allocate only the candidates that fired,
    the job candidates' then the UR source's, packed in emission order
    into passes of :func:`inject_width` columns, as many as the fullest
    member allocates (:func:`inject_fired`).

    ``probes`` compiles in the sim-plane observation rings
    (:mod:`repro.obs.probes`): per-level link utilization, per-app
    in-flight latency, pool occupancy, and queue depth sampled every
    ``probes.every`` live ticks. A static choice — ``probes=None``
    builds an engine whose tick contains no probe code at all.
    """
    net = net or NetConfig()
    # the fabric's one dispatch point: its gather tables + vectorized
    # router (dragonfly MIN/UGAL, fat-tree D-mod-k/spray, torus DOR/bypass)
    T, route_fn = routing_tables(topo)
    L = topo.n_links
    RW = topo.route_width  # pool route-row width (fabric-declared)
    n_nodes = topo.n_nodes
    M = pool_size or net.pool_size
    cap = capacity or EngineCapacity.of_jobs(jobs)
    J, Pmax, OPmax = cap.Jmax, cap.Pmax, cap.OPmax
    n_apps = J + (1 if ur else 0)
    adaptive = routing.upper() in ("ADP", "ADAPTIVE")
    dt = net.tick_us
    BINS = net.latency_hist_bins
    W = net.max_windows
    R = topo.n_routers
    n_rungs = len(KOPS.drain_widths(M))
    # an opted-in Pallas drain compiles Mosaic on a TPU and is emulated
    # in interpret mode elsewhere
    kernel_interpret = jax.default_backend() != "tpu"

    default_table = pack_jobs(
        jobs, cap, job_start_us=job_start_us, rank_slowdown=rank_slowdown
    )
    ur_r2n = jnp.asarray(ur.rank2node, jnp.int32) if ur else None
    Pu = int(ur.rank2node.shape[0]) if ur else 0
    link_dstr = jnp.concatenate(
        [jnp.asarray(topo.link_dst_router, jnp.int32),
         jnp.zeros((1,), jnp.int32)]
    )  # dummy row
    # fault gather tables: each tick recomputes the effective per-link
    # bandwidth factor from the state's runtime fault leaves —
    #   eff[l] = link_bw_factor[l] * router_factor[src[l]] * router_factor[dst[l]]
    # — so link *and* router health are runtime data (repro.netsim.faults).
    link_srcr_l = jnp.asarray(topo.link_src_router, jnp.int32)  # (L,)
    link_dstr_l = jnp.asarray(topo.link_dst_router, jnp.int32)  # (L,)
    bw_base = jnp.asarray(topo.link_bw, jnp.float32)  # (L,) healthy bw
    if link_down is not None:
        warnings.warn(
            "build_engine(link_down=...) is deprecated: failure patterns "
            "are runtime data now — pass init_state(faults=...) a "
            "repro.netsim.faults.FaultState (or use the StudyGrid.failures "
            "axis). The kwarg is a bit-compatible shim seeding the default "
            "fault mask.",
            DeprecationWarning, stacklevel=2,
        )
    default_link_factor = np.where(
        np.asarray(link_down, bool), 0.0, 1.0
    ).astype(np.float32) if link_down is not None else np.ones(L, np.float32)

    # probe constants (sim-plane observability): link -> level one-hot and
    # each level's aggregate healthy capacity, baked at build time. The
    # denominators deliberately stay *healthy* capacity under runtime
    # faults — a failure shows up as a per-level utilization shift, not a
    # silently renormalized ratio.
    if probes is not None:
        _lm = np.stack(
            [np.asarray(m, np.float32) for m in topo.link_levels().values()],
            axis=1,
        )  # (L, n_levels)
        probe_level_mask = jnp.asarray(_lm)
        probe_level_bw = jnp.asarray(
            (np.asarray(topo.link_bw, np.float32)
             * default_link_factor)[:, None] * _lm
        ).sum(axis=0)  # (n_levels,)
        probe_n_levels = _lm.shape[1]

    # histogram constants: link -> fabric-level index, baked at build time
    # (a message's level is the max level any of its route links sits on;
    # intra-node messages with no route links land on level 0). The table
    # carries a dummy 0 row at index L so padded route entries are inert.
    if hist is not None:
        _hl = np.zeros((L + 1,), np.int32)
        _levels = topo.link_levels()
        for _li, _mask in enumerate(_levels.values()):
            _hl[:L][np.asarray(_mask, bool)] = _li
        hist_link_level = jnp.asarray(_hl)
        hist_n_levels = max(len(_levels), 1)

    # the stacked injection pass's candidates are job-major, rank-major,
    # emission-minor — the same order the historical per-job loop
    # allocated slots in: candidate i < N is emission i % MAXE of rank
    # (i // MAXE) % Pmax of job i // (Pmax * MAXE); candidate N + r is
    # the UR source's rank r, allocated after every job's, as the
    # historical UR pass was. Those that fired are routed in passes of
    # ``cand_width`` (``inject_fired``).
    N = J * Pmax * MAXE
    cand_width = inject_width(N + Pu, M)

    # ------------------------------------------------------------------
    # stacked emission: one pass computes this (op, round)'s messages for
    # every (job, rank) — batched over members.
    # ------------------------------------------------------------------
    def vm_emit(jt: JobTable, vm: VMState, t, live_m):
        B = t.shape[0]
        ranks = jnp.arange(Pmax, dtype=jnp.int32)[None, None, :]  # (1,1,Pmax)
        P = jt.P[:, :, None]  # (B, J, 1)
        row = jnp.take_along_axis(
            jt.ops, vm.pc[:, :, :, None], axis=2
        )  # (B, J, Pmax, 4)
        opc, a0, a1, a2 = row[..., 0], row[..., 1], row[..., 2], row[..., 3]
        g = jnp.take_along_axis(jt.grid, vm.pc[:, :, :, None], axis=2)
        # live_m gates finished/horizon-frozen members in place of a
        # whole-state select: a non-live member never enters an (op, round),
        # so every downstream write is a no-op for it.
        enter = (
            (~vm.emitted) & (~vm.done)
            & (t[:, None, None] >= jt.start[:, :, None])
            & live_m[:, None, None]
        )

        dst = jnp.full((B, J, Pmax, MAXE), -1, jnp.int32)
        size = jnp.zeros((B, J, Pmax), jnp.float32)
        send_inc = jnp.zeros((B, J, Pmax), jnp.int32)
        recv_inc = jnp.zeros((B, J, Pmax), jnp.int32)
        busy = vm.busy_until

        # COMPUTE (straggler factor scales the delay per rank)
        is_comp = opc == OP["COMPUTE"]
        busy = jnp.where(
            enter & is_comp,
            t[:, None, None] + a0.astype(jnp.float32) * jt.slowdown, busy,
        )

        # P2P / IP2P
        is_p2p = (opc == OP["P2P"]) | (opc == OP["IP2P"])
        send_p2p = is_p2p & (ranks == a0)
        dst = dst.at[..., 0].set(jnp.where(send_p2p, a1, dst[..., 0]))
        size = jnp.where(send_p2p, a2.astype(jnp.float32), size)
        send_inc = send_inc + send_p2p.astype(jnp.int32)
        recv_inc = recv_inc + (is_p2p & (ranks == a1)).astype(jnp.int32)

        # GATHER (root a0, size a1)
        is_gather = opc == OP["GATHER"]
        send_g = is_gather & (ranks != a0)
        dst = dst.at[..., 0].set(jnp.where(send_g, a0, dst[..., 0]))
        size = jnp.where(send_g, a1.astype(jnp.float32), size)
        send_inc = send_inc + send_g.astype(jnp.int32)
        recv_inc = recv_inc + jnp.where(is_gather & (ranks == a0), P - 1, 0)

        # SCATTER (root a0, size a1), MAXE targets per round
        is_scat = opc == OP["SCATTER"]
        base = vm.rnd * MAXE
        tgt = base[..., None] + jnp.arange(MAXE, dtype=jnp.int32)
        tgt = tgt + (tgt >= a0[..., None])  # skip root
        valid_s = (
            is_scat[..., None] & (ranks == a0)[..., None] & (tgt < P[..., None])
        )
        dst = jnp.where(valid_s, tgt, dst)
        size = jnp.where(is_scat & (ranks == a0), a1.astype(jnp.float32), size)
        send_inc = send_inc + jnp.where(
            is_scat & (ranks == a0), valid_s.sum(-1).astype(jnp.int32), 0
        )
        recv_first = is_scat & (ranks != a0) & (vm.rnd == 0)
        recv_inc = recv_inc + recv_first.astype(jnp.int32)

        # XCHG (size a0, ndims a1, dims g): one round, 2*ndims neighbors
        is_x = opc == OP["XCHG"]
        dims = jnp.maximum(g, 1)  # (B, J, Pmax, 4)
        stride = jnp.concatenate(
            [jnp.ones_like(dims[..., :1]), jnp.cumprod(dims[..., :3], axis=-1)],
            axis=-1,
        )
        coord = (ranks[..., None] // stride) % dims
        for d in range(4):
            for s, dirn in ((2 * d, 1), (2 * d + 1, -1)):
                if s >= MAXE:
                    continue
                nb_c = (coord[..., d] + dirn) % dims[..., d]
                nb = ranks + (nb_c - coord[..., d]) * stride[..., d]
                use = is_x & (d < a1)
                dst = dst.at[..., s].set(jnp.where(use, nb, dst[..., s]))
        size = jnp.where(is_x, a0.astype(jnp.float32), size)
        nmsg = 2 * jnp.minimum(a1, 4)
        send_inc = send_inc + jnp.where(is_x, nmsg, 0)
        recv_inc = recv_inc + jnp.where(is_x, nmsg, 0)

        # ALLREDUCE: ring (>=4KiB) 2(P-1) rounds of size/P; else RD log2
        is_ar = opc == OP["ALLREDUCE"]
        is_bar = opc == OP["BARRIER"]
        big = a0 >= 4096
        ring = is_ar & big
        nb_ring = (ranks + 1) % P
        sz_ring = jnp.ceil(a0.astype(jnp.float32) / P)
        dst = dst.at[..., 0].set(jnp.where(ring, nb_ring, dst[..., 0]))
        size = jnp.where(ring, sz_ring, size)
        send_inc = send_inc + ring.astype(jnp.int32)
        recv_inc = recv_inc + ring.astype(jnp.int32)

        rd = (is_ar & ~big) | is_bar
        peer = ranks ^ (1 << jnp.minimum(vm.rnd, 30))
        rd_ok = rd & (peer < P)
        dst = dst.at[..., 0].set(jnp.where(rd_ok, peer, dst[..., 0]))
        size = jnp.where(rd_ok, jnp.maximum(a0.astype(jnp.float32), 8.0), size)
        send_inc = send_inc + rd_ok.astype(jnp.int32)
        recv_inc = recv_inc + rd_ok.astype(jnp.int32)

        # BCAST (root a0, size a1): binomial over relative ranks
        is_bc = opc == OP["BCAST"]
        rel = (ranks - a0) % P
        pow2 = 1 << jnp.minimum(vm.rnd, 30)
        bc_send = is_bc & (rel < pow2) & (rel + pow2 < P)
        bc_dst = (rel + pow2 + a0) % P
        dst = dst.at[..., 0].set(jnp.where(bc_send, bc_dst, dst[..., 0]))
        size = jnp.where(bc_send, a1.astype(jnp.float32), size)
        send_inc = send_inc + bc_send.astype(jnp.int32)
        bc_recv = is_bc & (rel >= pow2) & (rel < 2 * pow2)
        recv_inc = recv_inc + bc_recv.astype(jnp.int32)

        # apply entry
        dst = jnp.where(enter[..., None], dst, -1)
        vm = vm._replace(
            emitted=vm.emitted | enter,
            busy_until=busy,
            send_need=vm.send_need + jnp.where(enter, send_inc, 0),
            recv_need=vm.recv_need + jnp.where(enter, recv_inc, 0),
        )
        return vm, dst, size

    # ------------------------------------------------------------------
    # the tick (batched: every leaf carries the member dim B)
    # ------------------------------------------------------------------
    def _n_rounds(opc, a0, a1, P, logp):
        ring = opc == OP["ALLREDUCE"]
        big = a0 >= 4096
        return jnp.where(
            ring, jnp.where(big, 2 * (P - 1), logp),
            jnp.where(
                (opc == OP["BCAST"]) | (opc == OP["BARRIER"]), logp,
                jnp.where(opc == OP["SCATTER"], (P - 2) // MAXE + 1, 1),
            ),
        )

    def tick_batched(state: SimState, t_cap=jnp.inf, stop_m=None) -> SimState:
        # ``t_cap`` clamps the PDES time skip (step 7) for windowed runs:
        # it enters the wake-up min exactly like a pending job's start, so
        # a window boundary at an arrival time leaves the tick trajectory
        # bit-identical to an uninterrupted run with that job in the table.
        # ``stop_m`` (B,) freezes members that reached their window event
        # (run_window): a stopped member must not tick past its arrival /
        # completion boundary while batch-mates are still advancing.
        #
        # Each phase runs under ``jax.named_scope("tick/<phase>")``; the
        # scopes change HLO metadata only, and ``Engine.phase_map`` reads
        # them back to name the compiled program's instructions.
        jt = state.jobs
        t = state.t  # (B,)
        B = t.shape[0]
        pool, metrics, rng = state.pool, state.metrics, state.rng
        with jax.named_scope("tick/loop"):
            # per-member freeze mask: finished / horizon-capped members
            # must not mutate (bit-identity with their own B=1 run). The
            # mask is threaded through every write instead of
            # double-buffering the whole state — a full-state select per
            # tick is what made batching memory-bound.
            live_m = (t < horizon_us) & ~(
                jnp.all(state.vms.done, axis=(1, 2))
                & ~jnp.any(pool.active, axis=1)
            )
            if stop_m is not None:
                live_m = live_m & ~stop_m

        # --- 0. runtime fault mask -> effective per-link bandwidth ---
        # (B, L): the member's link factors times both endpoint routers'
        # health factors. Healthy members multiply by exact 1.0, so their
        # trajectories stay bit-identical to a fault-free engine.
        with jax.named_scope("tick/faults"):
            flt = state.faults
            rf = flt.router_factor  # (B, R)
            eff_f = (
                flt.link_bw_factor
                * rf[:, link_srcr_l] * rf[:, link_dstr_l]
            )  # (B, L)
            bw_run = jnp.concatenate(
                [bw_base[None, :] * eff_f,
                 jnp.ones((B, 1), jnp.float32)], axis=1,
            )  # (B, L+1) with the dummy row

        # --- 1. VM entry + emission + injection (one stacked pass) ---
        with jax.named_scope("tick/emit"):
            vms, dst, sizes = vm_emit(jt, state.vms, t, live_m)
            fired = jnp.any(dst >= 0, axis=(2, 3))  # (B, J)

            # per-job rng offsets reproduce the per-job-loop draw
            # schedule: each *fired* job advanced the stream by its
            # P*MAXE candidates.
            adv = (
                (jt.P * MAXE).astype(jnp.uint32) * fired.astype(jnp.uint32)
            )  # (B, J)
            base = rng[:, None] + jnp.cumsum(adv, axis=1) - adv  # exclusive
            rng_jobs = rng + jnp.sum(adv, axis=1)

            ur_state = state.ur
            rng2 = rng_jobs
            injecting = jnp.any(fired, axis=1)  # (B,)
            if ur_state is not None:
                fire = (t[:, None] >= ur_state.next_t) & live_m[:, None]
                injecting = injecting | jnp.any(fire, axis=1)
            any_inject = jnp.any(injecting)

        # injection hides behind one real cond over the whole batch:
        # pure-drain ticks (the majority) skip the demand scatter AND all
        # route computation. Inside, only the candidates that fired are
        # gathered, routed and written, packed in emission order into
        # passes of ``cand_width``; a member or job that did not fire adds
        # nothing
        # (rng schedules are handled outside via ``fired``).
        def do_inject(args):
            pool, metrics = args
            # link demand (outstanding bytes per link) from the
            # PRE-injection pool — the job and the UR candidates all route
            # against this same snapshot (the historical tick-start value).
            valid = (pool.routes >= 0) & pool.active[:, :, None]
            lidx = jnp.where(valid, pool.routes, L)  # dummy L
            demand = _flat_add(
                jnp.zeros((B, L + 1), jnp.float32), lidx,
                jnp.broadcast_to(pool.bytes_rem[:, :, None], lidx.shape)
                * valid,
            )
            # failed links: infinite demand steers adaptive routes around
            # them (MIN ignores demand and honestly stalls); +0.0 when
            # healthy, so the add is a bit-exact no-op.
            demand = demand.at[:, :L].add(
                jnp.where(eff_f > 0.0, 0.0, 1e18)
            )
            demand_f = demand.reshape(-1)  # (B * (L+1),)

            def route(src_node, dst_node, rand):
                W = src_node.shape[1]
                offs = jnp.repeat(jnp.arange(B, dtype=jnp.int32) * (L + 1), W)
                routes, hops = route_fn(
                    T, src_node.reshape(-1), dst_node.reshape(-1),
                    rand.reshape(-1).astype(jnp.int32) & 0x7FFFFFFF,
                    demand_f, adaptive, demand_offsets=offs,
                )
                return routes.reshape(B, W, -1), hops.reshape(B, W)

            dst_f = dst.reshape(B, N)
            r2n_f = jt.r2n.reshape(B, J * Pmax)
            mask = dst_f >= 0
            if ur_state is not None:
                mask = jnp.concatenate([mask, fire], axis=1)

            def cands(idx):
                at = jnp.minimum(idx, N - 1)
                jr = at // MAXE  # job * Pmax + rank
                job = jr // Pmax
                dst_rank = jnp.take_along_axis(dst_f, at, axis=1)
                c = Cands(
                    src_rank=jr % Pmax, dst_rank=dst_rank,
                    src_node=jnp.take_along_axis(r2n_f, jr, axis=1),
                    dst_node=jnp.take_along_axis(
                        r2n_f, job * Pmax + jnp.maximum(dst_rank, 0), axis=1),
                    size=jnp.take_along_axis(
                        sizes.reshape(B, J * Pmax), jr, axis=1),
                    app=job,
                    # the per-job rng schedule: job j's candidate p*MAXE+e
                    # draws from base[j] + p*MAXE + e
                    rand=_hash(jnp.take_along_axis(base, job, axis=1)
                               + (at % (Pmax * MAXE)).astype(jnp.uint32)),
                )
                if ur_state is None:
                    return c
                r = jnp.clip(idx - N, 0, Pu - 1)  # the UR source's rank
                ru = r.astype(jnp.uint32)
                rnd = _hash(
                    jnp.take_along_axis(ur_state.count, r, axis=1)
                    .astype(jnp.uint32) * jnp.uint32(9781)
                    + ru + rng_jobs[:, None]
                )
                u = Cands(
                    src_rank=r,
                    dst_rank=jnp.zeros_like(r),  # not tracked
                    src_node=jnp.take_along_axis(state.ur_nodes, r, axis=1),
                    dst_node=(rnd % jnp.uint32(n_nodes)).astype(jnp.int32),
                    size=jnp.full(r.shape, float(ur.size_bytes), jnp.float32),
                    app=jnp.full(r.shape, J, jnp.int32),
                    rand=_hash(rng_jobs[:, None] + ru),
                )
                return Cands(*(jnp.where(idx >= N, b, a)
                               for a, b in zip(c, u)))

            pool, can, passes = inject_fired(
                pool, t, mask, cand_width, cands, route, net.hop_latency_us)
            # per-app bytes this tick (job candidates are job-major
            # blocks; the UR source is one app)
            inj_bytes = jnp.where(can[:, :N], jnp.broadcast_to(
                sizes[:, :, :, None], (B, J, Pmax, MAXE)).reshape(B, N), 0.0)
            peak = jnp.max(inj_bytes.reshape(B, J, -1).sum(axis=2), axis=1)
            if ur_state is not None:
                peak = jnp.maximum(peak, jnp.where(
                    can[:, N:], jnp.float32(ur.size_bytes), 0.0).sum(axis=1))
            metrics = metrics._replace(
                peak_inject=jnp.maximum(metrics.peak_inject, peak))
            return pool, metrics, passes

        with jax.named_scope("tick/inject"):
            pool, metrics, inject_passes = jax.lax.cond(
                any_inject, do_inject, lambda a: a + (jnp.int32(0),),
                (pool, metrics)
            )
        drained_active = pool.active  # what the drain pass sees

        if ur_state is not None:
            with jax.named_scope("tick/emit"):
                rng2 = rng_jobs + jnp.uint32(Pu) * jnp.any(
                    fire, axis=1).astype(jnp.uint32)
                ur_state = URState(
                    next_t=jnp.where(
                        fire, ur_state.next_t + ur.interval_us,
                        ur_state.next_t
                    ),
                    count=ur_state.count + fire.astype(jnp.int32),
                )

        # --- 2-3. fused drain tick: demand -> fair share -> drain ->
        # delivery, plus per-link byte counters (kernels/drain_tick.py),
        # over the live slots only: packed into the smallest rung of
        # M, M/2, ..., M/16 that holds every member's live slots (the top
        # rung is the full-pool pass), results put back per slot ---
        with jax.named_scope("tick/drain"):
            (new_rem, _rate, delivered, lb_delta, rw_delta,
             drain_rung) = KOPS.drain_tick_live(
                pool.routes, pool.bytes_rem, pool.active, pool.job,
                pool.min_arrive, t, jnp.float32(dt), bw_run, link_dstr,
                n_apps=n_apps, n_routers=R, use_pallas=use_pallas,
                interpret=kernel_interpret,
            )
            # horizon-frozen members may still carry in-flight messages:
            # their drain results are discarded (the freeze in place of a
            # state select)
            new_rem = jnp.where(live_m[:, None], new_rem, pool.bytes_rem)
            delivered = delivered & live_m[:, None]
            link_bytes = metrics.link_bytes + lb_delta * live_m[:, None]
            router_win = metrics.router_win + rw_delta * live_m[:, None, None]

        # --- latency metrics ---
        with jax.named_scope("tick/latency"):
            lat = (t[:, None] + dt) - pool.inject_t  # delivered at tick end
            ratio = math.log(net.latency_hist_ratio)
            bins = jnp.clip(
                (jnp.log(jnp.maximum(lat / net.latency_hist_lo_us, 1e-6))
                 / ratio),
                0, BINS - 1,
            ).astype(jnp.int32)
            app_of = pool.job
            d32 = delivered.astype(jnp.int32)
            lat_hist = _flat_add(
                metrics.lat_hist,
                jnp.where(delivered, app_of, 0) * BINS
                + jnp.where(delivered, bins, 0),
                d32,
            )
            lat_sum = _flat_add(
                metrics.lat_sum, app_of, jnp.where(delivered, lat, 0.0))
            lat_cnt = _flat_add(metrics.lat_cnt, app_of, d32)
            lat_min = _flat_min(
                metrics.lat_min, app_of, jnp.where(delivered, lat, jnp.inf))
            lat_max = _flat_max(
                metrics.lat_max, app_of, jnp.where(delivered, lat, -jnp.inf))

            # full-fidelity (app, link-level) histograms (compiled in only
            # when requested; ``delivered`` is already live_m-gated above)
            hist_st = state.hist
            if hist is not None:
                msg_lvl = jnp.max(
                    jnp.where(
                        pool.routes >= 0,
                        hist_link_level[jnp.clip(pool.routes, 0, L)], 0,
                    ),
                    axis=-1,
                )  # (B, M)
                hist_st = update_hist(
                    hist_st, hist,
                    lat=lat, delivered=delivered, app=app_of, level=msg_lvl,
                )

        # --- 4. delivery notifications -> VMs (UR id J is dropped) ---
        with jax.named_scope("tick/deliver"):
            notify = delivered & (pool.job < J)
            sd = _flat_add(
                vms.send_done, pool.job * Pmax + pool.src_rank,
                notify.astype(jnp.int32), valid=notify,
            )
            rd = _flat_add(
                vms.recv_done, pool.job * Pmax + pool.dst_rank,
                notify.astype(jnp.int32), valid=notify,
            )
            vms = vms._replace(send_done=sd, recv_done=rd)

            # free delivered slots
            freed = delivered
            kf = jnp.cumsum(freed.astype(jnp.int32), axis=1) - 1
            pos = pool.free_top[:, None] + kf
            free_stack = _flat_set(
                pool.free_stack, pos,
                jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32), (B, M)),
                valid=freed,
            )
            pool = pool._replace(
                active=pool.active & ~delivered,
                bytes_rem=new_rem,
                free_stack=free_stack,
                free_top=pool.free_top + freed.sum(axis=1),
            )

        # --- 5. VM completion / advance (one stacked pass) ---
        with jax.named_scope("tick/advance"):
            row = jnp.take_along_axis(jt.ops, vms.pc[:, :, :, None], axis=2)
            opc, a0, a1 = row[..., 0], row[..., 1], row[..., 2]
            P = jt.P[:, :, None]
            nr = _n_rounds(opc, a0, a1, P, jt.logp[:, :, None])
            tdt = t[:, None, None] + dt
            ready = vms.emitted & ~vms.done & (tdt >= vms.busy_until)
            sat = ((vms.send_done >= vms.send_need)
                   & (vms.recv_done >= vms.recv_need))
            # IP2P / LOG / RESET never block; COMPUTE blocks on busy only
            nonblock = (
                (opc == OP["IP2P"]) | (opc == OP["LOG"])
                | (opc == OP["RESET"]) | (opc == OP["COMPUTE"])
            )
            complete = ready & (sat | nonblock) & live_m[:, None, None]
            is_comm = ~(
                (opc == OP["COMPUTE"]) | (opc == OP["LOG"])
                | (opc == OP["RESET"]) | (opc == OP["END"])
            )
            blocked = (
                vms.emitted & ~vms.done & ~complete & (tdt >= vms.busy_until)
                & is_comm & live_m[:, None, None]
            )
            comm_time = vms.comm_time + jnp.where(blocked, dt, 0.0)

            rnd2 = jnp.where(complete, vms.rnd + 1, vms.rnd)
            advance = complete & (rnd2 >= nr)
            pc2 = jnp.where(advance, vms.pc + 1, vms.pc)
            rnd2 = jnp.where(advance, 0, rnd2)
            emitted2 = vms.emitted & ~complete
            opc_next = jnp.take_along_axis(
                jt.ops, pc2[:, :, :, None], axis=2)[..., 0]
            done2 = vms.done | (opc_next == OP["END"])
            vms = vms._replace(
                pc=pc2, rnd=rnd2, emitted=emitted2, done=done2,
                comm_time=comm_time
            )

        # --- 6. window rotation (per member) ---
        with jax.named_scope("tick/window"):
            win_t = jnp.floor((t + dt) / net.window_us).astype(jnp.int32)
            rotate = (win_t > metrics.win_idx) & live_m  # (B,)
            wi = jnp.minimum(metrics.win_idx, W - 1)
            wins_flat = metrics.router_wins.reshape(B * W, n_apps, R)
            wrow = jnp.where(
                rotate, wi + jnp.arange(B, dtype=jnp.int32) * W, B * W)
            router_wins = wins_flat.at[wrow].set(
                router_win, mode="drop"
            ).reshape(metrics.router_wins.shape)
            router_win = jnp.where(rotate[:, None, None], 0.0, router_win)
            win_idx = metrics.win_idx + rotate.astype(jnp.int32)

        metrics = metrics._replace(
            lat_hist=lat_hist, lat_sum=lat_sum, lat_cnt=lat_cnt,
            lat_min=lat_min, lat_max=lat_max,
            link_bytes=link_bytes, router_win=router_win,
            router_wins=router_wins, win_idx=win_idx,
        )

        # --- 7. event-driven time skip (PDES hybrid): when the network is
        # empty and every live rank is inside a COMPUTE delay (or its job
        # has not arrived yet), jump to the earliest wake-up (clamped to
        # the next metrics window).
        with jax.named_scope("tick/skip"):
            any_active = jnp.any(pool.active, axis=1)  # (B,)
            started = t[:, None] >= jt.start  # (B, J)
            live_r = ~vms.done
            can_act = jnp.any(
                started[:, :, None] & live_r & ~vms.emitted, axis=(1, 2)
            ) | jnp.any(
                live_r & vms.emitted & (vms.busy_until <= tdt), axis=(1, 2))
            waiting_busy = live_r & vms.emitted & (vms.busy_until > tdt)
            min_busy = jnp.min(
                jnp.where(waiting_busy, vms.busy_until, jnp.inf), axis=(1, 2)
            )
            # a job still pending arrival wakes the sim at its start time
            pend = ~started & jnp.any(live_r, axis=2)
            min_busy = jnp.minimum(
                min_busy, jnp.min(jnp.where(pend, jt.start, jnp.inf), axis=1)
            )
            # windowed runs: the window cap is a wake-up too (a job about
            # to be admitted there); inert at the default t_cap=inf
            min_busy = jnp.minimum(min_busy, jnp.asarray(t_cap, jnp.float32))
            if ur_state is not None:
                min_busy = jnp.minimum(
                    min_busy, jnp.min(ur_state.next_t, axis=1))
            next_window = (win_idx.astype(jnp.float32) + 1.0) * net.window_us
            skip_to = jnp.minimum(min_busy, next_window)
            idle = ~any_active & ~can_act & jnp.isfinite(skip_to)
            # windowed runs only (t_cap finite): a member whose last job
            # just completed must not jump ahead — the scheduler reads its
            # ``t`` as "now" when starting queued jobs on the freed nodes.
            # Inert at t_cap=inf: such a member's run loop exits before the
            # next tick, so the jump was never observable.
            all_done_m = jnp.all(vms.done, axis=(1, 2)) & ~any_active
            idle = idle & ~(
                all_done_m & jnp.isfinite(jnp.asarray(t_cap, jnp.float32))
            )
            t_new = jnp.where(idle, jnp.maximum(t + dt, skip_to), t + dt)
            t_out = jnp.where(live_m, t_new, t)
            rng_out = jnp.where(live_m, rng2 + jnp.uint32(1), rng)

        # --- 8. always-on tick counters (per member, live ticks only) ---
        with jax.named_scope("tick/counters"):
            live_i = live_m.astype(jnp.int32)
            inject_i = (injecting & live_m).astype(jnp.int32)
            n_active = jnp.sum(drained_active, axis=1, dtype=jnp.int32)
            metrics = metrics._replace(
                ticks=metrics.ticks + live_i,
                inject_ticks=metrics.inject_ticks + inject_i,
                active_slot_ticks=metrics.active_slot_ticks
                + (n_active * live_i).astype(jnp.float32),
                peak_active=jnp.maximum(
                    metrics.peak_active, n_active * live_i),
                drain_rung_ticks=metrics.drain_rung_ticks
                + (jnp.arange(n_rungs) == drain_rung).astype(jnp.int32)
                * live_i[:, None],
                inject_passes=metrics.inject_passes
                + inject_passes * inject_i,
            )

        # --- 9. sim-plane probes (compiled in only when requested) ---
        probes_st = state.probes
        if probes is not None:
            with jax.named_scope("tick/probes"):
                probes_st = sample_probes(
                    probes_st, probes,
                    t_new=t_out, live_m=live_m, ticks=metrics.ticks,
                    link_bytes=metrics.link_bytes,
                    pool_active=pool.active, pool_job=pool.job,
                    pool_inject_t=pool.inject_t, free_top=pool.free_top,
                    level_mask=probe_level_mask, level_bw=probe_level_bw,
                    n_apps=n_apps, pool_size=M,
                )

        return SimState(
            t=t_out, vms=vms, ur=ur_state, pool=pool,
            metrics=metrics, rng=rng_out,
            jobs=jt, ur_nodes=state.ur_nodes, probes=probes_st,
            hist=hist_st, faults=state.faults,
        )

    # ------------------------------------------------------------------
    def init_state(
        seed: int = 1,
        placements: Optional[Sequence[np.ndarray]] = None,
        start_us: Optional[Sequence[float]] = None,
        jobs_override: Optional[Sequence[JobSpec]] = None,
        rank_slowdown_override: Optional[Sequence[np.ndarray]] = None,
        faults: Optional[FaultState] = None,
    ) -> SimState:
        """Build one member's initial state; every vmap-able knob lives here.

        ``placements`` (jobs' rank2node arrays, plus UR's as the final
        entry when a UR source exists) overrides the build-time
        placements; ``start_us`` overrides per-job arrival offsets;
        ``seed`` sets the engine RNG (routing tiebreaks + UR
        destinations); ``jobs_override`` swaps in a different job set that
        fits the engine's capacity envelope (ragged campaigns);
        ``faults`` sets the member's runtime fault mask (a
        :class:`repro.netsim.faults.FaultState`; default healthy, or the
        deprecated build-time ``link_down`` shim). Stack member states
        along a new leading axis and pass the batch straight to ``run`` —
        one call simulates the whole ensemble, members with *different
        failure patterns* included.
        """
        js = list(jobs_override) if jobs_override is not None else list(jobs)
        slow = rank_slowdown_override
        if slow is None and jobs_override is None:
            slow = rank_slowdown
        table = pack_jobs(
            js, cap,
            placements=placements[: len(js)] if placements is not None else None,
            start_us=start_us,
            job_start_us=job_start_us if jobs_override is None else None,
            rank_slowdown=slow,
        )
        P_np = np.asarray(table.P)
        ops_np = np.asarray(table.ops)
        ranks = np.arange(Pmax, dtype=np.int32)[None, :]
        done0 = (ranks >= P_np[:, None]) | (
            ops_np[:, 0, 0] == OP["END"]
        )[:, None]

        def z(dt_=jnp.int32):
            return jnp.zeros((J, Pmax), dt_)

        vms = VMState(
            pc=z(), rnd=z(), emitted=jnp.zeros((J, Pmax), bool),
            busy_until=jnp.zeros((J, Pmax), jnp.float32),
            send_need=z(), send_done=z(), recv_need=z(), recv_done=z(),
            comm_time=jnp.zeros((J, Pmax), jnp.float32),
            done=jnp.asarray(done0),
        )
        ur_state = None
        ur_nodes = None
        if ur is not None:
            ur_state = URState(
                next_t=jnp.full((Pu,), float(ur.start_us), jnp.float32),
                count=jnp.zeros((Pu,), jnp.int32),
            )
            ur_nodes = (
                jnp.asarray(placements[len(js)], jnp.int32)
                if placements is not None and len(placements) > len(js)
                else ur_r2n
            )
        pool = PoolState(
            active=jnp.zeros((M,), bool),
            src_rank=jnp.zeros((M,), jnp.int32),
            dst_rank=jnp.zeros((M,), jnp.int32),
            job=jnp.zeros((M,), jnp.int32),
            size=jnp.zeros((M,), jnp.float32),
            bytes_rem=jnp.zeros((M,), jnp.float32),
            inject_t=jnp.zeros((M,), jnp.float32),
            min_arrive=jnp.zeros((M,), jnp.float32),
            routes=jnp.full((M, RW), -1, jnp.int32),
            free_stack=jnp.arange(M, dtype=jnp.int32),
            free_top=jnp.int32(M),
            dropped=jnp.int32(0),
        )
        metrics = Metrics(
            lat_hist=jnp.zeros((n_apps, BINS), jnp.int32),
            lat_sum=jnp.zeros((n_apps,), jnp.float32),
            lat_min=jnp.full((n_apps,), jnp.inf, jnp.float32),
            lat_max=jnp.full((n_apps,), -jnp.inf, jnp.float32),
            lat_cnt=jnp.zeros((n_apps,), jnp.int32),
            link_bytes=jnp.zeros((L + 1,), jnp.float32),
            router_win=jnp.zeros((n_apps, R), jnp.float32),
            router_wins=jnp.zeros((W, n_apps, R), jnp.float32),
            win_idx=jnp.int32(0),
            peak_inject=jnp.float32(0.0),
            ticks=jnp.int32(0),
            inject_ticks=jnp.int32(0),
            active_slot_ticks=jnp.float32(0.0),
            peak_active=jnp.int32(0),
            drain_rung_ticks=jnp.zeros((n_rungs,), jnp.int32),
            inject_passes=jnp.int32(0),
        )
        if faults is None:
            flt = FaultState(
                link_bw_factor=jnp.asarray(default_link_factor),
                router_factor=jnp.ones((R,), jnp.float32),
            )
        else:
            flt = FaultState(
                link_bw_factor=jnp.asarray(
                    faults.link_bw_factor, jnp.float32),
                router_factor=jnp.asarray(
                    faults.router_factor, jnp.float32),
            )
            if flt.link_bw_factor.shape != (L,) \
                    or flt.router_factor.shape != (R,):
                raise ValueError(
                    f"faults shapes {flt.link_bw_factor.shape}/"
                    f"{flt.router_factor.shape} do not match fabric "
                    f"(L={L}, R={R})")
        return SimState(
            t=jnp.float32(0.0), vms=vms, ur=ur_state, pool=pool,
            metrics=metrics, rng=jnp.uint32(seed),
            jobs=table, ur_nodes=ur_nodes,
            probes=(
                init_probes(probes, probe_n_levels, n_apps)
                if probes is not None else None
            ),
            hist=(
                init_hist(hist, n_apps, hist_n_levels)
                if hist is not None else None
            ),
            faults=flt,
        )

    def all_done(state: SimState):
        return jnp.all(state.vms.done, axis=(1, 2)) & ~jnp.any(
            state.pool.active, axis=1
        )

    def live(s: SimState):
        return (s.t < horizon_us) & ~all_done(s)

    # the batched while loop keeps stepping until *every* member finishes;
    # tick_batched's live_m mask freezes finished members in place (no
    # whole-state double-buffer select), keeping each bit-identical to its
    # own B=1 run while stragglers tick on.
    def any_live(s: SimState):
        with jax.named_scope("tick/loop"):
            return jnp.any(live(s))

    @jax.jit
    def run_batched(state: SimState) -> SimState:
        return jax.lax.while_loop(any_live, tick_batched, state)

    def done_slots(s: SimState):
        """(B,) count of fully-done job slots (vacant slots count too)."""
        return jnp.sum(jnp.all(s.vms.done, axis=2), axis=1)

    # one scheduling window: advance until virtual time reaches ``t_stop``
    # (the next trace arrival) or a job slot completes — then hand control
    # back to the host so it can retire/admit slots. ``t_stop`` is a traced
    # scalar or a per-member (B,) vector — each member is capped by its
    # OWN stop time (the cap broadcasts through the PDES skip min), which
    # is what lets the lock-step batched scheduler advance every trace
    # cell to its own next event in one call. Every window of a trace run
    # shares one jit cache entry per t_stop shape. Per-member: a member
    # that reached its own window event freezes in place while batch-mates
    # tick on (the stop condition is monotone — a frozen member stays
    # frozen), so batched windowed runs keep each member bit-identical to
    # its own B=1 windows.
    @jax.jit
    def run_window_batched(state: SimState, t_stop) -> SimState:
        t_stop = jnp.asarray(t_stop, jnp.float32)
        n0 = done_slots(state)

        def stopped(s):
            with jax.named_scope("tick/loop"):
                return ~(live(s) & (s.t < t_stop) & (done_slots(s) <= n0))

        def running(s):
            with jax.named_scope("tick/loop"):
                return ~jnp.all(stopped(s))

        return jax.lax.while_loop(
            running,
            lambda s: tick_batched(s, t_stop, stop_m=stopped(s)),
            state,
        )

    def _member_window(fn):
        def wrapper(state: SimState, t_stop):
            if state.t.ndim == 0:
                batched = jax.tree_util.tree_map(lambda x: x[None], state)
                out = fn(batched, t_stop)
                return jax.tree_util.tree_map(lambda x: x[0], out)
            return fn(state, t_stop)

        return wrapper

    def lower(state: SimState, t_stop=None):
        # shapes in, as a member or a batch: the run program (run_window's
        # when ``t_stop`` is given) lowered exactly as ``run`` calls it
        if len(state.t.shape) == 0:
            state = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct((1,) + tuple(x.shape),
                                               x.dtype), state)
        if t_stop is None:
            return run_batched.lower(state)
        return run_window_batched.lower(state, t_stop)

    return Engine(
        init_state=init_state,
        run=_member_batched(run_batched),
        tick=_member_batched(tick_batched),
        run_window=_member_window(run_window_batched),
        capacity=cap,
        _lower=lower,
    )


# ---------------------------------------------------------------------------
# process-wide engine cache: one compiled engine per (capacity envelope,
# system config). Job tables are runtime data, so every execution path —
# single scenarios, batched/ragged campaigns, windowed scheduler runs —
# that asks for the same envelope + config shares one jit cache entry.
# ---------------------------------------------------------------------------

_ENGINE_CACHE: "OrderedDict[Tuple, Engine]" = OrderedDict()
_ENGINE_CACHE_STATS = {"hits": 0, "misses": 0, "builds": 0, "evictions": 0}
# LRU bound on the cache: ``None`` (default) is unbounded — the historical
# batch-CLI behavior — while long-lived processes (the repro.union.serve
# server) cap it so memory stays bounded over arbitrarily many distinct
# engine configs. Rebuild after eviction is bit-identical: the key holds
# every compile-relevant input (pinned by tests/test_store.py).
_ENGINE_CACHE_MAX: Optional[int] = (
    int(os.environ["REPRO_ENGINE_CACHE_MAX"])
    if os.environ.get("REPRO_ENGINE_CACHE_MAX") else None
)


def _cache_gauges() -> None:
    """Mirror cache size/evictions into the process metrics registry
    (lazy import: obs must stay importable without netsim and vice
    versa)."""
    from repro.obs.metrics import get_registry

    reg = get_registry()
    reg.gauge("engine_cache_size",
              "compiled engines held by the process-wide cache").set(
        len(_ENGINE_CACHE))
    limit = reg.gauge("engine_cache_limit",
                      "LRU cap on the engine cache (0 = unbounded)")
    limit.set(0 if _ENGINE_CACHE_MAX is None else _ENGINE_CACHE_MAX)


def _evict_to_limit() -> None:
    from repro.obs.metrics import get_registry

    ev = get_registry().counter(
        "engine_cache_evictions",
        "engines dropped by the LRU cap (rebuilt on next request)")
    while (_ENGINE_CACHE_MAX is not None
           and len(_ENGINE_CACHE) > _ENGINE_CACHE_MAX):
        _ENGINE_CACHE.popitem(last=False)
        _ENGINE_CACHE_STATS["evictions"] += 1
        ev.inc()


def set_engine_cache_limit(limit: Optional[int]) -> Optional[int]:
    """Cap the process-wide engine cache at ``limit`` entries (LRU
    eviction; ``None`` removes the cap). Returns the previous limit.
    Evicted engines rebuild bit-identically on their next request — the
    cache key carries every compile-relevant input — so a cap trades
    recompilation time for bounded memory in long-running servers."""
    global _ENGINE_CACHE_MAX
    if limit is not None and limit < 1:
        raise ValueError("engine cache limit must be >= 1 (or None)")
    prev = _ENGINE_CACHE_MAX
    _ENGINE_CACHE_MAX = limit
    _evict_to_limit()
    _cache_gauges()
    return prev


def engine_cache_key(
    topo: Fabric,
    *,
    routing: str = "ADP",
    ur: Optional[URSpec] = None,
    net: Optional[NetConfig] = None,
    pool_size: Optional[int] = None,
    horizon_us: float = 500_000.0,
    capacity: EngineCapacity,
    use_pallas: bool = False,
    probes: Optional[ProbeConfig] = None,
    hist: Optional[HistConfig] = None,
) -> Tuple:
    """Everything baked into a compiled engine besides the job tables.

    The fabric contributes :func:`repro.netsim.fabric.fabric_key` — its
    family name plus defining parameters — so two fabrics with identical
    capacity envelopes never share a compiled engine. The UR source
    contributes only its *shape* (rank count and traffic parameters) —
    its placement is overridable per member at init time. ``probes`` and
    ``hist`` are part of the key: an observed engine is a separate
    compiled entry, so requesting probes or histograms never perturbs
    the plain engines other callers hold. Failure patterns are
    deliberately **absent**: the fault mask is runtime data
    (``init_state(faults=...)``), so a whole failure campaign shares one
    compiled engine (pinned by the cache-counter test in
    tests/test_faults.py).
    """
    net = net or NetConfig()
    ur_key = None if ur is None else (
        int(ur.rank2node.shape[0]), float(ur.size_bytes),
        float(ur.interval_us), float(ur.start_us),
    )
    return (
        fabric_key(topo), routing.upper() in ("ADP", "ADAPTIVE"), ur_key,
        net, int(pool_size or net.pool_size), float(horizon_us), capacity,
        use_pallas, probes, hist,
    )


def get_engine(
    topo: Fabric,
    *,
    routing: str = "ADP",
    ur: Optional[URSpec] = None,
    net: Optional[NetConfig] = None,
    pool_size: Optional[int] = None,
    horizon_us: float = 500_000.0,
    capacity: EngineCapacity,
    use_pallas: bool = False,
    probes: Optional[ProbeConfig] = None,
    hist: Optional[HistConfig] = None,
) -> Engine:
    """A compiled engine from the process-wide cache (compile on miss).

    Cached engines are built with an **empty default job set** — callers
    must pass their jobs at init time (``init_state(jobs_override=...)``),
    and when a UR source exists, its per-member placement via the final
    ``placements`` entry. Fault injection is runtime data too
    (``init_state(faults=...)``): a failure campaign never forces a
    rebuild. :func:`build_engine` remains the uncached primitive for
    callers baking job-set defaults.
    """
    key = engine_cache_key(
        topo, routing=routing, ur=ur, net=net, pool_size=pool_size,
        horizon_us=horizon_us, capacity=capacity,
        use_pallas=use_pallas, probes=probes, hist=hist,
    )
    eng = _ENGINE_CACHE.get(key)
    if eng is not None:
        _ENGINE_CACHE_STATS["hits"] += 1
        _ENGINE_CACHE.move_to_end(key)  # LRU: a hit is a use
        return eng
    _ENGINE_CACHE_STATS["misses"] += 1
    _ENGINE_CACHE_STATS["builds"] += 1
    eng = build_engine(
        topo, [], routing=routing, ur=ur, net=net, pool_size=pool_size,
        horizon_us=horizon_us, capacity=capacity,
        use_pallas=use_pallas, probes=probes, hist=hist,
    )
    _ENGINE_CACHE[key] = eng
    _evict_to_limit()
    _cache_gauges()
    return eng


def engine_cache_stats() -> Dict[str, int]:
    """Hit/miss/eviction counters plus current size (and LRU limit, -1 =
    unbounded) of the process-wide cache."""
    return dict(
        _ENGINE_CACHE_STATS, size=len(_ENGINE_CACHE),
        limit=-1 if _ENGINE_CACHE_MAX is None else _ENGINE_CACHE_MAX,
    )


def clear_engine_cache() -> None:
    """Drop every cached engine (and its jit executables) and zero the
    counters — test isolation and long-lived-process memory control."""
    _ENGINE_CACHE.clear()
    _ENGINE_CACHE_STATS.update(hits=0, misses=0, builds=0, evictions=0)


# ---------------------------------------------------------------------------
# state accessors (the stacked layout's equivalent of the old per-job tuples)
# ---------------------------------------------------------------------------

def job_vm(state: SimState, ji: int) -> VMState:
    """Job ``ji``'s VM state of a member state, trimmed to its real ranks."""
    P = int(state.jobs.P[ji])
    return VMState(*[np.asarray(x[ji])[:P] for x in state.vms])


def job_done(state: SimState, ji: int) -> bool:
    return bool(np.asarray(job_vm(state, ji).done).all())


def member_state(batched_state: SimState, i: int) -> SimState:
    """Unstack member ``i`` of a batched state."""
    return jax.tree_util.tree_map(lambda x: x[i], batched_state)


def stack_members(states: Sequence[SimState]) -> SimState:
    """Stack member states into one batch (leading member dim)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


# one program for every leaf and member where eager indexing or stacking
# would dispatch once a leaf and member: on a host driving four chips
# those dispatches took most of a call's placement and split
@jax.jit
def _stack(states):
    return stack_members(states)


@jax.jit
def _unstack(batch):
    return [member_state(batch, j) for j in range(batch.t.shape[0])]


def shard_members(states: Sequence[SimState], mesh: Mesh) -> SimState:
    """Stack member states into one batch sharded on its leading member
    axis over ``mesh`` (:func:`member_mesh`), ``len(states) / mesh.size``
    members a device, in member order."""
    return jax.device_put(_stack(list(states)), NamedSharding(
        mesh, PartitionSpec(MEMBER_AXIS)))


def split_members(batched_state: SimState) -> list:
    """Every member of a batched state, in member order, each left on
    the device that holds it: each device's members are unstacked by one
    program on that device."""
    leaves, tree = jax.tree_util.tree_flatten(batched_state)
    shards = [sorted(x.addressable_shards,
                     key=lambda s: s.index[0].start or 0) for x in leaves]
    out = []
    for local in zip(*shards):
        out += _unstack(tree.unflatten([s.data for s in local]))
    return out


# ---------------------------------------------------------------------------
# job-slot admit/retire API (the online scheduler's state surgery).
#
# These operate on a *member* state between engine windows, on the host:
# a vacant slot is one with ``start == inf`` (how both ``pack_jobs`` pads
# unused capacity and ``retire_job`` leaves a finished slot). A retired
# slot's VMs are all-done and its program is END-only, so it is provably
# inert to the remaining jobs' trajectories — the chained-window
# equivalence tests pin this.
# ---------------------------------------------------------------------------

def vacant_slots(state: SimState) -> np.ndarray:
    """Indices of vacant job slots of a member state (``start == inf``)."""
    return np.flatnonzero(np.isinf(np.asarray(state.jobs.start)))


def slot_done(state: SimState, slot: int) -> bool:
    """Every rank of ``slot`` has reached END (its program finished)."""
    return bool(np.asarray(state.vms.done[slot]).all())


def slot_in_flight(state: SimState, slot: int) -> bool:
    """``slot`` still owns active pool messages (e.g. trailing IP2P
    traffic after its VMs finished). A slot must fully drain before it
    can be recycled — a reused slot id would misroute delivery
    notifications into the new tenant's counters."""
    return bool(
        (np.asarray(state.pool.active) & (np.asarray(state.pool.job) == slot))
        .any()
    )


class WindowView(NamedTuple):
    """Everything the scheduler host loop reads between engine windows,
    fetched in **one** device transfer (:func:`window_host_view`).

    Shapes are per-member (``(J,)``/``(J, Pmax)``) for a member state or
    carry a leading batch dim (``(B, J)``/``(B, J, Pmax)``) for a batched
    state; arrays are host numpy, so per-slot indexing is free."""

    t: np.ndarray          # () | (B,)       float32 virtual clock
    slot_done: np.ndarray  # (J,) | (B, J)   every rank at END
    in_flight: np.ndarray  # (J,) | (B, J)   slot owns active pool msgs
    lat_sum: np.ndarray    # per-slot latency sums (metrics app axis)
    lat_cnt: np.ndarray    # per-slot delivered-message counts
    comm_time: np.ndarray  # (J, Pmax) | (B, J, Pmax) per-rank comm time

    def member(self, i: int) -> "WindowView":
        """Member ``i``'s rows of a batched view (no further transfers)."""
        return WindowView(*(a[i] for a in self))


def window_host_view(state: SimState) -> WindowView:
    """Fetch the scheduler's whole per-window host view in one transfer.

    Replaces the per-slot ``slot_done``/``slot_in_flight``/metrics reads
    of the window loop (each a separate device fetch) with a single
    ``jax.device_get`` of the six leaves the host actually consumes; the
    slot masks are then computed host-side in numpy. Works on member and
    batched states alike — the lock-step batched scheduler fetches one
    view per window **round**, covering every member.
    """
    t, done, active, job, lat_sum, lat_cnt, comm = jax.device_get((
        state.t, state.vms.done, state.pool.active, state.pool.job,
        state.metrics.lat_sum, state.metrics.lat_cnt, state.vms.comm_time,
    ))
    slot_done_m = done.all(axis=-1)
    J = done.shape[-2]
    in_flight = np.zeros(slot_done_m.shape, bool)
    sel = active & (job < J)  # UR traffic uses the extra app id J
    if slot_done_m.ndim == 1:
        in_flight[job[sel]] = True
    else:
        b_idx = np.broadcast_to(
            np.arange(job.shape[0])[:, None], job.shape)[sel]
        in_flight[b_idx, job[sel]] = True
    return WindowView(t, slot_done_m, in_flight, lat_sum, lat_cnt, comm)


def admit_jobs(
    state: SimState, admits: Sequence[Tuple[int, int, JobSpec]]
) -> SimState:
    """Write many jobs into vacant slots of a **batched** state at once.

    ``admits`` is ``[(member, slot, spec), ...]`` with distinct
    ``(member, slot)`` pairs; payload rows are assembled host-side and
    applied with one scatter per state leaf, so the device cost of a
    lock-step scheduler round is O(leaves), independent of how many
    members admit. Envelope checks run here; *vacancy* checks are the
    caller's — the batched scheduler's host bookkeeping is authoritative
    (fetching per-slot occupancy back would reintroduce exactly the
    per-member round-trips this API removes).
    """
    if not admits:
        return state
    jt = state.jobs
    J, OPmax = jt.ops.shape[-3], jt.ops.shape[-2]
    Pmax = jt.r2n.shape[-1]
    K = len(admits)
    mi = np.empty((K,), np.int32)
    si = np.empty((K,), np.int32)
    ops_rows = np.zeros((K, OPmax, 4), np.int32)
    ops_rows[:, :, 0] = OP["END"]
    grid_rows = np.zeros((K, OPmax, 4), np.int32)
    p_vals = np.empty((K,), np.int32)
    logp_vals = np.empty((K,), np.int32)
    r2n_rows = np.zeros((K, Pmax), np.int32)
    start_vals = np.empty((K,), np.float32)
    done_rows = np.empty((K, Pmax), bool)
    for k, (m, slot, spec) in enumerate(admits):
        sk = spec.skeleton
        if not 0 <= slot < J:
            raise ValueError(f"slot {slot} outside envelope Jmax={J}")
        if sk.n_ranks > Pmax or sk.n_ops > OPmax:
            raise ValueError(
                f"job {spec.name!r} ({sk.n_ranks} ranks, {sk.n_ops} ops) "
                f"exceeds engine capacity (Pmax={Pmax}, OPmax={OPmax})"
            )
        mi[k], si[k] = m, slot
        ops_rows[k, : sk.n_ops] = sk.ops
        grid_rows[k, : sk.n_ops] = sk.grid
        p_vals[k] = sk.n_ranks
        logp_vals[k] = _ceil_log2(sk.n_ranks)
        r2n_rows[k, : sk.n_ranks] = np.asarray(spec.rank2node, np.int32)
        start_vals[k] = np.float32(spec.start_us)
        done_rows[k] = np.arange(Pmax) >= sk.n_ranks
    jobs = jt._replace(
        ops=jt.ops.at[mi, si].set(ops_rows),
        grid=jt.grid.at[mi, si].set(grid_rows),
        P=jt.P.at[mi, si].set(p_vals),
        logp=jt.logp.at[mi, si].set(logp_vals),
        r2n=jt.r2n.at[mi, si].set(r2n_rows),
        slowdown=jt.slowdown.at[mi, si].set(np.ones((K, Pmax), np.float32)),
        start=jt.start.at[mi, si].set(start_vals),
    )
    z_i = np.zeros((K, Pmax), np.int32)
    z_f = np.zeros((K, Pmax), np.float32)
    z_b = np.zeros((K, Pmax), bool)
    vms = state.vms
    vms = vms._replace(
        pc=vms.pc.at[mi, si].set(z_i), rnd=vms.rnd.at[mi, si].set(z_i),
        emitted=vms.emitted.at[mi, si].set(z_b),
        busy_until=vms.busy_until.at[mi, si].set(z_f),
        send_need=vms.send_need.at[mi, si].set(z_i),
        send_done=vms.send_done.at[mi, si].set(z_i),
        recv_need=vms.recv_need.at[mi, si].set(z_i),
        recv_done=vms.recv_done.at[mi, si].set(z_i),
        comm_time=vms.comm_time.at[mi, si].set(z_f),
        done=vms.done.at[mi, si].set(done_rows),
    )
    return state._replace(jobs=jobs, vms=vms)


def retire_jobs(
    state: SimState, retires: Sequence[Tuple[int, int]]
) -> SimState:
    """Vacate many ``(member, slot)`` pairs of a **batched** state at
    once — the multi-member mirror of :func:`retire_job`, one scatter per
    state leaf. Done/drained validation is the caller's (the lock-step
    scheduler just read both masks from :func:`window_host_view`)."""
    if not retires:
        return state
    jt = state.jobs
    OPmax = jt.ops.shape[-2]
    Pmax = jt.r2n.shape[-1]
    K = len(retires)
    mi = np.asarray([m for m, _ in retires], np.int32)
    si = np.asarray([s for _, s in retires], np.int32)
    ops_rows = np.zeros((K, OPmax, 4), np.int32)
    ops_rows[:, :, 0] = OP["END"]
    z_i = np.zeros((K, Pmax), np.int32)
    z_f = np.zeros((K, Pmax), np.float32)
    z_b = np.zeros((K, Pmax), bool)
    jobs = jt._replace(
        ops=jt.ops.at[mi, si].set(ops_rows),
        grid=jt.grid.at[mi, si].set(np.zeros((K, OPmax, 4), np.int32)),
        P=jt.P.at[mi, si].set(np.ones((K,), np.int32)),
        logp=jt.logp.at[mi, si].set(np.ones((K,), np.int32)),
        r2n=jt.r2n.at[mi, si].set(z_i),
        slowdown=jt.slowdown.at[mi, si].set(np.ones((K, Pmax), np.float32)),
        start=jt.start.at[mi, si].set(np.full((K,), np.inf, np.float32)),
    )
    vms = state.vms
    vms = vms._replace(
        pc=vms.pc.at[mi, si].set(z_i), rnd=vms.rnd.at[mi, si].set(z_i),
        emitted=vms.emitted.at[mi, si].set(z_b),
        busy_until=vms.busy_until.at[mi, si].set(z_f),
        send_need=vms.send_need.at[mi, si].set(z_i),
        send_done=vms.send_done.at[mi, si].set(z_i),
        recv_need=vms.recv_need.at[mi, si].set(z_i),
        recv_done=vms.recv_done.at[mi, si].set(z_i),
        comm_time=vms.comm_time.at[mi, si].set(z_f),
        done=vms.done.at[mi, si].set(np.ones((K, Pmax), bool)),
    )
    return state._replace(jobs=jobs, vms=vms)


def occupied_node_mask(state: SimState, n_nodes: int) -> np.ndarray:
    """(n_nodes,) bool — nodes held by non-vacant job slots.

    The free-node accounting the scheduler places against: incremental
    placement (``place_jobs(..., occupied=mask)``) draws only from the
    complement.
    """
    occ = np.zeros((n_nodes,), bool)
    start = np.asarray(state.jobs.start)
    P = np.asarray(state.jobs.P)
    r2n = np.asarray(state.jobs.r2n)
    for j in np.flatnonzero(np.isfinite(start)):
        occ[r2n[j, : int(P[j])]] = True
    return occ


def admit_job(
    state: SimState, slot: int, spec: JobSpec, checked: bool = True
) -> SimState:
    """Write ``spec`` into vacant job ``slot`` of a member state.

    Resets the slot's program/placement/arrival tables and its VM rows
    (padded ranks born done), leaving every other slot untouched. The
    admitted job idles until ``spec.start_us`` of virtual time.
    ``checked=False`` skips the vacancy validation (a device fetch) for
    callers whose own bookkeeping tracks slot occupancy — the scheduler's
    hot loop.
    """
    jt = state.jobs
    J, OPmax = jt.ops.shape[0], jt.ops.shape[1]
    Pmax = jt.r2n.shape[1]
    sk = spec.skeleton
    if not 0 <= slot < J:
        raise ValueError(f"slot {slot} outside envelope Jmax={J}")
    if checked and not np.isinf(float(jt.start[slot])):
        raise ValueError(f"slot {slot} is occupied (start="
                         f"{float(jt.start[slot])}); retire it first")
    if sk.n_ranks > Pmax or sk.n_ops > OPmax:
        raise ValueError(
            f"job {spec.name!r} ({sk.n_ranks} ranks, {sk.n_ops} ops) exceeds "
            f"engine capacity (Pmax={Pmax}, OPmax={OPmax})"
        )
    ops_row = np.zeros((OPmax, 4), np.int32)
    ops_row[:, 0] = OP["END"]
    ops_row[: sk.n_ops] = sk.ops
    grid_row = np.zeros((OPmax, 4), np.int32)
    grid_row[: sk.n_ops] = sk.grid
    r2n_row = np.zeros((Pmax,), np.int32)
    r2n_row[: sk.n_ranks] = np.asarray(spec.rank2node, np.int32)
    jobs = jt._replace(
        ops=jt.ops.at[slot].set(ops_row),
        grid=jt.grid.at[slot].set(grid_row),
        P=jt.P.at[slot].set(np.int32(sk.n_ranks)),
        logp=jt.logp.at[slot].set(np.int32(_ceil_log2(sk.n_ranks))),
        r2n=jt.r2n.at[slot].set(r2n_row),
        slowdown=jt.slowdown.at[slot].set(jnp.ones((Pmax,), jnp.float32)),
        start=jt.start.at[slot].set(np.float32(spec.start_us)),
    )
    done_row = np.arange(Pmax) >= sk.n_ranks
    vms = state.vms
    z_i = jnp.zeros((Pmax,), jnp.int32)
    z_f = jnp.zeros((Pmax,), jnp.float32)
    vms = vms._replace(
        pc=vms.pc.at[slot].set(z_i), rnd=vms.rnd.at[slot].set(z_i),
        emitted=vms.emitted.at[slot].set(jnp.zeros((Pmax,), bool)),
        busy_until=vms.busy_until.at[slot].set(z_f),
        send_need=vms.send_need.at[slot].set(z_i),
        send_done=vms.send_done.at[slot].set(z_i),
        recv_need=vms.recv_need.at[slot].set(z_i),
        recv_done=vms.recv_done.at[slot].set(z_i),
        comm_time=vms.comm_time.at[slot].set(z_f),
        done=vms.done.at[slot].set(jnp.asarray(done_row)),
    )
    return state._replace(jobs=jobs, vms=vms)


def retire_job(state: SimState, slot: int, checked: bool = True) -> SimState:
    """Vacate job ``slot``: END-only program, ``start=inf``, all-done VMs.

    The slot must have finished (``slot_done``) and drained
    (``not slot_in_flight``) — retiring earlier would let in-flight
    deliveries credit the next tenant. ``checked=False`` skips those two
    validations (each a device fetch) for callers that just read the
    masks from :func:`window_host_view`.
    """
    jt = state.jobs
    J, OPmax = jt.ops.shape[0], jt.ops.shape[1]
    Pmax = jt.r2n.shape[1]
    if not 0 <= slot < J:
        raise ValueError(f"slot {slot} outside envelope Jmax={J}")
    if checked and not slot_done(state, slot):
        raise ValueError(f"slot {slot} has unfinished ranks; cannot retire")
    if checked and slot_in_flight(state, slot):
        raise ValueError(
            f"slot {slot} still has in-flight messages; drain before retiring"
        )
    ops_row = np.zeros((OPmax, 4), np.int32)
    ops_row[:, 0] = OP["END"]
    jobs = jt._replace(
        ops=jt.ops.at[slot].set(ops_row),
        grid=jt.grid.at[slot].set(jnp.zeros((OPmax, 4), jnp.int32)),
        P=jt.P.at[slot].set(np.int32(1)),
        logp=jt.logp.at[slot].set(np.int32(1)),
        r2n=jt.r2n.at[slot].set(jnp.zeros((Pmax,), jnp.int32)),
        slowdown=jt.slowdown.at[slot].set(jnp.ones((Pmax,), jnp.float32)),
        start=jt.start.at[slot].set(np.float32(np.inf)),
    )
    vms = state.vms
    z_i = jnp.zeros((Pmax,), jnp.int32)
    vms = vms._replace(
        pc=vms.pc.at[slot].set(z_i), rnd=vms.rnd.at[slot].set(z_i),
        emitted=vms.emitted.at[slot].set(jnp.zeros((Pmax,), bool)),
        busy_until=vms.busy_until.at[slot].set(jnp.zeros((Pmax,), jnp.float32)),
        send_need=vms.send_need.at[slot].set(z_i),
        send_done=vms.send_done.at[slot].set(z_i),
        recv_need=vms.recv_need.at[slot].set(z_i),
        recv_done=vms.recv_done.at[slot].set(z_i),
        comm_time=vms.comm_time.at[slot].set(jnp.zeros((Pmax,), jnp.float32)),
        done=vms.done.at[slot].set(jnp.ones((Pmax,), bool)),
    )
    return state._replace(jobs=jobs, vms=vms)
