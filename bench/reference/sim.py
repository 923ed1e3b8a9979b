"""A plain numpy simulation of one ensemble member: the Union network
model, tick by tick, written from its description and not from the
engine's code.

The model (the Union paper's tensor-timestepped CODES adaptation):

- Every rank runs its job's operations in order. Entering an operation
  (or a round of a multi-round collective) emits up to ``MAXE`` messages
  and raises the rank's cumulative send/receive targets; the rank may
  leave the round at the end of a tick once its cumulative deliveries
  reach both targets (compute rounds: once the clock passes their end).
- Emitted messages take a route at injection (``fabric.routes``; the
  adaptive rule reads the bytes still to send on each link before this
  tick's injections) and wait ``hop_us`` per link before they may be
  delivered.
- Each tick every in-flight message moves at the least fair share of
  its links (link bandwidth divided by the messages on the link) and is
  delivered when nothing is left to send; its latency is the end of the
  tick less its injection time.
- A uniform-random background source sends ``size`` bytes from each of
  its ranks to a hashed destination node every ``interval`` us.
- When no message is in flight and no rank can act, the clock jumps to
  the next compute end, arrival, background send or metrics-window
  boundary.

Random draws use the model's own integer hash of a per-member counter,
so a seed gives the same routes as in the program. ``dtype`` sets the
precision of the byte and rate arithmetic (the clock stays float32): the
program states float32; bfloat16 is the control.
"""
from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Optional, Sequence

import numpy as np

from . import fabric as FB
from .program import Op

F32 = np.float32
U32 = np.uint32
MAXE = 8  # messages a rank may emit per round
KIND = {"compute": 0, "exchange": 1, "allreduce": 2, "gather": 3,
        "bcast": 4, "p2p": 5, "end": 6}


def hash32(x):
    x = np.asarray(x, U32)
    x = (x ^ (x >> U32(16))) * U32(0x45D9F3B)
    x = (x ^ (x >> U32(16))) * U32(0x45D9F3B)
    return x ^ (x >> U32(16))


def engine_seed(seed: int) -> int:
    """A member's seed -> the model's initial draw counter."""
    return (seed * 2654435761 + 1) % 2**32


def place(f: FB.Dragonfly, sizes: Sequence[int], policy: str,
          seed: int) -> List[np.ndarray]:
    """RN: nodes in a random order; RG: whole groups in a random order.
    Each job takes the next ``size`` nodes."""
    rng = np.random.default_rng(seed)
    if policy == "RN":
        order = rng.permutation(f.n_nodes)
    elif policy == "RG":
        npg = f.a * f.p
        order = (rng.permutation(f.G)[:, None] * npg
                 + np.arange(npg)[None, :]).ravel()
    else:
        raise ValueError(f"unknown placement {policy!r}")
    if sum(sizes) > f.n_nodes:
        raise ValueError("jobs outsize the machine")
    bounds = np.cumsum([0] + list(sizes))
    return [order[bounds[i]:bounds[i + 1]] for i in range(len(sizes))]


class Job(NamedTuple):
    ops: Sequence[Op]
    ranks: int
    start_us: float = 0.0


class UR(NamedTuple):
    ranks: int
    size_bytes: float
    interval_us: float
    start_us: float = 0.0


class _Rank:
    """One job's operation table and its ranks' state."""

    def __init__(self, job: Job, nodes):
        ops = list(job.ops) + [Op("end")]
        P = self.P = job.ranks
        self.kind = np.array([KIND[o.kind] for o in ops])
        self.size = np.array([o.size for o in ops], np.int64)
        self.root = np.array([o.root for o in ops], np.int64)
        self.peer = np.array([o.peer for o in ops], np.int64)
        self.ndim = np.array([len(o.dims) for o in ops], np.int64)
        self.dims = np.ones((len(ops), 4), np.int64)
        for i, o in enumerate(ops):
            self.dims[i, :len(o.dims)] = o.dims
        self.logp = max(1, math.ceil(math.log2(max(P, 2))))
        self.start = F32(job.start_us)
        self.nodes = np.asarray(nodes, np.int64)
        z = np.zeros(P, np.int64)
        self.pc, self.rnd = z.copy(), z.copy()
        self.send_need, self.send_done = z.copy(), z.copy()
        self.recv_need, self.recv_done = z.copy(), z.copy()
        self.emitted = np.zeros(P, bool)
        self.done = np.full(P, self.kind[0] == KIND["end"])
        self.busy = np.zeros(P, F32)
        self.rank = np.arange(P)

    def emit(self, t):
        """Enter the current round: ``(dst (P, MAXE), size (P,))``."""
        P, r = self.P, self.rank
        enter = ~self.emitted & ~self.done & (t >= self.start)
        k, a = self.kind[self.pc], self.size[self.pc]
        root, rnd = self.root[self.pc], self.rnd
        dst = np.full((P, MAXE), -1, np.int64)
        size = np.zeros(P, F32)
        s_inc = np.zeros(P, np.int64)
        r_inc = np.zeros(P, np.int64)

        comp = enter & (k == KIND["compute"])
        self.busy = np.where(comp, F32(t) + a.astype(F32), self.busy)

        ex = k == KIND["exchange"]
        if ex.any():
            dims = self.dims[self.pc]
            nd = self.ndim[self.pc]
            stride = np.concatenate(
                [np.ones((P, 1), np.int64), np.cumprod(dims[:, :3], axis=1)],
                axis=1)
            coord = (r[:, None] // stride) % dims
            for d in range(4):
                use = ex & (d < nd)
                for s, step in ((2 * d, 1), (2 * d + 1, -1)):
                    if s < MAXE:
                        nb = r + (((coord[:, d] + step) % dims[:, d])
                                  - coord[:, d]) * stride[:, d]
                        dst[:, s] = np.where(use, nb, dst[:, s])
            size = np.where(ex, a.astype(F32), size)
            n = np.where(ex, 2 * np.minimum(nd, 4), 0)
            s_inc += n
            r_inc += n

        ar = k == KIND["allreduce"]
        ring = ar & (a >= 4096)
        dst[:, 0] = np.where(ring, (r + 1) % P, dst[:, 0])
        size = np.where(ring, np.ceil(a.astype(F32) / F32(P)), size)
        s_inc += ring
        r_inc += ring
        rd = ar & (a < 4096)
        peer = r ^ (1 << np.minimum(rnd, 30))
        rd_ok = rd & (peer < P)
        dst[:, 0] = np.where(rd_ok, peer, dst[:, 0])
        size = np.where(rd_ok, np.maximum(a.astype(F32), F32(8)), size)
        s_inc += rd_ok
        r_inc += rd_ok

        ga = k == KIND["gather"]
        g_send = ga & (r != root)
        dst[:, 0] = np.where(g_send, root, dst[:, 0])
        size = np.where(g_send, a.astype(F32), size)
        s_inc += g_send
        r_inc += np.where(ga & (r == root), P - 1, 0)

        p2p = k == KIND["p2p"]
        p_send = p2p & (r == root)
        dst[:, 0] = np.where(p_send, self.peer[self.pc], dst[:, 0])
        size = np.where(p_send, a.astype(F32), size)
        s_inc += p_send
        r_inc += p2p & (r == self.peer[self.pc])

        bc = k == KIND["bcast"]
        rel = (r - root) % P
        pow2 = 1 << np.minimum(rnd, 30)
        b_send = bc & (rel < pow2) & (rel + pow2 < P)
        dst[:, 0] = np.where(b_send, (rel + pow2 + root) % P, dst[:, 0])
        size = np.where(b_send, a.astype(F32), size)
        s_inc += b_send
        r_inc += bc & (rel >= pow2) & (rel < 2 * pow2)

        dst[~enter] = -1
        self.emitted |= enter
        self.send_need += np.where(enter, s_inc, 0)
        self.recv_need += np.where(enter, r_inc, 0)
        return dst, size

    def rounds(self):
        k, a = self.kind[self.pc], self.size[self.pc]
        return np.where(k == KIND["allreduce"],
                        np.where(a >= 4096, 2 * (self.P - 1), self.logp),
                        np.where(k == KIND["bcast"], self.logp, 1))

    def advance(self, t, dt):
        tdt = F32(t) + F32(dt)
        k = self.kind[self.pc]
        ready = self.emitted & ~self.done & (tdt >= self.busy)
        sat = ((self.send_done >= self.send_need)
               & (self.recv_done >= self.recv_need))
        complete = ready & (sat | (k == KIND["compute"]))
        rnd = self.rnd + complete
        adv = complete & (rnd >= self.rounds())
        self.pc = self.pc + adv
        self.rnd = np.where(adv, 0, rnd)
        self.emitted &= ~complete
        self.done |= self.kind[self.pc] == KIND["end"]


class Member:
    """One member's network and ranks: its jobs' tables and the
    background source, advanced a tick at a time."""

    def __init__(self, f: FB.Dragonfly, ranks: Sequence[_Rank], *,
                 seed: int, horizon_us: float, tick_us: float,
                 pool_size: int, hop_us: float, window_us: float,
                 adaptive: bool = True, ur: Optional[UR] = None,
                 ur_nodes=None, dtype=F32):
        D = self.D = dtype
        self.f, self.ur, self.adaptive = f, ur, adaptive
        self.horizon, self.dt = F32(horizon_us), F32(tick_us)
        self.pool_size, self.hop_us, self.window_us = (
            pool_size, hop_us, window_us)
        self.R = list(ranks)
        J = len(self.R)
        self.bw = f.link_bw.astype(D)
        self.t = F32(0)
        self.rng = engine_seed(seed)  # the draw counter, a 32-bit integer
        self.win_idx = 0
        if ur is not None:
            self.ur_nodes = np.asarray(ur_nodes, np.int64)
            self.ur_next = np.full(ur.ranks, F32(ur.start_us), F32)
            self.ur_count = np.zeros(ur.ranks, np.int64)
        # in-flight messages, in pool-slot order; slots come off a stack
        # (the last freed is the first reused), and every sum over them
        # runs in slot order, in ``dtype``
        self.pool = dict(
            slot=np.zeros(0, np.int64),
            src=np.zeros(0, np.int64), dst=np.zeros(0, np.int64),
            app=np.zeros(0, np.int64), rem=np.zeros(0, D),
            inject=np.zeros(0, F32), arrive=np.zeros(0, F32),
            routes=np.zeros((0, FB.ROUTE_WIDTH), np.int64))
        self.free = np.arange(pool_size)
        self.free_top = pool_size
        self.link_bytes = np.zeros(f.n_links, D)
        self.lat_sum = np.zeros(J + 1, D)
        self.lat_cnt = np.zeros(J + 1, np.int64)
        self.lat_min = np.full(J + 1, np.inf, F32)
        self.lat_max = np.full(J + 1, -np.inf, F32)
        self.dropped = 0
        self.ticks = 0

    def live(self) -> bool:
        idle = (all(r.done.all() for r in self.R)
                and self.pool["src"].size == 0)
        return bool(self.t < self.horizon) and not idle

    def _per_link(self, values):
        """Sum ``values`` (one per message) over each message's links."""
        rt = self.pool["routes"]
        ok = rt >= 0
        out = np.zeros(self.f.n_links, self.D)
        np.add.at(out, rt[ok], np.broadcast_to(values[:, None], rt.shape)[ok])
        return out

    def _add(self, new):
        take = min(new["src"].size, self.free_top)
        self.dropped += new["src"].size - take
        new = {k: v[:take] for k, v in new.items()}
        new["slot"] = self.free[self.free_top - 1 - np.arange(take)]
        self.free_top -= take
        order = np.argsort(np.concatenate([self.pool["slot"], new["slot"]]),
                           kind="stable")
        for k in self.pool:
            self.pool[k] = np.concatenate([self.pool[k], new[k]])[order]

    def tick(self):
        """One tick."""
        f, D, R, dt, t = self.f, self.D, self.R, self.dt, self.t
        J, N, L = len(R), f.n_nodes, f.n_links
        ur = self.ur
        self.ticks += 1
        # 1. rounds entered now, and their messages
        emitted = [r.emit(t) for r in R]
        fired = [bool((d >= 0).any()) for d, _ in emitted]
        base, acc = [], self.rng
        for r, fi in zip(R, fired):
            base.append(U32(acc))
            acc = (acc + r.P * MAXE * fi) % 2**32
        rng_jobs = U32(acc)
        fire = t >= self.ur_next if ur is not None else None
        if any(fired) or (ur is not None and fire.any()):
            dem = self._per_link(self.pool["rem"])
            for j, (r, (d, sz)) in enumerate(zip(R, emitted)):
                p, e = np.nonzero(d >= 0)
                if not p.size:
                    continue
                rand = hash32(base[j] + (p * MAXE + e).astype(U32))
                rand = rand.astype(np.int64) & 0x7FFFFFFF
                rt, hops = FB.routes(f, r.nodes[p], r.nodes[d[p, e]], rand,
                                     dem, self.adaptive, D)
                self._add(dict(
                    src=p, dst=d[p, e], app=np.full(p.size, j),
                    rem=sz[p].astype(D), inject=np.full(p.size, t, F32),
                    arrive=t + hops.astype(F32) * F32(self.hop_us),
                    routes=rt))
            if ur is not None and fire.any():
                i = np.flatnonzero(fire)
                pu = np.arange(ur.ranks, dtype=U32)
                dst = (hash32(self.ur_count.astype(U32) * U32(9781) + pu
                              + rng_jobs) % U32(N)).astype(np.int64)[i]
                rand = hash32(rng_jobs + pu).astype(np.int64)[i] & 0x7FFFFFFF
                rt, hops = FB.routes(f, self.ur_nodes[i], dst, rand, dem,
                                     self.adaptive, D)
                self._add(dict(
                    src=i, dst=np.zeros(i.size, np.int64),
                    app=np.full(i.size, J),
                    rem=np.full(i.size, ur.size_bytes, D),
                    inject=np.full(i.size, t, F32),
                    arrive=t + hops.astype(F32) * F32(self.hop_us),
                    routes=rt))
        rng2 = acc
        if ur is not None:
            rng2 = (acc + ur.ranks * bool(fire.any())) % 2**32
            self.ur_next = np.where(fire, self.ur_next + F32(ur.interval_us),
                                    self.ur_next)
            self.ur_count = self.ur_count + fire

        # 2. every message moves at the least fair share of its links
        pool = self.pool
        rt = pool["routes"]
        ok = rt >= 0
        if rt.shape[0]:
            n_l = np.bincount(rt[ok], minlength=L).astype(D)
            share = self.bw / np.maximum(n_l, D(1)) * D(1e-6)
            rate = np.where(ok, share[np.maximum(rt, 0)], D(np.inf)).min(1)
            drain = np.minimum(rate * D(dt), pool["rem"])
            pool["rem"] = (pool["rem"] - drain).astype(D)
            self.link_bytes = (self.link_bytes
                               + self._per_link(drain)).astype(D)
            dlv = (pool["rem"] <= D(1e-6)) & (t >= pool["arrive"])
            app = pool["app"][dlv]
            lat = ((t + dt) - pool["inject"][dlv]).astype(F32)
            np.add.at(self.lat_sum, app, lat.astype(D))
            self.lat_cnt += np.bincount(app, minlength=J + 1)
            np.minimum.at(self.lat_min, app, lat)
            np.maximum.at(self.lat_max, app, lat)
            for j, r in enumerate(R):
                mine = dlv & (pool["app"] == j)
                np.add.at(r.send_done, pool["src"][mine], 1)
                np.add.at(r.recv_done, pool["dst"][mine], 1)
            freed = pool["slot"][dlv]
            self.free[self.free_top:self.free_top + freed.size] = freed
            self.free_top += freed.size
            for k in pool:
                pool[k] = pool[k][~dlv]

        # 3. ranks leave finished rounds
        for r in R:
            r.advance(t, dt)

        # 4. metrics windows, and the jump over idle time
        tdt = F32(t + dt)
        if int(np.floor(tdt / F32(self.window_us))) > self.win_idx:
            self.win_idx += 1
        wake = F32((self.win_idx + 1) * self.window_us)
        can_act = False
        for r in R:
            live = ~r.done
            started = t >= r.start
            can_act |= bool((started & live & ~r.emitted).any())
            can_act |= bool((live & r.emitted & (r.busy <= tdt)).any())
            waiting = live & r.emitted & (r.busy > tdt)
            if waiting.any():
                wake = min(wake, r.busy[waiting].min())
            if not started and live.any():
                wake = min(wake, r.start)
        if ur is not None:
            wake = min(wake, self.ur_next.min())
        if pool["src"].size == 0 and not can_act:
            self.t = F32(max(tdt, wake))
        else:
            self.t = tdt
        self.rng = (rng2 + 1) % 2**32

    def result(self) -> Dict[str, np.ndarray]:
        R = self.R
        return dict(
            t=np.float32(self.t), ticks=self.ticks, dropped=self.dropped,
            link_bytes=self.link_bytes.astype(np.float64),
            lat_sum=self.lat_sum.astype(np.float64), lat_cnt=self.lat_cnt,
            lat_min=self.lat_min.astype(np.float64),
            lat_max=self.lat_max.astype(np.float64),
            pc=[r.pc for r in R], send_done=[r.send_done for r in R],
            recv_done=[r.recv_done for r in R],
            ur_count=(self.ur_count if self.ur is not None
                      else np.zeros(0, np.int64)),
        )


def simulate(f: FB.Dragonfly, jobs: Sequence[Job], placements, *,
             seed: int, horizon_us: float, tick_us: float, pool_size: int,
             hop_us: float, window_us: float, adaptive: bool = True,
             ur: Optional[UR] = None, dtype=F32) -> Dict[str, np.ndarray]:
    """Run one member from ``t = 0`` to ``horizon_us`` (or until every job
    ends with nothing in flight); ``placements`` holds each job's nodes
    and, last, the background source's. Returns the final counters."""
    m = Member(f, [_Rank(j, placements[i]) for i, j in enumerate(jobs)],
               seed=seed, horizon_us=horizon_us, tick_us=tick_us,
               pool_size=pool_size, hop_us=hop_us, window_us=window_us,
               adaptive=adaptive, ur=ur,
               ur_nodes=placements[len(jobs)] if ur is not None else None,
               dtype=dtype)
    while m.live():
        m.tick()
    return m.result()
