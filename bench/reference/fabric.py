"""The dragonfly of the Union paper's Table II, and its routes, in numpy.

Link numbering, which the per-link byte counters are compared by:
``[0, N)`` terminal-in (node -> its router, id = node), ``[N, 2N)``
terminal-out (router -> node, id = N + node), then every group's local
links (group-major, in (from, to) local-index order), then the global
links (source group, target group, channel).

A 1-D dragonfly joins every pair of routers of a group; a 2-D one joins
the routers of one row or one column of a ``rows x cols`` group. Router
``l`` of a group owns global channels ``l*h .. l*h+h-1``; channel ``k``
goes to target group ``k mod (G-1)``, skipping the group itself, and the
m-th channel from g to tg is wired to the m-th channel from tg to g.

Routes are ten link ids wide, -1 where unused:
``[term_in, l1a, l1b, g1, l2a, l2b, g2, l3a, l3b, term_out]``. A local
leg is one link (1-D), or on a 2-D group the direct link where one
exists and otherwise two, through the router in the source's row and
the target's column. MIN takes global channel ``rand mod lpp``; the
adaptive rule (UGAL) builds a Valiant path through group
``(rand // 7) mod G``, moved past the source and target groups, and
takes it when the minimal path's cost exceeds twice its own plus 1e-6.
A path's cost is the sum, over its links, of the bytes still to send on
that link divided by the link's bandwidth.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

F32 = np.float32
ROUTE_WIDTH = 10


@dataclass
class Dragonfly:
    variant: str
    G: int  # groups
    a: int  # routers per group
    p: int  # nodes per router
    h: int  # global channels per router
    rows: int
    cols: int
    n_nodes: int
    n_routers: int
    n_links: int
    lpp: int  # global links per ordered group pair
    local_link_id: np.ndarray  # (R, a) link from router r to local index l
    global_gw: np.ndarray  # (G, G, lpp) router owning channel m of g -> tg
    global_link_id: np.ndarray  # (G, G, lpp)
    link_dst_router: np.ndarray  # (L,)
    link_bw: np.ndarray  # (L,) float32 bytes per second


def build(variant: str, groups: int, routers_per_group: int,
          nodes_per_router: int, global_per_router: int, rows: int = 0,
          cols: int = 0, terminal_bw: float = 16 * 2**30,
          local_bw: float = 4.69 * 2**30,
          global_bw: float = 5.25 * 2**30) -> Dragonfly:
    G, a, p, h = groups, routers_per_group, nodes_per_router, global_per_router
    R, N = G * a, G * a * p
    if variant not in ("1d", "2d"):
        raise ValueError(f"unknown dragonfly variant {variant!r}")
    if variant == "2d" and rows * cols != a:
        raise ValueError(f"2-D group {rows}x{cols} != {a} routers")
    if (a * h) % (G - 1):
        raise ValueError("global channels do not divide over group pairs")
    lpp = a * h // (G - 1)

    li = np.arange(a)
    same_row = (li[:, None] // max(cols, 1)) == (li[None, :] // max(cols, 1))
    same_col = (li[:, None] % max(cols, 1)) == (li[None, :] % max(cols, 1))
    joined = ~np.eye(a, dtype=bool)
    if variant == "2d":
        joined &= same_row | same_col
    src_l, dst_l = np.nonzero(joined)  # row-major: (from, to) order
    n_local = src_l.size
    local_link_id = np.full((R, a), -1, np.int64)
    grp = np.arange(G)[:, None]
    ids = 2 * N + grp * n_local + np.arange(n_local)[None, :]
    local_link_id[(grp * a + src_l[None, :]).ravel(),
                  np.tile(dst_l, G)] = ids.ravel()
    local_dst = (grp * a + dst_l[None, :]).ravel()

    # channel k of group g -> target group (k mod (G-1)), skipping g
    k = np.arange(a * h)
    owner = k // h
    gw_local = np.empty((G, G, lpp), np.int64)
    for g in range(G):
        tg = k % (G - 1)
        tg = tg + (tg >= g)
        for t in range(G):
            if t != g:
                gw_local[g, t] = owner[tg == t]
    global_gw = np.full((G, G, lpp), -1, np.int64)
    global_link_id = np.full((G, G, lpp), -1, np.int64)
    glob_dst = []
    nxt = 2 * N + G * n_local
    for g in range(G):
        for t in range(G):
            if t == g:
                continue
            global_gw[g, t] = g * a + gw_local[g, t]
            global_link_id[g, t] = nxt + np.arange(lpp)
            glob_dst.append(t * a + gw_local[t, g])
            nxt += lpp
    term_router = np.arange(N) // p
    link_dst_router = np.concatenate(
        [term_router, term_router, local_dst, np.concatenate(glob_dst)])
    bw = np.concatenate([
        np.full(2 * N, terminal_bw), np.full(G * n_local, local_bw),
        np.full(nxt - 2 * N - G * n_local, global_bw)]).astype(F32)
    return Dragonfly(variant, G, a, p, h, rows, cols, N, R, nxt, lpp,
                     local_link_id, global_gw, global_link_id,
                     link_dst_router, bw)


def _local_leg(f: Dragonfly, r_from, r_to):
    """The links of the intra-group leg ``r_from -> r_to`` (-1 unused)."""
    l_to = r_to % f.a
    direct = f.local_link_id[r_from, l_to]
    same = r_from == r_to
    if f.variant == "1d":
        return np.where(same, -1, direct), np.full_like(direct, -1)
    corner_l = ((r_from % f.a) // f.cols) * f.cols + l_to % f.cols
    corner_r = (r_from // f.a) * f.a + corner_l
    has_direct = direct >= 0
    la = np.where(same, -1, np.where(has_direct, direct,
                                     f.local_link_id[r_from, corner_l]))
    lb = np.where(same | has_direct, -1, f.local_link_id[corner_r, l_to])
    return la, lb


def _global(f: Dragonfly, g_from, g_to, m):
    """Gateway router, link, and landing router of channel ``m``
    (pairs of a group with itself give -1 and are masked by callers)."""
    gw = f.global_gw[g_from, g_to, m]
    gl = f.global_link_id[g_from, g_to, m]
    return gw, gl, f.link_dst_router[np.maximum(gl, 0)]


def min_routes(f: Dragonfly, src, dst, rand):
    r_s, r_d = src // f.p, dst // f.p
    g_s, g_d = r_s // f.a, r_d // f.a
    gw, gl, r_b = _global(f, g_s, g_d, rand % f.lpp)
    same_group = g_s == g_d
    gw = np.where(same_group, r_s, gw)
    r_b = np.where(same_group, r_s, r_b)
    l1a, l1b = _local_leg(f, r_s, gw)
    l2a, l2b = _local_leg(f, r_b, r_d)
    la, lb = _local_leg(f, r_s, r_d)
    none = np.full_like(src, -1)
    return np.stack([
        src,
        np.where(same_group, la, l1a), np.where(same_group, lb, l1b),
        np.where(same_group, -1, gl),
        np.where(same_group, -1, l2a), np.where(same_group, -1, l2b),
        none, none, none, f.n_nodes + dst,
    ], axis=1)


def valiant_routes(f: Dragonfly, src, dst, g_i, rand):
    r_s, r_d = src // f.p, dst // f.p
    g_s, g_d = r_s // f.a, r_d // f.a
    gw1, gl1, r_mid = _global(f, g_s, g_i, rand % f.lpp)
    gw2, gl2, r_b = _global(f, g_i, g_d, (rand // f.lpp) % f.lpp)
    ok = (g_s != g_i) & (g_i != g_d)  # else the route is never taken
    gw1, r_mid = np.where(ok, gw1, r_s), np.where(ok, r_mid, r_s)
    gw2, r_b = np.where(ok, gw2, r_s), np.where(ok, r_b, r_d)
    l1a, l1b = _local_leg(f, r_s, gw1)
    l2a, l2b = _local_leg(f, r_mid, gw2)
    l3a, l3b = _local_leg(f, r_b, r_d)
    return np.stack([src, l1a, l1b, gl1, l2a, l2b, gl2, l3a, l3b,
                     f.n_nodes + dst], axis=1)


def route_cost(routes, demand, bw):
    """Sum over a route's links of outstanding bytes / bandwidth."""
    idx = np.maximum(routes, 0)
    per = np.where(routes >= 0, demand[idx] / bw[idx], bw.dtype.type(0))
    return per.sum(axis=1, dtype=per.dtype)


def routes(f: Dragonfly, src, dst, rand, demand, adaptive: bool,
           dtype=F32):
    """``(routes (n, 10), hops (n,))`` for messages ``src -> dst`` nodes;
    ``rand`` is a non-negative int per message, ``demand`` the bytes
    still to send per link (``dtype``)."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    rand = np.asarray(rand, np.int64)
    r = min_routes(f, src, dst, rand)
    if adaptive and src.size:
        g_s, g_d = src // f.p // f.a, dst // f.p // f.a
        g_i = (rand // 7) % f.G
        g_i = np.where(g_i == g_s, (g_i + 1) % f.G, g_i)
        g_i = np.where(g_i == g_d, (g_i + 1) % f.G, g_i)
        g_i = np.where(g_i == g_s, (g_i + 1) % f.G, g_i)
        v = valiant_routes(f, src, dst, g_i, rand)
        demand, bw = demand.astype(dtype), f.link_bw.astype(dtype)
        c_min = route_cost(r, demand, bw)
        c_val = route_cost(v, demand, bw)
        take = (g_s != g_d) & (c_min > dtype(2.0) * c_val + dtype(1e-6))
        r = np.where(take[:, None], v, r)
    return r, (r >= 0).sum(axis=1)
