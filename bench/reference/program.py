"""The statements of the Union DSL that the benchmark's jobs use (the
Union paper's Table III applications, and point-to-point sends), read
into a flat list of operations.

Supported lines (each may end in ``then``; ``#`` starts a comment):

    Require language version "1.5".
    <name> is "..." and comes from "--<flag>" with default <n>.
    Assert that "..." with num_tasks >= <n>.
    For <n or name> repetitions {   ...   }
    all tasks compute for <x> microseconds|milliseconds|seconds
    all tasks allreduce a <x> <unit> message
    all tasks exchange a <x> <unit> message with their neighbors in a AxBxC grid
    all tasks send a <x> <unit> message to task <r>
    task <r> multicasts a <x> <unit> message to all other tasks
    task <a> sends a <x> <unit> message to task <b>

Anything else is an error. Loops are unrolled. Sizes are rounded to
whole bytes and times to whole microseconds.

An operation is ``(kind, size, root, dims, peer)``: ``compute`` (size =
µs), ``allreduce``, ``exchange`` (``dims`` the grid), ``gather`` (every
other rank sends ``size`` bytes to ``root``), ``bcast`` (``root`` sends
``size`` bytes to all, binomial tree), ``p2p`` (``root`` sends ``size``
bytes to ``peer`` and waits for it to arrive).
"""
from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Optional, Tuple

UNITS = {"byte": 1, "bytes": 1, "kb": 1000, "mb": 1000**2, "gb": 1000**3,
         "kib": 1024, "mib": 1024**2, "gib": 1024**3}
TIME_UNITS = {"microseconds": 1.0, "microsecond": 1.0, "usecs": 1.0,
              "milliseconds": 1e3, "millisecond": 1e3, "ms": 1e3,
              "msecs": 1e3, "seconds": 1e6, "second": 1e6}


class Op(NamedTuple):
    kind: str
    size: int = 0
    root: int = 0
    dims: Tuple[int, ...] = ()
    peer: int = 0


_NUM = r"(\d+(?:\.\d+)?|[a-z_]\w*)"
_SIZE = _NUM + r" (\w+)"
_PATTERNS = [
    ("param", rf"([a-z_]\w*) is \".*\" and comes from \".*\" with default {_NUM}"),
    ("assert", r"assert that \".*\" with num_tasks >= (\d+)"),
    ("require", r"require language version \".*\""),
    ("for", rf"for {_NUM} repetitions {{"),
    ("end", r"}"),
    ("compute", rf"all tasks compute for {_NUM} (\w+)"),
    ("allreduce", rf"all tasks allreduce an? {_SIZE}(?: byte)? message"),
    ("exchange", rf"all tasks exchange an? {_SIZE}(?: byte)? message with "
                 r"their neighbors in an? ([\dx]+) grid"),
    ("gather", rf"all tasks send an? {_SIZE}(?: byte)? message to task (\d+)"),
    ("bcast", rf"task (\d+) multicasts an? {_SIZE}(?: byte)? message to all "
              r"other tasks"),
    ("p2p", rf"task (\d+) sends an? {_SIZE}(?: byte)? message to task (\d+)"),
]


def _statements(src: str) -> List[str]:
    out = []
    for line in src.splitlines():
        line = line.split("#", 1)[0].strip().lower().rstrip(".")
        line = re.sub(r"\s+then$", "", line).strip()
        if line:
            out.append(line)
    return out


def parse(src: str, n_ranks: int,
          overrides: Optional[Dict[str, float]] = None) -> List[Op]:
    """The unrolled operations of ``src`` run on ``n_ranks`` ranks."""
    env: Dict[str, float] = {"num_tasks": float(n_ranks)}
    stack: List[Tuple[int, List[Op]]] = [(1, [])]

    def num(tok: str) -> float:
        return env[tok] if tok in env else float(tok)

    for st in _statements(src):
        for kind, pat in _PATTERNS:
            m = re.fullmatch(pat, st)
            if m:
                break
        else:
            raise ValueError(f"unsupported statement: {st!r}")
        g = m.groups()
        body = stack[-1][1]
        if kind == "param":
            env[g[0]] = float(g[1])
            env.update({k: float(v) for k, v in (overrides or {}).items()
                        if k == g[0]})
        elif kind == "assert":
            if n_ranks < int(g[0]):
                raise ValueError(f"{n_ranks} ranks fail: {st!r}")
        elif kind == "for":
            stack.append((int(num(g[0])), []))
        elif kind == "end":
            reps, inner = stack.pop()
            stack[-1][1].extend(inner * reps)
        elif kind == "compute":
            body.append(Op("compute", int(round(num(g[0]) * TIME_UNITS[g[1]]))))
        elif kind in ("allreduce", "exchange", "gather"):
            size = int(round(num(g[0]) * UNITS[g[1]]))
            if kind == "exchange":
                dims = tuple(int(d) for d in g[2].split("x"))
                n = 1
                for d in dims:
                    n *= d
                if n != n_ranks:
                    raise ValueError(f"grid {dims} != {n_ranks} ranks")
                body.append(Op("exchange", size, dims=dims))
            elif kind == "gather":
                body.append(Op("gather", size, root=int(g[2])))
            else:
                body.append(Op("allreduce", size))
        elif kind == "bcast":
            body.append(Op("bcast", int(round(num(g[1]) * UNITS[g[2]])),
                           root=int(g[0])))
        elif kind == "p2p":
            body.append(Op("p2p", int(round(num(g[1]) * UNITS[g[2]])),
                           root=int(g[0]), peer=int(g[3])))
    if len(stack) != 1:
        raise ValueError("unbalanced braces")
    return stack[0][1]
