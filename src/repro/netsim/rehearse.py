"""Compile the engine for a described TPU, with no chip attached.

JAX can describe a TPU topology that is not attached
(``jax.experimental.topologies``) and compile for its devices. The TPU
compiler then refuses what the chip would refuse — an op Mosaic cannot
lower, a program that does not fit the device — at no chip time.
Nothing runs, so a compile that passes says nothing about results or
times. ``tests/test_tpu_compile.py`` keeps small cases of these compiles
in the suite (among them the Mosaic refusal of the Pallas drain
kernel); this module's ``__main__`` rehearses the paper's widths::

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.netsim.rehearse

It compiles, for one chip of a v5e:2x2, the jnp drain tick at the 2-D
dragonfly's widths, then ``engine.run`` and ``run_window`` for the
paper's workload1 on the 1-D dragonfly; and for all four chips, the
member-sharded run of 8 such members, 2 a chip (about four minutes on a
few cores).
"""
from __future__ import annotations

import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.kernels import ops as KOPS


# the chip this repo targets, as a 2x2 host
TOPOLOGY = "v5e:2x2"
# the paper's workload1: four apps plus uniform-random background
N_APPS = 5


def describe():
    """The described (not attached) ``TOPOLOGY``."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name=TOPOLOGY)


def one_chip(topo) -> SingleDeviceSharding:
    return SingleDeviceSharding(topo.devices[0])


def abstract(tree, sharding):
    """Shapes and dtypes of ``tree``'s leaves, placed on ``sharding``."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def compile_drain(sharding, fabric, *, pool_size: int = 65536,
                  use_pallas: bool = False):
    """The fused drain tick (``kernels.ops.drain_tick``) of one member at
    ``fabric``'s link and router counts; ``use_pallas`` compiles the
    Mosaic kernel."""
    B, M, K = 1, pool_size, fabric.route_width
    Lp = fabric.n_links + 1

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    args = (
        s((B, M, K), jnp.int32), s((B, M), jnp.float32),
        s((B, M), jnp.bool_), s((B, M), jnp.int32), s((B, M), jnp.float32),
        s((B,), jnp.float32), s((), jnp.float32), s((B, Lp), jnp.float32),
        s((Lp,), jnp.int32),
    )
    return KOPS.drain_tick.lower(
        *args, n_apps=N_APPS, n_routers=fabric.n_routers,
        use_pallas=use_pallas, interpret=False).compile()


def compile_run(eng, state, sharding):
    """``engine.run`` for ``state``'s shapes (member or batched)."""
    return jax.jit(eng.run).lower(abstract(state, sharding)).compile()


def compile_run_window(eng, state, sharding):
    """``engine.run_window`` with a per-member ``(B,)`` stop-time vector,
    the batched scheduler's call shape."""
    t_stop = jax.ShapeDtypeStruct(state.t.shape, jnp.float32,
                                  sharding=sharding)
    return jax.jit(eng.run_window).lower(
        abstract(state, sharding), t_stop).compile()


def compile_run_sharded(eng, state, topo):
    """``engine.run_sharded`` on a mesh of every chip of ``topo``, the
    batch ``state`` sharded on its member axis as ``union.run`` places
    it."""
    from repro.netsim.engine import MEMBER_AXIS

    mesh = Mesh(np.asarray(topo.devices), (MEMBER_AXIS,))
    sharding = NamedSharding(mesh, PartitionSpec(MEMBER_AXIS))
    return eng.run_sharded(mesh).lower(abstract(state, sharding)).compile()


def scenario_engine(sc, members: int = 1):
    """The engine ``union.run`` draws for a ``members``-ensemble of
    ``sc`` and the stacked initial states it runs."""
    from repro.netsim.engine import stack_members
    from repro.union import Experiment
    from repro.union.experiment import node_engine
    from repro.union.planner import plan

    exp = Experiment(name=sc.name, scenarios=[sc], members=members)
    (node,) = plan(exp).nodes
    eng, inits, _ = node_engine(node, exp)
    return eng, stack_members(inits)


def _report(name, fn):
    t0 = time.time()
    try:
        compiled = fn()
    except Exception as e:  # the refusal is the rehearsal's result
        print(f"{name}: REFUSED after {time.time() - t0:.1f}s: "
              f"{type(e).__name__}: {str(e).splitlines()[0]}", flush=True)
        return False
    mem = compiled.memory_analysis()
    print(f"{name}: compiled in {time.time() - t0:.1f}s, arguments "
          f"{mem.argument_size_in_bytes / 2**20:.1f} MiB, temporaries "
          f"{mem.temp_size_in_bytes / 2**20:.1f} MiB", flush=True)
    return True


def main(argv=None) -> int:
    import argparse

    from repro.netsim.topology import get_topology
    from repro.union.scenario import mix_scenario

    ap = argparse.ArgumentParser(prog="python -m repro.netsim.rehearse",
                                 description=__doc__.split("\n\n")[0])
    ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = describe()
    chip = one_chip(topo)
    print(f"described {TOPOLOGY}: "
          f"{next(iter(chip.device_set)).device_kind}")
    ok = _report("jnp drain tick, 2-D paper widths",
                 lambda: compile_drain(chip, get_topology("2d", "paper")))
    sc = mix_scenario("workload1", topo="1d", scale="paper", tick_us=5.0,
                      horizon_ms=1.0)
    eng, state = scenario_engine(sc, members=2)
    ok &= _report("engine.run, workload1 paper 1-D, 2 members",
                  lambda: compile_run(eng, state, chip))
    ok &= _report("engine.run_window, same envelope",
                  lambda: compile_run_window(eng, state, chip))
    eng, state = scenario_engine(sc, members=8)
    ok &= _report(f"engine.run_sharded, same envelope, 8 members over "
                  f"the {TOPOLOGY}",
                  lambda: compile_run_sharded(eng, state, topo))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
