"""The main path compiles for one TPU v5e chip, with no chip attached.

A v5e:2x2 topology is described (not attached) and the chip's compiler
runs on this CPU: what it refuses here, the chip would refuse. Nothing
runs, so these tests say nothing about results or times. The paper-scale
engine compile (about a minute) stays out of the suite:
``python -m repro.netsim.rehearse`` runs it.
"""
import pytest

import jax

from repro.netsim import rehearse
from repro.netsim.topology import get_topology
from repro.union.scenario import mix_scenario


@pytest.fixture(scope="module")
def topo():
    try:
        return rehearse.describe()
    except Exception as e:
        pytest.skip(f"no {rehearse.TOPOLOGY} topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return rehearse.one_chip(topo)


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def workload1_small():
    sc = mix_scenario("workload1", topo="1d", scale="small", tick_us=5.0,
                      horizon_ms=1.0)
    return rehearse.scenario_engine(sc, members=1)


def test_jnp_drain_compiles_at_2d_paper_widths(one_chip):
    fabric = get_topology("2d", "paper")
    assert (fabric.n_links, fabric.n_routers, fabric.route_width) == (
        73920, 2112, 10)
    compiled = rehearse.compile_drain(one_chip, fabric, pool_size=65536)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2**30
    assert "tpu_custom_call" not in compiled.as_text()  # no Pallas kernel


def test_engine_run_compiles_for_workload1(one_chip, workload1_small):
    eng, state = workload1_small
    compiled = rehearse.compile_run(eng, state, one_chip)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_run_window_compiles_at_that_envelope(one_chip, workload1_small):
    eng, state = workload1_small
    compiled = rehearse.compile_run_window(eng, state, one_chip)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30
