"""The member axis sharded over the local devices (``union.run`` through
``experiment.run_members``), on 4 CPU host devices at the small scale of
``chip_smoke.py``'s workload1: one process (the device count is fixed
when JAX starts) runs every case and the tests read what it found.

- The sharded run's final states equal the one-device runs' of the same
  per-device batches (the members in pairs), member for member, leaf for
  leaf, bit for bit; and the one-device run of all 8 members in one
  batch on every leaf but ``drain_rung_ticks`` and ``inject_passes``,
  which count the widths each batch was drained at and the passes it
  routed in, and so depend on the batch's fullest member (the rung rows
  still sum to the same ticks).
- ``device_phases()`` names the sharded program's ops by ``tick/<phase>``.
- ``engine.counters`` gives ``chip_iterations``, one entry a device, the
  largest of them ``iterations``.
- 2 members on 4 devices stay on one device.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CASES = """
import importlib.util, json
import jax, numpy as np
spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro import obs, union
from repro.union import experiment as X

sc = smoke.paper_scenario("small", 1.0)


def run(seeds, one_device=False):
    exp = union.Experiment(name=sc.name, scenarios=[sc], members=len(seeds),
                           seeds=seeds)
    mesh = X.member_mesh
    if one_device:
        X.member_mesh = lambda n: None
    try:
        return union.run(exp, keep_states=True)
    finally:
        X.member_mesh = mesh


def leaves(st):
    return {jax.tree_util.keystr(p): np.asarray(x) for p, x in
            jax.tree_util.tree_flatten_with_path(st)[0]}


def differing(a, b):
    la, lb = leaves(a), leaves(b)
    assert la.keys() == lb.keys()
    return sorted(k for k in la if not (
        la[k].dtype == lb[k].dtype and np.array_equal(la[k], lb[k],
                                                      equal_nan=True)))


seeds = list(range(8))
tr = obs.get_tracer()
obs.enable()
sharded = run(seeds)
obs.disable()
counters = [e["args"] for e in tr.events if e.get("name") == "engine.counters"]
shard_spans = [e["name"] for e in tr.events if e.get("name") == "engine.shard"]
phases = obs.device_phases()
key = next(iter(tr.programs))
in_pairs = [s for k in range(4) for s in run(seeds[2 * k:2 * k + 2]).states]
batch8 = run(seeds, one_device=True)
two = run([0, 1])
print(json.dumps(dict(
    devices=sharded.telemetry["devices"],
    member_devices=[sorted(d.id for d in s.t.devices())
                    for s in sharded.states],
    vs_pairs=[differing(a, b) for a, b in zip(sharded.states, in_pairs)],
    vs_batch8=[differing(a, b) for a, b in zip(sharded.states,
                                               batch8.states)],
    rung_rows=[[int(np.asarray(s.metrics.drain_rung_ticks).sum()),
                int(np.asarray(s.metrics.ticks))] for s in sharded.states],
    batch8_devices=batch8.telemetry["devices"],
    counters=counters, shard_spans=shard_spans,
    phases=sorted(set(phases.values())), n_named=len(phases),
    noted_sharding=str(key[2][0].sharding),
    two_devices=two.telemetry["devices"],
)))
"""


@pytest.fixture(scope="module")
def found():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", _CASES], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sharded_states_equal_the_one_device_runs_bit_for_bit(found):
    assert found["devices"] == [0, 1, 2, 3]
    # members 2d and 2d + 1 on device d, in member order
    assert found["member_devices"] == [[d] for d in (0, 0, 1, 1, 2, 2, 3, 3)]
    assert found["batch8_devices"] == [0]
    assert found["vs_pairs"] == [[]] * 8
    assert all(set(d) <= {".metrics.drain_rung_ticks",
                          ".metrics.inject_passes"}
               for d in found["vs_batch8"]), found["vs_batch8"]
    assert all(rungs == ticks for rungs, ticks in found["rung_rows"])


def test_device_phases_name_the_sharded_program(found):
    assert "NamedSharding" in found["noted_sharding"]
    assert found["n_named"] > 0
    assert {"emit", "inject", "drain", "deliver", "advance",
            "loop"} <= set(found["phases"])
    assert set(found["phases"]) <= {
        "loop", "faults", "emit", "inject", "drain", "deliver", "advance",
        "latency", "window", "skip", "counters", "probes"}


def test_chip_iterations_one_entry_a_device(found):
    (c,) = found["counters"]
    assert len(c["chip_iterations"]) == 4
    assert max(c["chip_iterations"]) == c["iterations"]
    assert found["shard_spans"] == ["engine.shard", "engine.shard"]


def test_two_members_on_four_devices_stay_on_one(found):
    assert found["two_devices"] == [0]
