"""The sharded ensemble driver end to end on 4 CPU host devices, at the
small cell's size: the members of ``workload1-ens8`` spread over the
devices, nothing compiles in the window, and the reference finds every
member correct. A fresh process: the device count is fixed when JAX
starts."""
import json
import os
import re
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

_RUN = """
import json, os, sys
sys.path[:0] = [os.path.join("bench", "tests"), "bench", "src"]
import importlib.util
import jax
import small

spec = importlib.util.spec_from_file_location("bench_run", "bench/run.py")
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)
with open(os.path.join("bench", "traffic", "workload1-ens8.json")) as f:
    ens8 = json.load(f)
traffic = small.traffic()
traffic.update({k: ens8[k] for k in ("driver", "members", "member_seeds")})
e2e = [dict(name="sim_us_per_s", unit="us/s"), dict(name="setup_s", unit="s")]
run.cell_spec = lambda name, root=None: (
    dict(chips=4), small.config(), traffic, e2e, [])
run.check_device = lambda chips: (jax.devices()[:chips],
                                  dict(hbm_bytes_per_s=819e9))
import repro.compile_cache
repro.compile_cache.use_persistent_cache = lambda: "off"
sys.exit(run.main(["--workload", "small.x4", "--seed", str(2**31 + 7),
                   "--seconds", "0.5", "--trace", "0"]))
"""


def test_sharded_ensemble_is_correct_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", _RUN], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] % 8 == 0
    assert res["device"]["count"] == 4
    assert res["checks"]["state_mismatches"]["value"] == 0
    m = re.search(r"(\d+) programs compiled or loaded in the window",
                  p.stderr)
    assert m and m.group(1) == "0", p.stderr[-2000:]


def test_deployment_holds_the_mix_to_its_members():
    """The cell's configuration states its deployment; the sharded driver
    refuses a mix that runs another number of members."""
    import importlib.util

    import pytest

    spec = importlib.util.spec_from_file_location(
        "bench_driver_ensemble_sharded",
        os.path.join(BENCH, "drivers", "ensemble_sharded.py"))
    sharded = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sharded)
    with open(os.path.join(BENCH, "configs", "df1d-paper-v5e4-ens8.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", "workload1-ens8.json")) as f:
        traffic = json.load(f)
    dep = config["deployment"]
    assert dep["members"] == dep["chips"] * dep["members_per_chip"] == 8
    assert sharded.Driver(config, traffic, 2**31 + 7).members == 8
    with pytest.raises(ValueError, match="deployment"):
        sharded.Driver(config, dict(traffic, members=4), 2**31 + 7)
