"""``chip_smoke.py``'s phases on the CPU at small sizes, its refusal to
run without a TPU, and where the persistent compile cache goes."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_goldens_phase(smoke, capsys):
    smoke.phase_goldens()
    out = capsys.readouterr().out
    for case in ("equiv-mix", "equiv-coll"):
        assert f"(b) {case}: integer fields equal the goldens" in out


def test_paper_phase_at_small_scale(smoke):
    out = smoke.phase_paper(scale="small", horizon_ms=1.0, members=2)
    assert out["members"] == 2 and out["horizon_ms"] == 1.0
    assert out["envelope"]["Jmax"] == 4
    assert out["cold_wall_s"] > 0 and out["warm_wall_s"] > 0
    assert out["warm_spans_ms"]["engine.run"] <= out["warm_wall_s"] * 1e3


def test_windows_phase(smoke):
    out = smoke.phase_windows()
    assert out["completed"] == 32  # 8 jobs x 2 seeds x 2 policies
    assert out["windows"] > 1


def test_four_chip_phase_refuses_one_device(smoke):
    with pytest.raises(smoke.SmokeFailure, match="cannot shard"):
        smoke.phase_four_chips(scale="small", horizon_ms=1.0)


_FOUR_DEVICES = """
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
print(smoke.phase_four_chips(scale="small", horizon_ms=1.0))
"""


def test_four_chip_phase_on_four_host_devices():
    """Phase (e)'s sharding and equality checks on 4 CPU host devices
    (a fresh process: the device count is fixed when JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", _FOUR_DEVICES], env=env,
                       cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "final state on 4 distinct devices ([0, 1, 2, 3])" in r.stdout


def test_main_fails_without_a_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    assert "platform=cpu" in out


def test_nan_paths_finds_nested_nan(smoke):
    rep = dict(a=1.0, lat=dict(x=[0.0, float("nan")]), b="s")
    assert list(smoke._nan_paths(rep)) == ["report.lat.x[1]"]


_CACHE_PROBE = """
import json, jax, jax.numpy as jnp
from repro.compile_cache import use_persistent_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
path = use_persistent_cache()
jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.arange(8.0)).block_until_ready()
print(json.dumps(dict(path=path, cfg=jax.config.jax_compilation_cache_dir)))
"""


def _probe_cache(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_follows_the_environment(tmp_path):
    from repro.compile_cache import CHECKOUT_CACHE

    before = (set(os.listdir(CHECKOUT_CACHE))
              if CHECKOUT_CACHE.exists() else set())
    got = _probe_cache(tmp_path)
    assert got == dict(path=str(tmp_path), cfg=str(tmp_path))
    assert os.listdir(tmp_path), "nothing was cached in the set directory"
    after = (set(os.listdir(CHECKOUT_CACHE))
             if CHECKOUT_CACHE.exists() else set())
    assert after == before, "the checkout cache was written as well"


def test_compile_cache_defaults_to_the_checkout():
    from repro.compile_cache import CHECKOUT_CACHE

    assert CHECKOUT_CACHE == Path(ROOT) / ".jax_cache"
    got = _probe_cache(None)
    assert got == dict(path=str(CHECKOUT_CACHE), cfg=str(CHECKOUT_CACHE))
    assert CHECKOUT_CACHE.is_dir() and os.listdir(CHECKOUT_CACHE)
