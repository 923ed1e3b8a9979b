"""The harness end to end on the CPU, at a size a test run holds: it
refuses to run without a TPU; with the look for a chip skipped it runs a
small cell and finds it correct; with the timed path broken underneath,
or the reference's lower precision put in the program's place, it finds
it not correct."""
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import control
import small

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_exits_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "df1d-paper.workload1", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("kind,count,chips,msg", [
    ("TPU v99 imaginary", 1, 1, "not in bench/peaks.json"),
    ("TPU v5 lite", 1, 4, "needs 4 chips"),
])
def test_exits_on_an_unknown_kind_or_too_few_chips(monkeypatch, capsys,
                                                    kind, count, chips,
                                                    msg):
    run = load_run()
    dev = SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * count)
    with pytest.raises(SystemExit) as e:
        run.check_device(chips)
    assert msg in str(e.value)


def run_small(monkeypatch, capsys, seed=5, traffic=None):
    """One run of the small cell with the look for a chip skipped;
    returns its result line."""
    run = load_run()
    traffic = traffic or small.traffic()
    e2e = [dict(name="sim_us_per_s", unit="us/s"),
           dict(name="setup_s", unit="s")]
    monkeypatch.setattr(run, "cell_spec", lambda name, root=None: (
        dict(chips=1), small.config(), traffic, e2e, []))
    monkeypatch.setattr(run, "check_device", lambda chips: (
        jax.devices()[:1], dict(hbm_bytes_per_s=819e9)))
    monkeypatch.setattr("repro.compile_cache.use_persistent_cache",
                        lambda: "off")
    assert run.main(["--workload", "small", "--seed", str(seed),
                     "--seconds", "0.5", "--trace", "0"]) == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(out)


def test_a_sound_run_is_correct(monkeypatch, capsys):
    res = run_small(monkeypatch, capsys, seed=2**31 + 11)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] >= 2
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"link_bytes_gap", "latency_gap",
                                  "state_mismatches", "failed"}
    assert res["metrics"]["sim_us_per_s"]["value"] > 0


def _unchanged(eng, state):
    return state


def _half_batch(eng, state):
    """The first half of the members run; the rest come back as they
    went in."""
    out = eng.run(state)
    B = state.t.shape[0]
    keep = jnp.arange(B) < B // 2
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(
            keep.reshape((B,) + (1,) * (a.ndim - 1)), a, b), out, state)


def _altered_bytes(eng, state):
    out = eng.run(state)
    lb = out.metrics.link_bytes.at[:, 0].add(1e6)
    return out._replace(metrics=out.metrics._replace(link_bytes=lb))


def _altered_rank(eng, state):
    out = eng.run(state)
    return out._replace(vms=out.vms._replace(pc=out.vms.pc.at[:, 0, 0].add(1)))


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered_bytes,
                                   _altered_rank])
def test_a_broken_timed_path_is_not_correct(monkeypatch, capsys, fault):
    from repro.union import experiment

    real = experiment.get_engine

    def broken(*a, **kw):
        eng = real(*a, **kw)
        return dataclasses.replace(eng, run=lambda s: fault(eng, s))

    monkeypatch.setattr(experiment, "get_engine", broken)
    res = run_small(monkeypatch, capsys)
    assert res["correct"] is False


def test_a_run_that_drops_messages_is_not_correct(monkeypatch, capsys):
    """A pool too small for the mix drops messages in the program and the
    reference alike; the dropped messages alone make the run fail."""
    traffic = dict(small.traffic(), pool_size=64)
    res = run_small(monkeypatch, capsys, traffic=traffic)
    assert res["failed"] > 0
    assert res["checks"]["failed"] == dict(value=res["failed"], limit=0)
    assert res["correct"] is False


def test_the_control_is_not_correct():
    """The reference in bfloat16 in the program's place fails a limit."""
    traffic = small.traffic()
    for seed in (3, 4, 5):
        got = control.control_gaps(small.config(), traffic, seed)
        assert any(got[k] > v for k, v in traffic["limits"].items()), got
