"""The trace reduction of ``benchmarks/profile_tick.py`` on small recorded
traces: nesting, clipping to the annotated window, busy and idle time."""
import importlib.util
import os

import pytest

from jax.profiler import ProfileData

_spec = importlib.util.spec_from_file_location(
    "profile_tick", os.path.join(os.path.dirname(__file__), "..",
                                 "benchmarks", "profile_tick.py"))
PT = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(PT)

# a device line with a conditional holding a scatter and a gather, the
# same scatter again after it, and an op outside the host's annotated
# window; the host line carries the annotation. Times in ps: the device
# line starts at 1,000 ns, the host line at 900 ns. On a TPU an event is
# named by its HLO instruction's text, layouts and all.
SCATTER = ("%fusion.12 = f32[16]{0:T(1024)S(1)} fusion(s32[1024]{0:T(1024)"
           "S(1)} %bitcast.1, f32[1024]{0:T(1024)} %r.2, f32[]{:T(128)} "
           "%c.3), kind=kCustom, calls=%fused_computation.1")
GATHER = ("%fusion.4 = f32[1024]{0:T(1024)} fusion(f32[64]{0:T(1024)S(1)} "
          "%t.1, s32[1024]{0:T(1024)S(1)} %i.2), kind=kLoop, calls=%fc.2")
TRACE = f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }}
    events {{ metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }}
    events {{ metadata_id: 3 offset_ps: 3000000 duration_ps: 1000000 }}
    events {{ metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 }}
    events {{ metadata_id: 4 offset_ps: 20000000 duration_ps: 1000000 }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "conditional.3" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "{SCATTER}" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "{GATHER}" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "fusion.1" }} }}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{
    id: 7
    name: "python"
    timestamp_ns: 900
    events {{ metadata_id: 1 offset_ps: 100000 duration_ps: 10000000 }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "profile_tick.run" }} }}
}}
"""


@pytest.fixture(scope="module")
def reduced():
    return PT.reduce_trace(ProfileData.from_text_proto(TRACE), entries=1024)


def test_window_busy_and_idle(reduced):
    # window 1,000..11,000 ns; busy 1,000..6,000 and 7,000..9,000
    assert reduced["window_ns"] == 10000
    assert reduced["busy_ns"] == 7000
    assert reduced["idle_share"] == pytest.approx(0.3)
    assert reduced["events"] == 4  # fusion.1 lies outside the window


def test_self_time_per_instruction(reduced):
    ops = reduced["ops"]
    assert list(ops) == ["fusion.12", "conditional.3", "fusion.4"]
    assert ops["conditional.3"]["self_ns"] == 2000  # less what it holds
    assert ops["conditional.3"]["total_ns"] == 5000
    assert ops["fusion.12"]["count"] == 2
    assert ops["fusion.12"]["self_ns"] == 4000
    assert sum(v["self_ns"] for v in ops.values()) == reduced["busy_ns"]


def test_by_run_count_and_role(reduced):
    assert reduced["by_count"] == {2: dict(ops=1, self_ns=4000),
                                   1: dict(ops=2, self_ns=3000)}
    assert reduced["by_role"] == dict(
        scatter=dict(ops=1, count=2, self_ns=4000),
        gather=dict(ops=1, count=1, self_ns=1000))


@pytest.mark.parametrize("name,inst,want", [
    (SCATTER, "fusion.12", "scatter"),
    (GATHER, "fusion.4", "gather"),
    ("%copy-start.4 = (s32[2,4]{1,0}, s32[2,4]{1,0}, u32[]{:S(2)}) "
     "copy-start(s32[2,4]{1,0:T(4,128)} %p.1)", "copy-start.4", None),
    ("scatter.12", "scatter.12", None),
])
def test_parse_op_and_role(name, inst, want):
    op = PT.parse_op(name)
    assert op["inst"] == inst
    assert PT.role(op, 1024) == want


def test_ops_are_clipped_to_the_window():
    red = PT.reduce_events([("while.1", 0, 100), ("add.2", 150, 100)],
                           window=(50, 200))
    assert red["ops"]["while.1"]["total_ns"] == 50
    assert red["ops"]["add.2"]["total_ns"] == 50
    assert red["idle_share"] == pytest.approx(1 / 3)


def test_misaligned_clocks_raise():
    with pytest.raises(ValueError, match="clocks"):
        PT.reduce_events([("scatter.1", 0, 10)], window=(100, 200))


def test_one_annotation_required():
    pd = ProfileData.from_text_proto(TRACE.replace("profile_tick.run", "x"))
    with pytest.raises(ValueError, match="0 host events"):
        PT.annotated_window(pd)


def test_roofline_estimate(reduced):
    roof = PT.roofline_estimate(reduced, "TPU v5 lite")
    assert roof["scatter"]["bytes"] == 8192
    assert roof["scatter"]["mean_s"] == pytest.approx(2e-6)
    assert roof["scatter"]["share"] == pytest.approx(8192 / 819e9 / 2e-6)
    assert roof["gather"]["mean_s"] == pytest.approx(1e-6)
    with pytest.raises(KeyError):
        PT.roofline_estimate(reduced, "TPU v9")
