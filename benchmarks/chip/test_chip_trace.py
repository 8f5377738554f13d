"""Trace reduction on a small synthetic `.xplane.pb`: the busy union, the
device time by name and the idle gaps given to the enclosing host span."""
import os
import types

import pytest

import harness
import tracereduce

# one device: ops at [1000, 6000) and [4000, 8000) ns (overlapping), then
# [12000, 13000); modules over the first two; host spans: session_run over
# [0, 20000) and a submit span [8000, 11000) inside it
XSPACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 11000000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 7000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "%cka_terms.1 = (f32[1,1]{1,0}) custom-call(f32[128,1024]{1,0} %pad.0)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_multi" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 3
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 100 duration_ps: 100 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/session_run" } }
  event_metadata { key: 2 value { id: 2 name: "bench/submit" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(multi)" } }
}
'''


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    from jax.profiler import ProfileData

    d = tmp_path_factory.mktemp("trace")
    sub = d / "plugins" / "profile" / "run"
    sub.mkdir(parents=True)
    (sub / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return tracereduce.load(tracereduce.find_xplane(str(d)))


def test_load_reads_device_lines_and_bench_spans(trace):
    assert [e[0] for e in trace.all_ops()] == ["fusion.1", "cka_terms.1",
                                               "cka_terms.1"]
    assert trace.all_modules() == [("jit_multi", 1000, 7000)]
    assert sorted(s[0] for s in trace.spans) == ["bench/session_run",
                                                 "bench/submit"]


def test_busy_is_the_union_of_op_intervals(trace):
    assert tracereduce.busy_ns(trace, 0, 20000) == 7000 + 1000
    assert tracereduce.busy_ns(trace, 5000, 12500) == 3000 + 500


def test_time_by_name(trace):
    assert tracereduce.time_by_name(trace.all_ops(), 0, 20000) == {
        "fusion.1": 5000, "cka_terms.1": 5000}


def test_idle_gaps_go_to_the_innermost_host_span(trace):
    assert tracereduce.idle_gaps(trace, 0, 20000) == [
        (0, 1000), (8000, 12000), (13000, 20000)]
    # the gap [8000, 12000) has its midpoint inside bench/submit
    assert tracereduce.idle_by_span(trace, 0, 20000) == {
        "session_run": 1000 + 7000, "submit": 4000}


def test_idle_share_and_roofline_readers(trace):
    ctx = types.SimpleNamespace(trace=trace, lo=0, hi=20000, busy_s=8e-6,
                                window_s=20e-6)
    idle = harness.load_module(os.path.join(harness.HERE, "metrics",
                                            "device_idle_share.py"),
                               "metric_device_idle_share")
    assert idle.read(ctx) == pytest.approx(60.0)
    # two kernel events, but a one-unit model: one call per unit and pass
    cell = types.SimpleNamespace(
        ref=types.SimpleNamespace(unit_feature_sizes=lambda doc: [1024]),
        doc={}, mix={"stream": {"batch_size": 16}})
    notes = []
    ctx.cell, ctx.note = cell, notes.append
    ctx.peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    roof = harness.load_module(os.path.join(harness.HERE, "metrics",
                                            "cka_roofline.py"),
                               "metric_cka_roofline")
    least = 2 * 8.0 * 16 * 1024 / 819e9   # memory-bound, two calls
    assert roof.read(ctx) == pytest.approx(100.0 * least / 5000e-9)
    assert "memory-bound" in notes[0]
    # a count of kernel events that does not fit the units reads nothing
    cell.ref.unit_feature_sizes = lambda doc: [1024, 2048, 4096]
    assert roof.read(ctx) is None
