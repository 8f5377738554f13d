"""The readers of the program's own host spans and counters: on a
synthetic `RunResult.host` and on a small synthetic `.xplane.pb`. Each
reads nothing from a program that records no host spans."""
import os
import types

import pytest

import harness
import programspans
import tracereduce

HERE = harness.HERE


def reader(name):
    return harness.load_module(os.path.join(HERE, "metrics", name + ".py"),
                               "metric_" + name.replace(".", "_"))


def span(count, total, self_s=None):
    return {"count": count, "total_s": total,
            "self_s": total if self_s is None else self_s}


# one session: two rounds (one in the end-of-run flush), a CKA pass
# inside the first round's policy step, serving in the event loop
HOST = {
    "spans": {
        "event/data": span(4, 1.0, 0.1),
        "event/data>round": span(1, 0.5, 0.01),
        "event/data>round>round/own_buffers": span(1, 0.1),
        "event/data>round>train/dispatch": span(1, 0.02),
        "event/data>round>round/publish": span(1, 0.05, 0.01),
        "event/data>round>round/publish>serve/drain": span(1, 0.04, 0.01),
        "event/data>round>round/publish>serve/drain>serve/forward":
            span(1, 0.03),
        "event/data>round>round/policy": span(1, 0.3, 0.02),
        "event/data>round>round/policy>cka/pass": span(1, 0.28, 0.2),
        "event/data>round>round/policy>cka/pass>cka/features":
            span(1, 0.08),
        "event/data>cka/reference": span(1, 0.12),
        "event/segment": span(2, 0.3, 0.0),
        "event/segment>serve/drain": span(2, 0.26, 0.06),
        "event/segment>serve/drain>serve/forward": span(2, 0.2),
        "event/segment>event/inference": span(3, 0.04, 0.01),
        "event/segment>event/inference>serve/submit": span(3, 0.03),
        "flush": span(1, 0.2, 0.0),
        "flush>round": span(1, 0.2, 0.03),
        "flush>round>round/validate": span(1, 0.17),
    },
    "counters": {
        "host_syncs{site=validate}": 4.0,
        "host_syncs{site=serve}": 3.0,
        "host_syncs{site=cka_unit}": 20.0,
        "device_copies{site=own_buffers}": 470.0,
        "compile_s{span=event/data>round>round/cost,stage=lowering}": 0.5,
    },
    "histograms": {"request_wait_s": [0.001, 0.002, 0.010]},
}


def ctx_of(*hosts):
    logs = [types.SimpleNamespace(result=types.SimpleNamespace(**h),
                                  logits=[None] * 3)
            for h in hosts]
    notes = []
    return types.SimpleNamespace(
        window_logs=logs, log=logs[-1], notes=notes, note=notes.append,
        session=types.SimpleNamespace(images=lambda lg: 100))


def test_round_host_ms_reads_self_time_at_or_under_round():
    ctx = ctx_of({"host": HOST}, {"host": HOST})
    # event/data>round: 0.01 + 0.1 + 0.02 + publish 0.01 + policy 0.02;
    # flush>round: 0.03 + 0.17; serve/* and cka/* under them left out
    want = 1e3 * 2 * (0.01 + 0.1 + 0.02 + 0.01 + 0.02 + 0.03 + 0.17) / 4
    assert reader("round_host_ms").read(ctx) == pytest.approx(want)
    assert "round/validate" in ctx.notes[0]


def test_cka_host_ms_per_pass_reads_outermost_cka_spans():
    ctx = ctx_of({"host": HOST})
    want = 1e3 * (0.28 + 0.12) / 1
    assert reader("cka_host_ms_per_pass").read(ctx) == pytest.approx(want)


def test_serve_host_ms_per_request_reads_outermost_serve_spans():
    ctx = ctx_of({"host": HOST})
    want = 1e3 * (0.04 + 0.26 + 0.03) / 3
    assert reader("serve_host_ms_per_request").read(ctx) == \
        pytest.approx(want)


def test_host_syncs_per_image_sums_every_site():
    ctx = ctx_of({"host": HOST}, {"host": HOST})
    assert reader("host_syncs_per_image").read(ctx) == \
        pytest.approx(2 * 27.0 / 200)
    assert "'cka_unit': 40.0" in ctx.notes[0]


def test_request_wait_p95_over_every_sample():
    import numpy as np

    ctx = ctx_of({"host": HOST}, {"host": HOST})
    want = np.percentile([0.001, 0.002, 0.010] * 2, 95) * 1e3
    assert reader("request_wait_ms.p95").read(ctx) == pytest.approx(want)


def test_setup_compile_is_the_process_total_less_the_sessions():
    from repro.obs import host

    key = "event/data>round>round/cost"
    ctx = ctx_of({"host": HOST}, {"host": HOST})
    before = reader("setup_cost_model_compile_s").read(ctx)
    host.count("compile_s", 2.5, span=key, stage="lowering")
    host.count("compile_s", 9.0, span="pretrain", stage="lowering")
    # the two sessions' own 0.5 s each are not set-up
    after = reader("setup_cost_model_compile_s").read(ctx)
    assert after - before == pytest.approx(2.5)
    assert "window sessions' compiles by span: {}" in ctx.notes[-1]


@pytest.mark.parametrize("name", [
    "round_host_ms", "cka_host_ms_per_pass", "serve_host_ms_per_request",
    "host_syncs_per_image", "request_wait_ms.p95",
    "setup_cost_model_compile_s"])
def test_nothing_read_from_a_program_without_host_spans(name):
    # a RunResult from before host spans has no `host` field
    assert reader(name).read(ctx_of({})) is None


# device ops at [1000, 6000), [4000, 8000), [12000, 13000) ns: idle gaps
# [0, 1000), [8000, 12000), [13000, 20000). Program spans: event/data
# over [0, 9000) with round [2000, 8500) inside it; serve/drain over
# [9500, 11000); nothing after 11000. A bench/ span covers it all.
XSPACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 1 offset_ps: 3000000 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 11000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 3
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 6500000 }
    events { metadata_id: 4 offset_ps: 9500000 duration_ps: 1500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/session_run" } }
  event_metadata { key: 2 value { id: 2 name: "edgeol/event/data" } }
  event_metadata { key: 3 value { id: 3 name: "edgeol/round" } }
  event_metadata { key: 4 value { id: 4 name: "edgeol/serve/drain" } }
}
'''


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    from jax.profiler import ProfileData

    d = tmp_path_factory.mktemp("trace")
    sub = d / "plugins" / "profile" / "run"
    sub.mkdir(parents=True)
    (sub / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return str(d)


def test_trace_spans_are_the_program_spans(trace_dir):
    spans = programspans.trace_spans(tracereduce.find_xplane(trace_dir),
                                     0, 20000)
    assert sorted(spans) == [("event/data", 0, 9000),
                             ("round", 2000, 6500),
                             ("serve/drain", 9500, 1500)]
    # clipped to the window
    assert programspans.trace_spans(tracereduce.find_xplane(trace_dir),
                                    9200, 20000) == [("serve/drain", 9500,
                                                      1500)]


def test_innermost_pieces_follow_nesting():
    spans = [("a", 0, 100), ("b", 10, 20), ("c", 50, 10), ("d", 200, 5)]
    assert programspans.innermost(spans) == [
        (0, 10, "a"), (10, 30, "b"), (30, 50, "a"), (50, 60, "c"),
        (60, 100, "a"), (200, 205, "d")]


def test_idle_outside_program_spans(trace_dir, monkeypatch):
    monkeypatch.setattr(programspans, "trace_dir", lambda ctx: trace_dir)
    trace = tracereduce.load(tracereduce.find_xplane(trace_dir))
    notes = []
    ctx = types.SimpleNamespace(trace=trace, lo=0, hi=20000,
                                note=notes.append)
    # gaps: [0, 1000) mid 500 in event/data; [8000, 12000) mid 10000 in
    # serve/drain; [13000, 20000) mid 16500 in no program span
    value = reader("idle_outside_program_spans").read(ctx)
    assert value == pytest.approx(100.0 * 7000 / 12000)
    assert "serve/drain 0.0000" in notes[0] and "event/data" in notes[0]


def test_idle_outside_reads_nothing_without_program_spans(tmp_path,
                                                          monkeypatch):
    from jax.profiler import ProfileData

    sub = tmp_path / "plugins" / "profile" / "run"
    sub.mkdir(parents=True)
    (sub / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(
            XSPACE.replace("edgeol/", "other/")))
    monkeypatch.setattr(programspans, "trace_dir", lambda ctx: str(tmp_path))
    trace = tracereduce.load(tracereduce.find_xplane(str(tmp_path)))
    ctx = types.SimpleNamespace(trace=trace, lo=0, hi=20000,
                                note=lambda m: None)
    assert reader("idle_outside_program_spans").read(ctx) is None
    # and no trace directory at all
    monkeypatch.setattr(programspans, "trace_dir",
                        lambda ctx: str(tmp_path / "missing"))
    assert reader("idle_outside_program_spans").read(ctx) is None
