"""Host spans and counters (`repro.obs.host`, DESIGN.md §14 "Host spans").

The spans time the host on the program's own clock and mirror into
`jax.profiler`; the counters count device-to-host pulls, leaf copies,
compiles by span and each request's wait. A session's share of them is
`RunResult.host`. Timings are real, so no test here pins a number of
seconds: they pin structure (paths, nesting, self time), counts against
an independent count, and per-run deltas."""
import time

import jax
import pytest
from jax._src.array import ArrayImpl

from repro.obs import host
from repro.runtime import RuntimeConfig, SlotConfig, edgeol_session
from repro.runtime.train_loop import make_optimizer_state

SCALE = dict(batches_per_scenario=3, inferences=6, num_scenarios=2)


def _session():
    cfg = RuntimeConfig(slots={"cv": SlotConfig()}, workload="single-poisson",
                        workload_scale=dict(SCALE), seed=0,
                        pretrain_epochs=1, compiled=True)
    return edgeol_session(cfg)


@pytest.fixture(scope="module")
def runs():
    """Two fresh compiled ETuner sessions (the second warm, timed around
    `run()`). The first counts every `jax.Array` pull independently of the
    program: `float()` and `__array__` go through `ArrayImpl._value`, and
    numpy reads a CPU array through the buffer protocol (`__buffer__`).
    Model initialisation (directly under `pretrain`) is not the hot path:
    iterating a split key array there pulls a chunk count."""
    pulls = [0]
    value, buffer = ArrayImpl._value, ArrayImpl.__buffer__

    def pulled():
        pulls[0] += host.current_path() != "pretrain"

    def counted_value(self):
        pulled()
        return value.fget(self)

    def counted_buffer(self, flags):
        pulled()
        return buffer(self, flags)

    ArrayImpl._value = property(counted_value)
    ArrayImpl.__buffer__ = counted_buffer
    try:
        first = _session().run()
    finally:
        ArrayImpl._value, ArrayImpl.__buffer__ = value, buffer
    rt = _session()
    t0 = time.perf_counter()
    second = rt.run()
    wall = time.perf_counter() - t0
    params = jax.eval_shape(rt.model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(
        lambda p: make_optimizer_state(rt.model, rt.opt_cfg, p), params)
    return {"first": first, "second": second, "pulls": pulls[0],
            "wall": wall, "param_leaves": len(jax.tree.leaves(params)),
            "state_leaves": len(jax.tree.leaves(state))}


def test_spans_nest_by_path_with_self_time():
    mark = host.snapshot()
    with host.span("outer"):
        time.sleep(0.02)
        for _ in range(2):
            with host.span("inner", step=1):
                time.sleep(0.01)
    spans = host.since(mark)["spans"]
    assert set(spans) == {"outer", "outer>inner"}
    outer, inner = spans["outer"], spans["outer>inner"]
    assert (outer["count"], inner["count"]) == (1, 2)
    assert inner["total_s"] >= 0.02
    assert outer["total_s"] >= 0.04
    # self time: the total less what the children covered
    assert outer["self_s"] == pytest.approx(
        outer["total_s"] - inner["total_s"], abs=1e-9)
    assert inner["self_s"] == pytest.approx(inner["total_s"], abs=1e-12)
    assert host.current_path() == host.OUTSIDE


def test_span_closes_on_an_exception():
    mark = host.snapshot()
    with pytest.raises(ValueError):
        with host.span("fails"):
            raise ValueError("x")
    assert host.current_path() == host.OUTSIDE
    assert host.since(mark)["spans"]["fails"]["count"] == 1


def test_counters_and_histograms_are_deltas():
    host.count("test_counter", 2, site="a")
    host.observe("test_hist", 9.0)
    mark = host.snapshot()
    host.count("test_counter", 3, site="a")
    host.count("test_counter", site="b")
    host.observe("test_hist", 1.5)
    host.observe("test_hist", 2.5, kind="x")
    delta = host.since(mark)
    assert delta["counters"] == {"test_counter{site=a}": 3.0,
                                 "test_counter{site=b}": 1.0}
    assert delta["histograms"] == {"test_hist": [1.5],
                                   "test_hist{kind=x}": [2.5]}
    assert host.since(host.snapshot()) == {"spans": {}, "counters": {},
                                           "histograms": {}}


def test_compiles_are_charged_to_the_open_span():
    mark = host.snapshot()
    with host.span("compile_here"):
        jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7.0))
    counters = host.since(mark)["counters"]
    assert counters["compiles{span=compile_here}"] >= 1.0
    for stage in ("tracing", "lowering", "compiling"):
        assert counters[f"compile_s{{span=compile_here,stage={stage}}}"] > 0


def test_run_result_host_is_a_per_run_delta(runs):
    a, b = runs["first"].host, runs["second"].host
    for h in (a, b):
        assert h["spans"]["pretrain"]["count"] == 1
        assert h["spans"]["flush"]["count"] == 1
    # same events, same spans and counts in both runs (not cumulative)
    assert {p: s["count"] for p, s in a["spans"].items()} == \
        {p: s["count"] for p, s in b["spans"].items()}
    for key in ("device_copies{site=own_buffers}",
                "host_syncs{site=validate}"):
        assert a["counters"][key] == b["counters"][key] > 0
    # the warm run builds no program, under any span
    assert not [k for k in b["counters"] if k.startswith("compiles")]
    # rounds, the round's children, the shared train spans
    rounds = sum(s["count"] for p, s in b["spans"].items()
                 if p.split(host.PATH_SEP)[-1] == "round")
    assert rounds == runs["second"].rounds
    names = {p.split(host.PATH_SEP)[-1] for p in b["spans"]}
    assert {"round/own_buffers", "round/cost", "round/publish",
            "round/validate", "round/policy", "train/stage",
            "train/dispatch", "event/data", "event/segment",
            "event/inference", "serve/submit", "serve/drain", "serve/stage",
            "serve/forward", "serve/score", "cka/reference", "cka/pass",
            "cka/features"} <= names


def test_one_copy_program_per_round_and_per_pretraining(runs):
    """A donating round owns the params (what escapes the executor)
    through one copy program; pretraining de-aliases params and optimizer
    state through one. `device_copies` counts every leaf copied."""
    n_params, n_state = runs["param_leaves"], runs["state_leaves"]
    for r in (runs["first"], runs["second"]):
        c = r.host["counters"]
        assert c["copy_programs{site=own_buffers}"] == r.rounds > 0
        assert c["device_copies{site=own_buffers}"] == r.rounds * n_params
        assert c["copy_programs{site=pretrain}"] == 1
        assert c["device_copies{site=pretrain}"] == n_params + n_state


def test_host_field_stays_out_of_equality_and_summary(runs):
    import dataclasses

    r = runs["second"]
    twin = dataclasses.replace(r, host={"spans": {"x": 1}})
    assert twin == r
    assert "host" not in r.summary() and "spans" not in repr(r)


def test_top_level_spans_cover_the_run(runs):
    h = runs["second"].host
    top = sum(s["total_s"] for p, s in h["spans"].items()
              if host.PATH_SEP not in p)
    assert top >= 0.9 * runs["wall"]
    assert {p for p in h["spans"] if host.PATH_SEP not in p} <= {
        "pretrain", "flush", "event/data", "event/inference",
        "event/segment", "event/probe", "event/scenario"}


def test_host_syncs_count_every_pull(runs):
    counters = runs["first"].host["counters"]
    syncs = sum(v for k, v in counters.items()
                if k.startswith("host_syncs{"))
    assert syncs == runs["pulls"] > 0


def test_one_request_wait_per_request_served(runs):
    for r in (runs["first"], runs["second"]):
        waits = r.host["histograms"]["request_wait_s"]
        assert len(waits) == len(r.inference_accs) > 0
        assert min(waits) >= 0.0


def test_profiler_trace_holds_nested_program_spans(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with host.span("outer"):
            with host.span("inner"):
                jax.numpy.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    data = ProfileData.from_file(str(path))
    found = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(host.PREFIX)}
    outer, inner = found["edgeol/outer"], found["edgeol/inner"]
    assert outer[0] <= inner[0] and inner[1] <= outer[1]


def test_unsegmented_inference_events_nest_once(runs):
    """Per-event serving (segment batching off) drains inside the event's
    own `event/inference` span, which holds no second one."""
    rt = _session()
    rt.segment = False
    spans = rt.run().host["spans"]
    assert "event/inference>serve/drain" in spans
    assert "event/segment" not in spans
    assert not [p for p in spans
                if p.count("event/inference") > 1]
