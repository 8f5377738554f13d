"""Compiled hot path (DESIGN.md §12): equivalence, donation, and the
compile-ledger contract.

The headline property: executing a compiled preset timeline as fused
segments — `lax.scan` over stacked train batches, vmapped stacks of
serving groups — yields the *identical* `RunResult` to dispatching the
same timeline one event at a time, and to the pure-Python fallback
(`compiled=False`). Identical means exact: a scan's while-loop HLO is
trip-count-independent and the validity mask leaves padded steps' carry
untouched, so fusion is purely a dispatch optimization; any drift is a
bug, not noise. The same must hold under QoS preemption, where
segment-batched rounds fall back to segment-split execution.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.models import Model
from repro.obs import host
from repro.optim import AdamWConfig
from repro.runtime import RuntimeConfig, SlotConfig, edgeol_session
from repro.runtime.train_loop import (TrainStepCache, as_jnp,
                                      batch_signature, copy_tree,
                                      make_optimizer_state, same_shape_runs)

SCALE = dict(batches_per_scenario=3, inferences=6, num_scenarios=2)


def _run(workload="single-poisson", *, compiled=True, segment=True,
         preemptible=False, scale=SCALE, **cfg_kw):
    cfg = RuntimeConfig(slots={"cv": SlotConfig()}, workload=workload,
                        workload_scale=dict(scale), seed=0,
                        pretrain_epochs=1, preemptible=preemptible,
                        compiled=compiled, **cfg_kw)
    rt = edgeol_session(cfg)
    rt.segment = segment
    return rt.run()


def _assert_identical(a, b):
    """Exact RunResult equality — accuracy trace, ledger totals, and the
    per-stream / per-model attribution down to the last bit."""
    assert a.rounds == b.rounds
    assert a.recompiles == b.recompiles
    assert a.preemptions == b.preemptions
    np.testing.assert_array_equal(a.inference_accs, b.inference_accs)
    np.testing.assert_array_equal(a.val_curve, b.val_curve)
    assert a.total_time_s == b.total_time_s
    assert a.total_energy_j == b.total_energy_j
    assert a.compute_tflops == b.compute_tflops
    assert a.per_stream == b.per_stream
    assert a.per_model == b.per_model


def test_segment_batched_matches_per_event():
    seg = _run(segment=True)
    per_event = _run(segment=False)
    _assert_identical(seg, per_event)


def test_compiled_matches_fallback():
    compiled = _run(segment=True)
    fallback = _run(compiled=False)
    _assert_identical(compiled, fallback)


def test_segment_batched_matches_per_event_preemptible():
    # QoS preemption splits rounds mid-flight; preempted rounds leave the
    # fused path and advance batch-by-batch, which must not perturb a bit.
    # The CI quick-sweep scale is the smallest one that actually preempts.
    scale = dict(batches_per_scenario=4, inferences=10, num_scenarios=2)
    seg = _run("qos", segment=True, preemptible=True, scale=scale)
    per_event = _run("qos", segment=False, preemptible=True, scale=scale)
    assert seg.preemptions > 0
    _assert_identical(seg, per_event)


def test_compiled_matches_fallback_multi_stream():
    compiled = _run("two-stream")
    fallback = _run("two-stream", compiled=False)
    _assert_identical(compiled, fallback)


# ---------------------------------------------------------------------------
# TrainStepCache: fused scan + donation semantics on a micro model


def _micro_cache(donate):
    def loss(params, batch, plan=None):
        pred = batch["x"] @ params["w"]
        l = jnp.mean((pred - batch["y"]) ** 2)
        return l, {"loss": l}

    model = Model(cfg=None, loss=loss, features=None, num_freeze_units=1,
                  init=lambda rng: {"w": jax.random.normal(rng, (4, 2))})
    opt = AdamWConfig(lr=1e-2)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = make_optimizer_state(model, opt, params)
    return TrainStepCache(model, opt, donate=donate), params, opt_state


def _micro_batches(n, seed=1):
    rng = np.random.default_rng(seed)
    return [{"x": rng.standard_normal((5, 4)).astype(np.float32),
             "y": rng.standard_normal((5, 2)).astype(np.float32)}
            for _ in range(n)]


def _leaves(tree):
    return [np.asarray(l) for l in jax.tree.leaves(tree)]


def test_donated_step_bitwise_matches_undonated():
    batches = _micro_batches(3)
    results = []
    for donate in (False, True):
        cache, params, opt_state = _micro_cache(donate)
        step = cache.get(None)
        for b in batches:
            # exclusive copies: the donated variant consumes its inputs
            params, opt_state, _ = step(
                jax.tree.map(jnp.copy, params),
                jax.tree.map(jnp.copy, opt_state), as_jnp(b))
        results.append(_leaves(params) + _leaves(opt_state))
    for a, b in zip(*results):
        np.testing.assert_array_equal(a, b)


def _aliased_tree(seed):
    """Params and Adam state as init builds them: the moments share one
    zero buffer, and a second parameter aliases the first."""
    w = jax.random.normal(jax.random.PRNGKey(seed), (4, 2))
    zeros = jnp.zeros((4, 2))
    return ({"w": w, "v": w}, {"mu": zeros, "nu": zeros,
                               "count": jnp.zeros([], jnp.int32)})


def test_copy_tree_gives_fresh_buffers_for_aliased_leaves():
    tree = _aliased_tree(0)
    out = copy_tree(tree, "test")
    assert jax.tree.structure(out) == jax.tree.structure(tree)
    ins, outs = jax.tree.leaves(tree), jax.tree.leaves(out)
    for a, b in zip(ins, outs):
        assert (a.dtype, a.shape) == (b.dtype, b.shape)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    in_ptrs = {l.unsafe_buffer_pointer() for l in ins}
    out_ptrs = [l.unsafe_buffer_pointer() for l in outs]
    assert len(in_ptrs) < len(ins)  # the input does alias
    assert len(set(out_ptrs)) == len(outs)
    assert not in_ptrs & set(out_ptrs)


def test_copy_tree_is_one_program_per_tree_structure():
    copy_tree(_aliased_tree(1), "test")
    tree = _aliased_tree(2)
    mark = host.snapshot()
    with host.span("copy_again"):
        out = copy_tree(tree, "test")
    counters = host.since(mark)["counters"]
    assert not [k for k in counters if k.startswith("compiles")]
    assert counters["copy_programs{site=test}"] == 1
    assert counters["device_copies{site=test}"] == len(jax.tree.leaves(out))


def test_session_leaves_no_aliased_train_state():
    """Rounds copy only the params (what escapes the executor); the
    optimizer state they donate uncopied must never share a buffer with
    another leaf of the train state."""
    cfg = RuntimeConfig(slots={"cv": SlotConfig()}, workload="single-poisson",
                        workload_scale=dict(SCALE), seed=0,
                        pretrain_epochs=1, compiled=True)
    rt = edgeol_session(cfg)
    assert rt.run().rounds > 0
    for dev in rt.fleet.devices:
        for slot in dev.slots.values():
            ex = slot.executor
            ptrs = [l.unsafe_buffer_pointer()
                    for l in jax.tree.leaves((ex.params, ex.opt_state))]
            assert len(set(ptrs)) == len(ptrs)


def test_fused_scan_bitwise_matches_single_steps():
    batches = _micro_batches(5)
    cache, params, opt_state = _micro_cache(False)
    step = cache.get(None)
    p_seq, o_seq = params, opt_state
    for b in batches:
        p_seq, o_seq, _ = step(p_seq, o_seq, as_jnp(b))
    # one fused dispatch (bucket 8, 3 masked padding steps)
    p_fused, o_fused, _ = cache.fused_call(None, params, opt_state, batches)
    for a, b in zip(_leaves(p_seq) + _leaves(o_seq),
                    _leaves(p_fused) + _leaves(o_fused)):
        np.testing.assert_array_equal(a, b)


def test_recompile_ledger_counts_plan_shape_triples():
    cache, _, _ = _micro_cache(False)
    b_small, b_large = _micro_batches(1)[0], {
        "x": np.zeros((9, 4), np.float32), "y": np.zeros((9, 2), np.float32)}
    assert cache.recompiles == 0
    cache.get("planA")
    assert cache.recompiles == 1
    cache.get("planA", b_small)          # first shape rides the plan compile
    cache.get("planA", b_small)
    assert cache.recompiles == 1
    cache.get("planA", b_large)          # second shape = second program
    assert cache.recompiles == 2
    cache.get("planB", b_large)          # new plan (its first shape rides)
    assert cache.recompiles == 3
    cache.get("planB", b_small)
    assert cache.recompiles == 4
    # steady state: re-requesting any known (plan, shape) is free
    for plan, b in (("planA", b_small), ("planA", b_large),
                    ("planB", b_small), ("planB", b_large)):
        cache.get(plan, b)
    assert cache.recompiles == 4


def test_same_shape_runs_slices_maximal_runs():
    a = {"x": np.zeros((2, 4), np.float32)}
    b = {"x": np.zeros((3, 4), np.float32)}
    runs = list(same_shape_runs([a, a, b, a]))
    assert [len(r) for r in runs] == [2, 1, 1]
    assert batch_signature(runs[0][0]) == batch_signature(a)
    assert batch_signature(runs[1][0]) == batch_signature(b)


# ---------------------------------------------------------------------------
# scheduler segmentation + config surface


def test_scheduler_slices_inference_segments():
    from repro.data.arrivals import Event
    from repro.runtime.scheduler import EventScheduler

    events = [Event(0.0, "data", 0, 0), Event(1.0, "inference", 0, 0),
              Event(2.0, "inference", 0, 1), Event(3.0, "inference", 0, 2),
              Event(4.0, "data", 0, 1), Event(5.0, "inference", 0, 3)]
    sched = EventScheduler(events)
    segments, singles, datas = [], [], []
    sched.run(on_data=lambda ev, b: datas.append(ev.time),
              on_inference=lambda ev: singles.append(ev.time),
              on_inference_segment=lambda seg:
                  segments.append([e.time for e in seg]))
    assert segments == [[1.0, 2.0, 3.0], [5.0]]
    assert singles == []            # the segment handler owns every one
    assert datas == [0.0, 4.0]
    assert sched.dispatched == len(events)
    assert sched.now == 5.0


def test_scheduler_per_event_without_segment_handler():
    from repro.data.arrivals import Event
    from repro.runtime.scheduler import EventScheduler

    events = [Event(1.0, "inference", 0, 0), Event(2.0, "inference", 0, 1)]
    sched = EventScheduler(events)
    singles = []
    sched.run(on_data=lambda ev, b: None,
              on_inference=lambda ev: singles.append(ev.time))
    assert singles == [1.0, 2.0]


def test_config_roundtrip_compiled_flags():
    cfg = RuntimeConfig(slots={"cv": SlotConfig()},
                        workload="single-poisson",
                        compiled=True, use_pallas=True)
    assert cfg.to_dict()["compiled"] is True
    assert cfg.to_dict()["use_pallas"] is True
    assert RuntimeConfig.from_dict(cfg.to_dict()) == cfg
    # defaults stay off: the golden regression path is the eager one
    assert RuntimeConfig().compiled is False
    assert RuntimeConfig().use_pallas is False
