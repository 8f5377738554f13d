"""The check that decides `correct`, driven end to end on the CPU at a
small size: the harness's look for a chip is skipped, everything else of
a run (set-up, window, reference replay, limits) runs as on the chip.

A sound program passes. Each fault a cell can have, planted in the
program underneath the timed path, turns `correct` false: a train step
that returns its state unchanged, half of every batch left out (the mean
taken over the rest), an answer altered where it is produced (the served
logits, the CKA kernel's terms), stale params published to serving. So
does the control: the reference computed in bfloat16, the precision below
the configuration's float32, put in the program's place. The cells have
no exchange between chips.

The small size: the reduced MobileNetV2 (width 0.5, four blocks, 32x32
images, 8 classes) on four scenarios of three batches of 16."""
import copy
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import refcheck

run = harness.load_module(os.path.join(harness.HERE, "run.py"), "chip_run")

SEED = 2 ** 31 + 21


def tiny_cell():
    doc = copy.deepcopy(harness.load_json(
        os.path.join(harness.HERE, "configs", "mobilenetv2.json")))
    doc.update(program_model="mobilenetv2-reduced", image_size=32,
               num_classes=8, width_mult=0.5,
               blocks=[[1, 16, 1, 1], [6, 24, 1, 2], [6, 32, 1, 2],
                       [6, 64, 1, 2]])
    ref = harness.load_module(
        os.path.join(harness.HERE, "configs", "mobilenetv2.py"),
        "config_mobilenetv2")
    mix = harness.load_json(os.path.join(harness.HERE, "traffic",
                                         "nc.etuner.json"))
    mix["stream"].update(num_scenarios=4, batches_per_scenario=3)
    return harness.Cell("mbv2.nc.etuner", "mobilenetv2", "nc.etuner", 1, doc,
                        ref, mix)


def measure(tmp_path):
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    out = run.measure(tiny_cell(), SEED, 1.0, False, jax.devices(), bench,
                      str(tmp_path))
    out.pop("_lines")
    return out


def test_sound_run_is_correct(tmp_path):
    out = measure(tmp_path)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"images_per_s", "setup_s"}
    assert [k for k in out if not k.startswith("_")][-1] == "check"


def test_state_left_unchanged_is_caught(tmp_path, monkeypatch):
    from repro.runtime.train_loop import TrainStepCache

    orig = TrainStepCache.fused_call

    def unchanged(self, plan, params, opt_state, batches):
        copy_ = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
        _, _, metrics = orig(self, plan, copy_(params), copy_(opt_state),
                             batches)
        return params, opt_state, metrics

    monkeypatch.setattr(TrainStepCache, "fused_call", unchanged)
    out = measure(tmp_path)
    assert not out["correct"]
    assert out["check"]["worst_update_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_caught(tmp_path, monkeypatch):
    from repro.runtime.train_loop import TrainStepCache

    orig = TrainStepCache.fused_call

    def halved(self, plan, params, opt_state, batches):
        half = [{k: v[:len(v) // 2] for k, v in b.items()} for b in batches]
        return orig(self, plan, params, opt_state, half)

    monkeypatch.setattr(TrainStepCache, "fused_call", halved)
    assert not measure(tmp_path)["correct"]


def test_altered_answer_is_caught(tmp_path, monkeypatch):
    from repro.runtime.inference import InferenceServer

    orig = InferenceServer._forward_stack

    def altered(self, *a, **k):
        out = orig(self, *a, **k)
        return out[:, ::-1]          # each image gets another's logits

    monkeypatch.setattr(InferenceServer, "_forward_stack", altered)
    out = measure(tmp_path)
    assert not out["correct"]
    assert out["check"]["serve_gap"]["value"] > \
        out["check"]["serve_gap"]["limit"]


def test_altered_cka_value_shows_in_the_record(tmp_path, monkeypatch):
    """A CKA kernel whose answer is altered where it is produced: its
    terms are compared with the plain terms of the operands it was given."""
    from repro.kernels.cka import ops

    orig = ops.cka_terms

    def altered(x, y, **k):
        hsic, nx, ny = orig(x, y, **k)
        return hsic * 0.9, nx, ny

    monkeypatch.setattr(ops, "cka_terms", altered)
    out = measure(tmp_path)
    assert not out["correct"]
    gap = out["check"]["cka_kernel_gap"]
    assert gap["value"] == pytest.approx(0.1, rel=1e-3)
    assert gap["value"] > gap["limit"]


def test_stale_publish_is_caught(tmp_path, monkeypatch):
    """Serving gets the params of the call before the one that trained
    them."""
    from repro.runtime.inference import InferenceServer

    orig = InferenceServer.publish
    last = {}

    def stale(self, params, *a, **k):
        before = last.get(id(self), params)
        last[id(self)] = params
        return orig(self, before, *a, **k)

    monkeypatch.setattr(InferenceServer, "publish", stale)
    out = measure(tmp_path)
    assert not out["correct"]
    assert any(out["check"][k]["value"] > out["check"][k]["limit"]
               for k in ("serve_gap", "serve_acc_gap") if k in out["check"])


def test_kernel_terms_are_the_plain_terms():
    """The reference's float64 terms against the kernel's float32 terms,
    on centred operands of a probe's shape."""
    from repro.kernels.cka import ops

    x, y = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 4096))
    x, y = x - x.mean(0), 0.5 * x + y - (0.5 * x + y).mean(0)
    got = np.array([float(v) for v in ops.cka_terms(x, y)])
    want = refcheck.kernel_terms(np.asarray(x), np.asarray(y))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert refcheck.kernel_terms(x, y, "high") == pytest.approx(want,
                                                                rel=1e-3)


def test_bfloat16_control_fails(tmp_path):
    """The reference in bfloat16, in the program's place, against the
    float32 reference, on the session's own decisions."""
    cell = tiny_cell()
    session = harness.Session(cell, SEED)
    try:
        log = session.run(keep=True)
    finally:
        session.close()
    want = refcheck.replay(cell.ref, cell.doc, session.params, log)
    control = refcheck.replay(cell.ref, cell.doc, session.params, log,
                              control=True)
    numbers = refcheck.compare(control.observed, want)
    numbers = {k: numbers[k] for k in cell.doc["limits"]}
    ok, _ = refcheck.verdict(numbers, cell.doc["limits"])
    assert not ok, numbers
    assert np.isfinite(list(numbers.values())).all()


def test_traced_run_reads_the_per_layer_metrics(tmp_path, monkeypatch):
    """The `--trace 1` path end to end on the CPU: the CPU has no device
    plane, so the device readers read nothing; the device's kind is
    stood in for so that the peaks table is found."""
    info = run.device_info
    monkeypatch.setattr(run, "device_info", lambda d: {
        **info(d), "kind": "TPU v5 lite"})
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    out = run.measure(tiny_cell(), SEED, 1.0, True, jax.devices(), bench,
                      str(tmp_path))
    out.pop("_lines")
    assert out["correct"], out["check"]
    assert {"setup_lowering_s", "window_compiles", "avg_inference_acc",
            "request_ms.p95", "mfu"} <= set(out["metrics"])
    assert out["metrics"]["window_compiles"]["value"] == 0
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
