"""Smoke run of the online-learning runtime on one TPU chip.

Drives one ETuner session — LazyTune decides when to fine-tune, SimFreeze
decides which layers to freeze, and SimFreeze's CKA probe runs through the
compiled Pallas kernel — through the declarative front door,
`edgeol_session`, with MobileNetV2 at its published width (width 1.0,
128x128 images, 50 classes; random weights from the seed). Only the
stream is cut short: 5 scenarios of 6 batches of 16 images, 24 requests.

    python chip_smoke.py

Phases, in one process:

1. set-up: build a session and run it once, cold. Every XLA program the
   run needs (train steps per freeze plan, serving forwards, the CKA
   kernel per probe shape) is built here — compiled, or loaded from a
   warm persistent compilation cache — so compile time is set-up.
2. run: a fresh session with the same config runs against the built
   programs; its wall clock ends in `jax.block_until_ready` on the
   trained params.
3. checks: rounds, requests served, recompiles and set-up programs are
   positive, the warm run builds no program, accuracy is finite, and the
   chip's CKA kernel on the session's own probe activations agrees with
   `repro.kernels.cka.ref` (fp32 tolerance of tests/test_kernels.py).

`time_s`/`energy_j` are the runtime's modelled edge-device cost
(`EdgeCostModel`), not chip time. The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``; it is printed only when every
phase passed. Without a TPU the script exits non-zero before running
anything.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

MODEL = "mobilenetv2"
# scenario 0 pretrains; scenarios 1-4 stream 6 batches each, enough rounds
# for SimFreeze to change the freeze plan and train under the new one
BENCH = dict(num_classes=50, image_size=128, num_scenarios=5, batches=6,
             batch_size=16)
INFERENCES = 24
# tests/test_kernels.py's fp32 tolerance for the CKA kernel against ref.py
CKA_RTOL = 1e-4


def require_tpu():
    """The chip's devices; exits non-zero when JAX found no TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); nothing was run")
    return devices


def session_config():
    from repro.core.policies import PolicySpec, PolicyStackSpec
    from repro.runtime import RuntimeConfig, SlotConfig

    etuner = PolicyStackSpec(
        trigger=PolicySpec("lazytune", {"max_batches_needed": 8.0}),
        freeze=PolicySpec("simfreeze", {"freeze_interval": 6}),
        drift=PolicySpec("none"))
    return RuntimeConfig(
        slots={"default": SlotConfig(arch=MODEL, benchmark="nc",
                                     benchmark_kw=BENCH, policies=etuner)},
        compiled=True, use_pallas=True, pretrain_epochs=1)


def run_session(model):
    """One session through the front door; returns (runtime, result,
    trained params), with the params on the device."""
    import jax

    from repro.runtime import edgeol_session

    rt = edgeol_session(session_config(), model=model)
    res = rt.run(inferences_total=INFERENCES)
    params = rt.fleet.devices[0].slots["default"].executor.params
    jax.block_until_ready(params)
    return rt, res, params


class CompileLog:
    """Tallies jax.monitoring's compile-path events: XLA programs built
    (compiled, or loaded from the persistent compilation cache, which
    jax times as one event), persistent-cache hits, and seconds spent
    tracing, lowering to MLIR and compiling. Tracing spans of nested jits
    overlap, so the tracing seconds may count some time twice."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "tracing",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowering",
              "/jax/core/compile/backend_compile_duration": "compiling"}

    def __init__(self):
        import jax

        self.programs, self.cache_hits = 0, 0
        self.seconds = dict.fromkeys(self.EVENTS.values(), 0.0)
        jax.monitoring.register_event_duration_secs_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, duration, **_):
        name = self.EVENTS.get(event)
        if name is not None:
            self.seconds[name] += duration
            self.programs += name == "compiling"

    def _on_event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    def summary(self) -> str:
        secs = ", ".join(f"{k} {v} s" for k, v in self.seconds.items())
        return (f"{self.programs} XLA programs built, {self.cache_hits} of "
                f"them loaded from the persistent cache; {secs}")


def check_cka_kernel(rt, params):
    """The session's CKA probe — SimFreeze's probe batch through the
    scenario's reference params and through the trained params — via the
    compiled kernel, against the pure-jnp oracle at fp32 precision.
    Returns one row per freeze unit."""
    import jax
    import numpy as np

    import jax.numpy as jnp

    from repro.core.cka import cka
    from repro.kernels import resolve_interpret
    from repro.kernels.cka import ops as cka_ops
    from repro.kernels.cka.ref import cka_ref

    def centered(f):  # [B, ...] activations -> centered [B, features]
        f = jnp.asarray(f, jnp.float32).reshape(f.shape[0], -1)
        return f - f.mean(axis=0)

    sf = rt.controller.simfreeze
    if sf.probe_batch is None:
        raise AssertionError("SimFreeze never took a probe batch")
    ref_feats = sf.features_fn(sf.reference_params, sf.probe_batch)
    cur_feats = sf.features_fn(params, sf.probe_batch)
    widest = max(ref_feats, key=lambda f: f[0].size)
    x = centered(widest)
    hlo = cka_ops.cka_terms.lower(x, x).as_text()
    mode = "interpret" if resolve_interpret() else "compiled"
    if mode == "compiled" and "tpu_custom_call" not in hlo:
        raise AssertionError("CKA kernel lowered without a tpu_custom_call")
    rows = []
    for unit, (a, b) in enumerate(zip(cur_feats, ref_feats)):
        got = float(cka(a, b, use_kernel=True))
        with jax.default_matmul_precision("highest"):
            want = float(cka_ref(centered(a), centered(b)))
        n, d = centered(a).shape
        rows.append((unit, n, d, got, want))
        np.testing.assert_allclose(got, want, rtol=CKA_RTOL,
                                   err_msg=f"CKA kernel, unit {unit}")
    return mode, rows


def main() -> int:
    from repro.launch.platform import bootstrap

    bootstrap()
    devices = require_tpu()
    import jax

    from repro.configs import get_config
    from repro.models import build_model

    dev = devices[0]
    print(f"platform: {dev.platform}  device_kind: {dev.device_kind}  "
          f"devices: {len(devices)}  jax: {jax.__version__}", flush=True)
    compiles = CompileLog()

    t0 = time.perf_counter()
    cfg = get_config(MODEL)
    model = build_model(cfg)
    _, cold, _ = run_session(model)
    setup_s = time.perf_counter() - t0
    setup_programs = compiles.programs
    print(f"model: {cfg.name} width_mult={cfg.width_mult} "
          f"image={cfg.image_size}x{cfg.image_size} "
          f"classes={cfg.num_classes} freeze_units={model.num_freeze_units}",
          flush=True)
    print(f"set-up: {setup_s} s (session build + cold run; "
          f"{compiles.summary()})", flush=True)

    t0 = time.perf_counter()
    rt, res, params = run_session(model)
    run_s = time.perf_counter() - t0
    window_programs = compiles.programs - setup_programs
    served = int(res.per_model["default"]["inferences"])
    print(f"run: {run_s} s wall (session build + run, ended by "
          f"block_until_ready; {window_programs} XLA programs built)",
          flush=True)
    print(f"rounds: {res.rounds}  inferences: {served}  "
          f"recompiles: {res.recompiles}  "
          f"avg_inference_acc: {res.avg_inference_acc}  "
          f"controller: {res.controller_stats}", flush=True)
    print(f"modelled edge cost (EdgeCostModel, not chip time): "
          f"time_s={res.total_time_s} energy_j={res.total_energy_j}",
          flush=True)
    print(f"cold and warm sessions agree: "
          f"{(cold.rounds, cold.avg_inference_acc) == (res.rounds, res.avg_inference_acc)}",
          flush=True)

    failures = []
    if res.rounds < 1:
        failures.append("no fine-tuning round ran")
    if served < 1:
        failures.append("no inference request was served")
    if res.recompiles < 2:
        failures.append(f"{res.recompiles} recompiles (no freeze-plan "
                        f"change reached the train step)")
    if setup_programs < 1:
        failures.append("no XLA program was built during set-up")
    if window_programs:
        failures.append(f"{window_programs} XLA programs were built in the "
                        f"warm run")
    if not math.isfinite(res.avg_inference_acc):
        failures.append(f"avg_inference_acc is {res.avg_inference_acc}")

    mode, rows = check_cka_kernel(rt, params)
    for unit, n, d, got, want in rows:
        print(f"cka unit {unit:2d} [{n}x{d}]: kernel={got!r} ref={want!r} "
              f"rel_err={abs(got - want) / max(abs(want), 1e-12):.2e}",
              flush=True)
    print(f"cka kernel: {mode}, {len(rows)} units within rtol={CKA_RTOL} "
          f"of ref.py", flush=True)
    if failures:
        raise SystemExit("chip_smoke: " + "; ".join(failures))

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
