"""Set-up, hooks and the measured window of the chip benchmark.

The program is driven through its front door, `edgeol_session(cfg,
model=..., benchmark=...).run(events=...)`. The harness observes it
through wrappers on public methods, installed by `Recorder.install`:

- `TrainStepCache.fused_call`: the freeze plan, the labelled batches and
  the per-step losses of every train-step call (the pretraining call's
  leaf norms too);
- `InferenceServer.publish` / `submit` / `drain` and
  `DeviceRuntime.served`: which params answered each request, its logits,
  and its host time from `submit` to the end of the `drain` that scored
  it;
- `SimFreeze.start_scenario` / `maybe_freeze`: the probe batch and the
  CKA values of each freezing pass;
- `repro.kernels.cka.ops.cka_terms` (SimFreeze's CKA Pallas kernel): the
  operands and the terms of a sample of its calls, drawn from the seed;
- `FineTuneExecutor.execute_round`, `SimFreeze.scenario_changed`: spans
  only.

With tracing on, each wrapper also opens a `jax.profiler.TraceAnnotation`
named `bench/<what>`, so that the device's idle gaps can be attributed to
what the host was doing.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def use_program():
    """Put the system under test (`<checkout>/src`) on the import path."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def load_module(path: str, name: str):
    """Import the file at `path` as module `name` (once per process)."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# compile events


class CompileLog:
    """Tallies jax.monitoring's compile-path events: XLA programs built
    (compiled, or loaded from the persistent compilation cache, which jax
    times as one event), persistent-cache hits, and seconds spent tracing,
    lowering to MLIR and compiling."""

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

    def snapshot(self) -> dict:
        return {"programs": self.programs, "cache_hits": self.cache_hits,
                **self.seconds}


# ---------------------------------------------------------------------------
# what one session did


@dataclass
class Call:
    flags: Optional[Tuple[bool, ...]]   # freeze plan (None = nothing frozen)
    batches: List[dict]                 # the labelled batches, in order
    loss: Any                           # per scan step (device array)


@dataclass
class Request:
    images: Any                         # None where the check needs none
    labels: Any
    params_index: int                   # answered by the params after this
                                        # call (-1: the weights it started from)


@dataclass
class SessionLog:
    keep: bool = False                  # keeps what the check compares
    calls: List[Call] = field(default_factory=list)
    first_norms: Any = None             # (m norms, change norms) after call 0
    final_norms: Any = None             # change norms after the session
    publishes: List[int] = field(default_factory=list)  # calls done at publish
    requests: List[Request] = field(default_factory=list)
    logits: List[Any] = field(default_factory=list)     # in served order
    latencies_s: List[float] = field(default_factory=list)
    # (calls done, order among probes and passes, ...)
    probes: List[Tuple[int, int, Any]] = field(default_factory=list)
    cka: List[Tuple[int, int, List[float]]] = field(default_factory=list)
    # sampled CKA kernel calls: (x, y, (hsic, |XX^T|, |YY^T|))
    kernel: List[Tuple[Any, Any, Any]] = field(default_factory=list)
    kernel_calls: int = 0
    wall_s: float = 0.0
    result: Any = None

    def first_cka(self):
        """(calls done at the probe, calls done at the pass, probe batch)
        of the first freezing pass."""
        if not self.cka:
            return None
        done, order, _ = self.cka[0]
        at, _, probe = [p for p in self.probes if p[1] < order][-1]
        return at, done, probe


class Recorder:
    """Installs the wrappers (`uninstall` puts the methods back) and routes
    what they see to the session in flight (`current`)."""

    # share of the CKA kernel's calls whose operands a kept session keeps,
    # and the most it keeps
    KERNEL_SHARE, KERNEL_MOST = 1 / 8, 64

    def __init__(self, init_params, seed: int, *, spans: bool = False):
        import jax
        import numpy as np

        from refcheck import leaf_norms

        self.current: Optional[SessionLog] = None
        self.spans = spans
        self._rng = np.random.default_rng(
            [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, 7])
        self._pending: List[float] = []   # submit times, not yet scored
        self._scored = 0                   # scored since the last drain ended
        self._init = init_params
        self._norms = jax.jit(lambda p, m, p0: (
            leaf_norms(m), leaf_norms(jax.tree.map(lambda a, b: a - b, p, p0))))
        self._change = jax.jit(lambda p, p0: leaf_norms(
            jax.tree.map(lambda a, b: a - b, p, p0)))
        self._undo: List[Tuple[Any, str, Any]] = []

    def _span(self, name):
        import contextlib

        import jax

        return jax.profiler.TraceAnnotation(f"bench/{name}") if self.spans \
            else contextlib.nullcontext()

    def _patch(self, cls, name, make):
        orig = getattr(cls, name)
        self._undo.append((cls, name, orig))
        setattr(cls, name, make(orig))

    def install(self):
        from repro.core.simfreeze import SimFreeze
        from repro.kernels.cka import ops as cka_ops
        from repro.runtime.device import DeviceRuntime
        from repro.runtime.executor import FineTuneExecutor
        from repro.runtime.inference import InferenceServer
        from repro.runtime.train_loop import TrainStepCache

        rec = self

        def fused_call(orig):
            def wrapped(self, plan, params, opt_state, batches):
                with rec._span("train_call"):
                    out = orig(self, plan, params, opt_state, batches)
                log = rec.current
                if log is not None:
                    flags = tuple(plan.layers) if plan is not None else None
                    log.calls.append(Call(flags, list(batches),
                                          out[2]["loss"]))
                    if len(log.calls) == 1 and log.keep:
                        log.first_norms = rec._norms(out[0], out[1].m,
                                                     rec._init)
                return out
            return wrapped

        def execute_round(orig):
            def wrapped(self, *a, **k):
                with rec._span("execute_round"):
                    return orig(self, *a, **k)
            return wrapped

        def publish(orig):
            def wrapped(self, *a, **k):
                log = rec.current
                if log is not None:
                    log.publishes.append(len(log.calls))
                with rec._span("publish"):
                    return orig(self, *a, **k)
            return wrapped

        def submit(orig):
            def wrapped(self, t, request, *a, **k):
                log = rec.current
                rec._pending.append(time.perf_counter())
                if log is not None:
                    # only the session the check compares keeps the
                    # images (3 MB a request)
                    log.requests.append(Request(
                        request["images"] if log.keep else None,
                        request["labels"], log.publishes[-1] - 1))
                with rec._span("submit"):
                    return orig(self, t, request, *a, **k)
            return wrapped

        def drain(orig):
            def wrapped(self, *a, **k):
                with rec._span("drain"):
                    out = orig(self, *a, **k)
                if rec._scored:
                    # requests are scored in arrival order
                    now = time.perf_counter()
                    done = rec._pending[:rec._scored]
                    del rec._pending[:rec._scored]
                    rec._scored = 0
                    log = rec.current
                    if log is not None:
                        log.latencies_s.extend(now - t for t in done)
                return out
            return wrapped

        def served(orig):
            def wrapped(self, logits, stream=0):
                rec._scored += 1
                log = rec.current
                if log is not None:
                    log.logits.append(logits)
                return orig(self, logits, stream)
            return wrapped

        def start_scenario(orig):
            def wrapped(self, reference_params, probe_batch):
                log = rec.current
                if log is not None:
                    log.probes.append((len(log.calls),
                                       len(log.probes) + len(log.cka),
                                       probe_batch["images"]))
                with rec._span("cka_probe"):
                    return orig(self, reference_params, probe_batch)
            return wrapped

        def maybe_freeze(orig):
            def wrapped(self, params, iters_elapsed):
                due = (self.state.iters_since_pass + iters_elapsed
                       >= self.cfg.freeze_interval)
                with rec._span("cka_probe"):
                    out = orig(self, params, iters_elapsed)
                log = rec.current
                if due and log is not None:
                    log.cka.append((len(log.calls),
                                    len(log.probes) + len(log.cka),
                                    [h[-1] for h in self.state.cka_history
                                     if h]))
                return out
            return wrapped

        def cka_terms(orig):
            def wrapped(x, y, *a, **k):
                out = orig(x, y, *a, **k)
                log = rec.current
                if log is not None and log.keep:
                    log.kernel_calls += 1
                    # the first call, and a share of the others
                    if len(log.kernel) < rec.KERNEL_MOST and (
                            log.kernel_calls == 1
                            or rec._rng.random() < rec.KERNEL_SHARE):
                        log.kernel.append((x, y, out))
                return out
            return wrapped

        def scenario_changed(orig):
            def wrapped(self, *a, **k):
                with rec._span("cka_probe"):
                    return orig(self, *a, **k)
            return wrapped

        self._patch(TrainStepCache, "fused_call", fused_call)
        self._patch(FineTuneExecutor, "execute_round", execute_round)
        self._patch(InferenceServer, "publish", publish)
        self._patch(InferenceServer, "submit", submit)
        self._patch(InferenceServer, "drain", drain)
        self._patch(DeviceRuntime, "served", served)
        self._patch(SimFreeze, "start_scenario", start_scenario)
        self._patch(SimFreeze, "maybe_freeze", maybe_freeze)
        self._patch(SimFreeze, "scenario_changed", scenario_changed)
        self._patch(cka_ops, "cka_terms", cka_terms)
        return self

    def uninstall(self):
        while self._undo:
            cls, name, orig = self._undo.pop()
            setattr(cls, name, orig)


# ---------------------------------------------------------------------------
# the cell


@dataclass
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    doc: dict            # configuration file
    ref: Any             # its plain reference module
    mix: dict            # traffic parameters


def load_cell(bench: dict, name: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    path = os.path.join(ROOT, conf["file"])
    doc = load_json(path)
    ref = load_module(path[:-len(".json")] + ".py", f"config_{w['config']}")
    mix = load_json(os.path.join(HERE, "traffic", f"{w['traffic']}.json"))
    return Cell(name, w["config"], w["traffic"], w["chips"], doc, ref, mix)


def program_model(doc: dict):
    """The program's model at the configuration's sizes. The program picks
    its layer table by name (a "-reduced" name selects the small one)."""
    from repro.configs import get_config
    from repro.models import build_model

    name = doc["program_model"]
    kw = dict(name=name, image_size=doc["image_size"],
              num_classes=doc["num_classes"])
    if "width_mult" in doc:
        kw["width_mult"] = doc["width_mult"]
    return build_model(get_config(name.replace("-reduced", "")).replace(**kw))


def make_weights(cell: Cell, seed: int):
    """The benchmark's weights, made on the device in one jitted call."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)
    params = jax.jit(lambda k: cell.ref.init(cell.doc, k))(key)
    return jax.block_until_ready(params)


def session_config(cell: Cell, seed: int):
    from repro.core.policies import PolicySpec, PolicyStackSpec
    from repro.runtime import RuntimeConfig, SlotConfig

    s = cell.mix["session"]
    stack = PolicyStackSpec(**{k: PolicySpec(name, dict(params))
                               for k, (name, params) in s["policies"].items()})
    return RuntimeConfig(
        slots={"default": SlotConfig(arch=cell.doc["program_model"].replace("-reduced", ""),
                                     benchmark="nc", policies=stack)},
        compiled=s["compiled"], use_pallas=s["use_pallas"],
        pretrain_epochs=s["pretrain_epochs"],
        inference_batch=s["inference_batch"],
        inference_window=s["inference_window"], seed=seed % (2 ** 31))


def program_events(timeline):
    from repro.data.arrivals import Event

    return [Event(a.time, a.kind, a.scenario, a.index) for a in timeline]


# model (by its loss function) -> the benchmark's weights of the newest
# session of that model in this process
_WEIGHTS: Dict[Any, Any] = {}


class Session:
    """One prepared cell: model with the benchmark's weights, stream,
    events and config. `run()` drives one whole session."""

    def __init__(self, cell: Cell, seed: int, recorder_spans: bool = False):
        gen = load_module(os.path.join(HERE, "traffic", "generator.py"),
                          "traffic_generator")
        self.cell = cell
        self.seed = seed
        self.params = make_weights(cell, seed)
        model = program_model(cell.doc)
        # the program memoizes its jitted wrap of a model by the model's
        # functions, keeping the first `init` it saw: that init reads the
        # weights of the newest session of this model from `_WEIGHTS`
        _WEIGHTS[model.loss] = self.params
        self.model = dataclasses.replace(
            model, init=lambda rng, key=model.loss: _WEIGHTS[key])
        self.bench, timeline = gen.make_traffic(
            cell.mix, num_classes=cell.doc["num_classes"],
            image_size=cell.doc["image_size"], seed=seed)
        self.events = program_events(timeline)
        self.cfg = session_config(cell, seed)
        self.recorder = Recorder(self.params, seed,
                                 spans=recorder_spans).install()
        s = cell.mix["stream"]
        streamed = s["num_scenarios"] - 1
        self.labelled_images = streamed * s["batches_per_scenario"] * \
            s["batch_size"]

    def close(self):
        self.recorder.uninstall()

    def run(self, keep: bool = False) -> SessionLog:
        """One whole session; with `keep`, it also keeps what the check
        compares (request images, a sample of the CKA kernel's operands,
        the leaf norms of the parameters' change)."""
        import jax

        from repro.runtime import edgeol_session

        log = SessionLog(keep=keep)
        self.recorder.current = log
        t0 = time.perf_counter()
        with self.recorder._span("session_build"):
            rt = edgeol_session(self.cfg, model=self.model,
                                benchmark=self.bench)
        with self.recorder._span("session_run"):
            log.result = rt.run(events=self.events)
            params = rt.fleet.devices[0].slots["default"].executor.params
            jax.block_until_ready(params)
        log.wall_s = time.perf_counter() - t0
        self.recorder.current = None
        if keep:
            log.final_norms = self.recorder._change(params, self.params)
        return log

    def images(self, log: SessionLog) -> int:
        """Images the stream delivered in one session: labelled images of
        the streamed scenarios plus every request's images."""
        return self.labelled_images + sum(len(r.labels) for r in log.requests)
