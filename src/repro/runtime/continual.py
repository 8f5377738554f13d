"""ContinualRuntime — composition root of the event-driven continual-
learning loop of the paper (Fig. 1): training batches and inference
requests arrive on a shared timeline; a controller (ETuner or a baseline)
decides when to launch fine-tuning rounds and which layers are frozen; the
cost model charges per-round overheads (system init / load / save),
per-plan recompiles and XLA-*measured* compute FLOPs.

The runtime itself is deliberately thin. It wires four owned subsystems
(DESIGN.md §1):

- `EventScheduler` (runtime/scheduler.py) — the priority-ordered timeline,
  wall-clock/`busy_until` device occupancy, scenario boundaries;
- `InferenceServer` (runtime/inference.py) — request serving, the
  arrival-time params-visibility seam, opt-in micro-batched serving;
- `FineTuneExecutor` (runtime/executor.py) — round execution, the replay
  buffer, and `RoundHook`s (SimSiam semi-supervised pass, fake-quant QAT);
- `CostLedger` (runtime/ledger.py) — all time/energy/FLOPs accounting;

plus, optionally, a **`ModelPool`** (runtime/modelpool.py, DESIGN.md §9):
one model slot per modality — its own params/optimizer/steps/replay/
controller and per-slot cost calibration — multiplexed over the one
shared device timeline under a device memory budget (cold slots pay a
real load/save swap charge). Without a pool the runtime runs its single
model under the reserved "default" slot, byte-identical to the pre-pool
behaviour (the golden regression suite pins this).

Controllers implement the `ControllerProtocol` documented in
core/controller.py; the runtime drives them from scheduler callbacks and
never reaches into their internals. Monolithic controllers predating the
policy decomposition are adapted transparently
(`repro.core.policies.adapt_controller`), and a controller's optional
`publish_policy` decides when a round's params reach serving.

Construction (DESIGN.md §11): the front door is the declarative
`RuntimeConfig` — `ContinualRuntime.from_config(cfg, ...)` or
`edgeol_session(cfg)` — with live objects (a custom benchmark, a
pre-built controller/pool, a cost model) injected alongside the config.
The legacy ~18-kwarg constructor still works but is deprecated: it
delegates to the same resolution path and emits a `DeprecationWarning`.

Faithfulness notes:
- the model is pre-trained on scenario 0 ("originally well-trained in the
  first scenario"); costs are accounted from scenario 1 on;
- a small replay buffer stands in for the CWR anti-forgetting technique of
  the CORe50 paper (documented substitution, DESIGN.md);
- inference requests resolve their params at *arrival* time via the
  InferenceServer's visibility seam; a round occupies wall-clock, which is
  the "outdated model" effect LazyTune must balance (paper §III-A). Note
  the pre-decomposition monolith served mid-round requests by the round's
  freshly trained params (visible == latest); that behaviour is kept
  bug-compatible and the seam documented in DESIGN.md §5;
- validation accuracy (5% split) drives LazyTune; inference accuracy is
  only recorded, never used by the controller (paper §IV-A).
"""
from __future__ import annotations

import dataclasses
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from repro.data.arrivals import Event, build_timeline
from repro.data.streams import ContinualBenchmark
from repro.obs.host import span
from repro.obs.trace import NULL_TRACER
from repro.optim import AdamWConfig
from repro.runtime.config import (DeviceConfig, HookSpec, RuntimeConfig,
                                  SlotConfig, resolve_session)
from repro.runtime.costmodel import EdgeCostModel, scale_cost
from repro.runtime.executor import (FineTuneExecutor, ReplayBuffer,
                                    RoundHook, fake_quant, quantized_model)
from repro.runtime.ledger import DEFAULT_DEVICE, DEFAULT_MODEL, CostLedger
from repro.runtime.modelpool import ModelPool
from repro.runtime.train_loop import TrainStepCache

# legacy aliases (pre-decomposition import sites)
_fake_quant = fake_quant
_quantized_model = quantized_model


@dataclass
class RunResult:
    avg_inference_acc: float
    total_time_s: float
    total_energy_j: float
    compute_tflops: float
    rounds: int
    recompiles: int
    inference_accs: List[float] = field(default_factory=list)
    breakdown: Dict[str, float] = field(default_factory=dict)
    controller_stats: Dict[str, Any] = field(default_factory=dict)
    val_curve: List[float] = field(default_factory=list)
    # per-arrival-stream attribution (multi-stream workloads): stream id ->
    # {time_s, energy_j, flops, rounds, preemptions, avg_inference_acc,
    #  inferences, latency_p50, latency_p95}
    per_stream: Dict[int, Dict[str, float]] = field(default_factory=dict)
    # per-model-slot attribution (ModelPool; single-model runs report one
    # "default" slot): slot -> {time_s, energy_j, flops, rounds, swaps,
    # avg_inference_acc, inferences}
    per_model: Dict[str, Dict[str, float]] = field(default_factory=dict)
    # per-device attribution (DeviceFleet; single-device runs report one
    # "dev0"): device -> {time_s, energy_j, flops, rounds, swaps, syncs,
    # avg_inference_acc, inferences, streams, utilization, evicted}
    per_device: Dict[str, Dict[str, float]] = field(default_factory=dict)
    # QoS: total round splits absorbed by lower-priority streams' rounds
    preemptions: int = 0
    # ModelPool: total cold-slot swap-ins charged to the run
    swaps: int = 0
    # DeviceFleet: per-device cross-device sync charges (federated merges)
    syncs: int = 0
    # detector mode: drift-confirmation probe passes fired
    probes: int = 0
    # host spans and counters of this run (repro.obs.host.since): real
    # seconds, not deterministic, so out of equality and summary()
    host: Dict[str, Any] = field(default_factory=dict, compare=False,
                                 repr=False)

    def summary(self) -> str:
        return (f"acc={self.avg_inference_acc*100:.2f}% "
                f"time={self.total_time_s:.1f}s energy={self.total_energy_j:.1f}J "
                f"rounds={self.rounds} recompiles={self.recompiles} "
                f"tflops={self.compute_tflops:.2f}")


@dataclass
class _SlotState:
    """Per-model-slot runtime state assembled by `run()`: the single-model
    path has exactly one ("default"); a ModelPool run has one per slot."""
    name: str
    model: Any
    bench: ContinualBenchmark
    controller: Any
    steps: TrainStepCache
    executor: FineTuneExecutor
    reference_params: Any = None


class ContinualRuntime:
    def __init__(self, model, benchmark: Optional[ContinualBenchmark],
                 controller,
                 cost_model: Optional[EdgeCostModel] = None,
                 opt_cfg=None, seed: int = 0,
                 boundaries: str = "oracle",       # 'oracle' | 'detector'
                 replay_batches: int = 2,
                 pretrain_epochs: int = 3,
                 inference_batch: int = 16,
                 quant_bits: int = 0,
                 unlabeled_fraction: float = 0.0,
                 calibrate_cost: bool = True,
                 inference_window: float = 0.0,
                 extra_hooks: Optional[List[RoundHook]] = None,
                 stream_benchmarks: Optional[Dict[int, ContinualBenchmark]] = None,
                 controller_factory: Optional[Callable[[Any], Any]] = None,
                 preemptible: bool = False,
                 preempt_resume_cost_s: float = 0.0,
                 model_pool: Optional[ModelPool] = None):
        """Deprecated legacy kwarg constructor. It builds the equivalent
        `RuntimeConfig` (quant_bits/unlabeled_fraction become per-slot
        `HookSpec`s) and delegates to the same resolution path as
        `from_config`, replaying bit-exact — the golden regression pins
        this — while steering callers to the declarative API."""
        warnings.warn(
            "ContinualRuntime legacy kwarg construction is deprecated; "
            "build a RuntimeConfig and use "
            "ContinualRuntime.from_config(cfg, ...) or edgeol_session(cfg) "
            "(DESIGN.md §11)", DeprecationWarning, stacklevel=2)
        hook_specs = []
        if quant_bits:
            hook_specs.append(HookSpec("fake-quant", {"bits": quant_bits}))
        if unlabeled_fraction:
            hook_specs.append(HookSpec("simsiam",
                                       {"fraction": unlabeled_fraction}))
        cfg = RuntimeConfig(
            slots={"default": SlotConfig(hooks=tuple(hook_specs))},
            seed=seed, boundaries=boundaries,
            replay_batches=replay_batches, pretrain_epochs=pretrain_epochs,
            inference_batch=inference_batch, calibrate_cost=calibrate_cost,
            inference_window=inference_window, preemptible=preemptible,
            preempt_resume_cost_s=preempt_resume_cost_s)
        self._init(**resolve_session(
            cfg, model=model, benchmark=benchmark, controller=controller,
            controller_factory=controller_factory,
            stream_benchmarks=stream_benchmarks, model_pool=model_pool,
            cost_model=cost_model, opt_cfg=opt_cfg,
            extra_hooks=extra_hooks))

    @classmethod
    def from_config(cls, cfg: RuntimeConfig, *, model=None, benchmark=None,
                    controller=None, controller_factory=None,
                    stream_benchmarks=None, model_pool=None,
                    cost_model=None, opt_cfg=None, extra_hooks=None,
                    workload_spec=None) -> "ContinualRuntime":
        """The declarative front door (DESIGN.md §11): materialize a
        session from a validated `RuntimeConfig`. Anything the config
        cannot express serializably — a custom benchmark object, a
        pre-built controller/factory/pool, a cost model, live RoundHooks,
        an already-scaled `WorkloadSpec` — is injected as a keyword and
        wins over what the config would build. When the config names a
        workload preset, the per-stream benchmarks and the compiled event
        timeline are materialized too and `run()` replays them by
        default."""
        rt = cls.__new__(cls)
        rt._init(**resolve_session(
            cfg, model=model, benchmark=benchmark, controller=controller,
            controller_factory=controller_factory,
            stream_benchmarks=stream_benchmarks, model_pool=model_pool,
            cost_model=cost_model, opt_cfg=opt_cfg,
            extra_hooks=extra_hooks, workload_spec=workload_spec))
        return rt

    def _init(self, *, model, benchmark, controller, cost_model, opt_cfg,
              seed, boundaries, replay_batches, pretrain_epochs,
              inference_batch, calibrate_cost, inference_window, hooks,
              slot_hooks, stream_benchmarks, controller_factory,
              preemptible, preempt_resume_cost_s, model_pool,
              compiled=False, use_pallas=False, session_events=None,
              devices=(), routing="static", aggregate_every=0.0,
              telemetry=None):
        # ModelPool construction path: the pool's slots carry the models,
        # benchmarks and (optionally) controllers; model/benchmark/
        # controller may be None and default to the first slot's. Slot
        # controllers missing from the pool are built through the
        # `controller_factory` seam, called with the *slot name*.
        self.pool = model_pool
        if model_pool is not None:
            first = next(iter(model_pool.slots.values()))
            model = model if model is not None else first.model
            benchmark = benchmark if benchmark is not None else first.benchmark
        self.model = model
        self.bench = benchmark
        self.controller = controller
        # multi-stream workloads: stream id -> its own benchmark (falls back
        # to `benchmark`, or to the stream's slot benchmark under a pool);
        # streams > 0 get controllers from `controller_factory(stream)` when
        # given, else share `controller` (one policy object observing every
        # stream). Under a pool the same factory seam builds *per-slot*
        # controllers instead, called with the slot name.
        self.stream_benchmarks = dict(stream_benchmarks or {})
        self.controller_factory = controller_factory
        self.cost = cost_model if cost_model is not None else EdgeCostModel()
        self.opt_cfg = opt_cfg or AdamWConfig(lr=1e-3)
        self.seed = seed
        self.boundaries = boundaries
        self.replay_batches = replay_batches
        self.pretrain_epochs = pretrain_epochs
        self.inference_batch = inference_batch
        self.calibrate_cost = calibrate_cost
        self.inference_window = inference_window
        # QoS: when True, fine-tuning rounds run as preemptible
        # reservations — a strictly-higher-priority inference arrival
        # splits the in-flight round (served at its arrival instant
        # instead of waiting for the round's end) and the round resumes,
        # its cost charged in segments that sum to the unpreempted charge.
        # Default False keeps the golden single-stream regression
        # bit-exact (rounds complete synchronously at trigger time).
        self.preemptible = preemptible
        # QoS: modeled checkpoint-resume overhead paid on each round split
        # (charged to the preempting stream; 0.0 = legacy free splits)
        self.preempt_resume_cost_s = preempt_resume_cost_s
        # compiled hot path (DESIGN.md §12): all training goes through the
        # donated fused-scan step, serving through deferred vmapped
        # dispatch, and the event loop through segment slicing. Default
        # False keeps the golden regression on the legacy eager path.
        # `segment` (overridable before run(); the equivalence property
        # test forces it off) additionally fuses whole same-shape runs —
        # per-event compiled execution is the same scan program at trip
        # count 1, so toggling it never moves a bit.
        self.compiled = bool(compiled)
        self.use_pallas = bool(use_pallas)
        self.segment = True
        # round hooks: model-wrapping ones bind first so every later
        # consumer (train steps, serving, SimSiam features) sees the
        # wrapped model. `hooks` wrap the single model; `slot_hooks` bind
        # per pool slot (a quantized CV slot next to an fp32 NLP slot) and
        # wrap that slot's model in _build_slots.
        self.hooks: List[RoundHook] = list(hooks or [])
        self.slot_hooks: Dict[str, List[RoundHook]] = {
            k: list(v) for k, v in (slot_hooks or {}).items()}
        for h in self.hooks:
            self.model = h.bind(self.model)
        # DeviceFleet knobs (DESIGN.md §13): device specs, initial stream
        # routing and the federated aggregation period. Empty `devices`
        # means a fleet of one reference device — the legacy session.
        self.devices = tuple(devices or ())
        self.routing = routing
        self.aggregate_every = float(aggregate_every)
        # optional straggler-mitigation config, picked up by the fleet
        # (None = StragglerConfig defaults)
        self.straggler_config = None
        # observability (DESIGN.md §14): a live `repro.obs.Telemetry`
        # bundle (tracer + metrics + sinks) built by resolve_session when
        # `RuntimeConfig.telemetry` is active; None (the default) keeps
        # every instrumented path on the falsy NULL_TRACER — bit-exact
        # and allocation-free. After a run: ``rt.telemetry.snapshot()``.
        self.telemetry = telemetry
        # the DeviceFleet the last run() drove (live handle for tests)
        self.fleet = None
        # a config-built session may carry its workload's compiled event
        # timeline; run() replays it when no explicit events are passed
        self._session_events: Optional[List[Event]] = session_events
        # single-model step cache lives on the runtime (reused across
        # run() calls); pool slots build their own caches per run
        self.steps = None if model_pool is not None else \
            TrainStepCache(model=self.model, opt_cfg=self.opt_cfg)

    @property
    def session_events(self) -> Optional[List[Event]]:
        """The workload timeline a config-built session will replay when
        `run()` is called without explicit events (None otherwise)."""
        return self._session_events

    # -------------------------------------------------------------------
    def _build_slots(self, ledger: CostLedger, rng: np.random.Generator,
                     device: Optional[DeviceConfig] = None
                     ) -> Dict[str, _SlotState]:
        """Assemble per-slot runtime state for one device (`device=None`
        means the reference "dev0" at identity cost scales — a bitwise
        no-op on every cost figure). The single-model path builds exactly
        one "default" slot wired to the runtime's own model/steps/cost
        and the *shared* rng — preserving the legacy RNG consumption
        order bit-for-bit."""
        spec = device if device is not None else DeviceConfig(DEFAULT_DEVICE)
        tracer = self.telemetry.tracer if self.telemetry is not None \
            else NULL_TRACER
        slots: Dict[str, _SlotState] = {}
        if self.pool is None:
            replay = ReplayBuffer(
                self.bench.scenarios[0].train_batches[:self.replay_batches])
            executor = FineTuneExecutor(
                self.steps,
                scale_cost(self.cost, speed=spec.speed_scale,
                           energy=spec.energy_scale),
                ledger, replay, rng=rng,
                hooks=self.hooks, calibrate_cost=self.calibrate_cost,
                device_name=spec.name, speed_scale=spec.speed_scale,
                preempt_resume_cost_s=self.preempt_resume_cost_s,
                compiled=self.compiled, fuse=self.segment, tracer=tracer)
            slots[DEFAULT_MODEL] = _SlotState(
                DEFAULT_MODEL, self.model, self.bench, self.controller,
                self.steps, executor)
            return slots
        for i, slot in enumerate(self.pool.slots.values()):
            # per-slot RoundHooks (RuntimeConfig SlotConfig.hooks): wrap
            # this slot's model only — its train steps, serving lane and
            # pretraining all see the wrapped model, other slots stay
            # untouched (a quantized CV slot next to an fp32 NLP slot)
            hooks = self.slot_hooks.get(slot.name, [])
            model = slot.model
            for h in hooks:
                model = h.bind(model)
            ctrl = slot.controller
            if ctrl is None and self.controller_factory is not None:
                ctrl = self.controller_factory(slot.name)
            if ctrl is None:
                ctrl = self.controller
            if ctrl is None:
                raise ValueError(
                    f"slot {slot.name!r} has no controller: set "
                    f"ModelSlot.controller or pass controller_factory")
            steps = TrainStepCache(model=model, opt_cfg=self.opt_cfg)
            replay = ReplayBuffer(
                slot.benchmark.scenarios[0].train_batches[:self.replay_batches])
            executor = FineTuneExecutor(
                steps,
                scale_cost(slot.cost, speed=spec.speed_scale,
                           energy=spec.energy_scale),
                ledger, replay,
                rng=np.random.default_rng([self.seed, i]),
                hooks=hooks, calibrate_cost=self.calibrate_cost,
                model_name=slot.name, device_name=spec.name,
                speed_scale=spec.speed_scale,
                preempt_resume_cost_s=self.preempt_resume_cost_s,
                compiled=self.compiled, fuse=self.segment, tracer=tracer)
            slots[slot.name] = _SlotState(slot.name, model,
                                          slot.benchmark, ctrl, steps,
                                          executor)
        return slots

    # -------------------------------------------------------------------
    def run(self, events: Optional[List[Event]] = None,
            inferences_total: Optional[int] = None,
            data_dist: Optional[str] = None,
            inf_dist: Optional[str] = None) -> RunResult:
        """Drive the full continual-learning session. The timeline comes
        from, in precedence order: explicit `events`, the config-built
        session's compiled workload (`session_events`), or a legacy
        timeline generated from `inferences_total`/`data_dist`/`inf_dist`
        (defaults 60/"poisson"/"poisson") — the generation knobs apply
        only to that last case."""
        timeline_kw = {k: v for k, v in (("inferences_total",
                                          inferences_total),
                                         ("data_dist", data_dist),
                                         ("inf_dist", inf_dist))
                       if v is not None}
        if timeline_kw and (events is not None
                            or self._session_events is not None):
            warnings.warn(
                f"run(): {sorted(timeline_kw)} only shape the generated "
                f"legacy timeline and are ignored when events are "
                f"supplied (explicit or from the session's workload "
                f"config)", UserWarning, stacklevel=2)
        bench = self.bench
        if events is None and self._session_events is not None:
            # config-built session: replay the workload's compiled timeline
            events = list(self._session_events)
        if events is None:
            events = build_timeline(
                num_scenarios=bench.num_scenarios - 1,
                batches_per_scenario=len(bench.scenarios[1].train_batches),
                inferences_total=timeline_kw.get("inferences_total", 60),
                seed=self.seed,
                data_dist=timeline_kw.get("data_dist", "poisson"),
                inf_dist=timeline_kw.get("inf_dist", "poisson"))
            # shift scenario ids by 1 (scenario 0 = pretraining)
            events = [dataclasses.replace(e, scenario=e.scenario + 1)
                      for e in events]

        # --- delegate to the fleet (DESIGN.md §13): the default session
        # is a DeviceFleet of one reference device, whose device 0 is
        # built through the exact legacy code path — the golden regression
        # pins single-device runs bit-for-bit. `RuntimeConfig.devices` /
        # `routing` / `aggregate_every` turn the same session into a
        # multi-device one.
        from repro.runtime.fleet import DeviceFleet

        self.fleet = DeviceFleet(self)
        return self.fleet.run(events)


def edgeol_session(cfg: RuntimeConfig, **inject) -> ContinualRuntime:
    """Declarative session front door (DESIGN.md §11): build a ready
    `ContinualRuntime` from a `RuntimeConfig`. Keyword injections are the
    same as `ContinualRuntime.from_config` (live objects win over what
    the config would build). When the config names a workload preset,
    `session.run()` replays its compiled event timeline::

        res = edgeol_session(RuntimeConfig(workload="mixed", ...)).run()
    """
    with span("build"):
        return ContinualRuntime.from_config(cfg, **inject)
