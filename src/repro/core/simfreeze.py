"""SimFreeze — the intra-tuning optimization (paper §IV-B, Algorithm 1).

Tracks per-layer CKA between the model under fine-tuning and the frozen
*reference* (initial) model, on a fixed per-scenario probe batch (the first
training batch of the scenario):

- every ``freeze_interval`` training iterations, recompute CKA for each
  *active* layer; a layer whose CKA variation rate is below ``cka_threshold``
  (default 1%) is converged -> freeze (Alg. 1 l.4-9);
- on a scenario change, recompute CKA for each *frozen* layer on the new
  scenario's probe batch; if it moved by more than the threshold, unfreeze
  (Alg. 1 l.22-26).

The output is a FreezePlan / LayerFreezePlan consumed by the execution
engine (core/freeze_plan.py) and the optimizer, so freezing translates
into skipped backward FLOPs, skipped gradient all-reduce chunks, and
skipped optimizer updates (DESIGN.md §2)."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List

import numpy as np

from repro.core.cka import cka as _cka
from repro.core.freeze_plan import FreezePlan, LayerFreezePlan
from repro.obs.host import count, span


@dataclass
class SimFreezeConfig:
    cka_threshold: float = 0.01      # 1% variation rate (paper default)
    freeze_interval: int = 200       # iterations between freezing passes
    min_history: int = 2             # CKA points before a freeze decision
    never_freeze_head: bool = True   # classifier/lm head keeps training
    use_kernel: bool = False         # route CKA through the Pallas kernel


@dataclass
class SimFreezeState:
    frozen: List[bool]
    cka_history: List[List[float]]   # per layer
    iters_since_pass: int = 0
    freezes: int = 0
    unfreezes: int = 0
    cka_flops: float = 0.0           # bookkeeping for the overhead account


class SimFreeze:
    """`features_fn(params, probe_batch) -> [acts per layer]` must present
    layers in execution order; layer i here is freeze-unit i of the model
    (groups for scanned LMs, layers for unrolled paper models)."""

    def __init__(self, num_units: int, features_fn: Callable,
                 config: SimFreezeConfig = SimFreezeConfig(),
                 scan_mode: bool = False):
        self.cfg = config
        self.num_units = num_units
        self.features_fn = features_fn
        self.scan_mode = scan_mode
        self.state = SimFreezeState(
            frozen=[False] * num_units,
            cka_history=[[] for _ in range(num_units)])
        self.reference_params = None
        self.probe_batch = None
        self._ref_feats = None

    # -- lifecycle -----------------------------------------------------------
    def start_scenario(self, reference_params, probe_batch) -> None:
        """Set the reference model and per-scenario CKA probe data
        (paper: 'the first arrived training data batch')."""
        self.reference_params = reference_params
        self.probe_batch = probe_batch
        self._ref_feats = self._reference_features(reference_params,
                                                   probe_batch)
        for h in self.state.cka_history:
            h.clear()

    # -- Alg.1 l.4-9: periodic freezing pass ----------------------------------
    def maybe_freeze(self, params, iters_elapsed: int) -> bool:
        """Returns True if the plan changed."""
        st = self.state
        st.iters_since_pass += iters_elapsed
        if st.iters_since_pass < self.cfg.freeze_interval:
            return False
        st.iters_since_pass = 0
        return self._freeze_pass(params)

    def _layer_cka(self, params, unit: int) -> float:
        feats = self.features_fn(params, self.probe_batch)
        return float(_cka(feats[unit], self._ref_feats[unit],
                                 use_kernel=self.cfg.use_kernel))

    def _reference_features(self, params, probe_batch) -> List[np.ndarray]:
        """The reference model's features on the probe batch, on the host
        (one pull per unit)."""
        with span("cka/reference"):
            feats = [np.asarray(f, np.float32)
                     for f in self.features_fn(params, probe_batch)]
        count("host_syncs", len(feats), site="cka_reference")
        return feats

    def _all_cka(self, params) -> List[float]:
        with span("cka/pass"):
            with span("cka/features"):
                feats = self.features_fn(params, self.probe_batch)
            vals = []
            for f, rf in zip(feats, self._ref_feats):
                vals.append(float(_cka(f, rf,
                                       use_kernel=self.cfg.use_kernel)))
                self.state.cka_flops += 2.0 * np.prod(np.shape(f)) * min(
                    np.shape(np.asarray(f).reshape(-1, np.shape(f)[-1]))[0],
                    np.shape(f)[-1])
        # per unit: the CKA value, and the feature map to read its shape
        count("host_syncs", len(vals), site="cka_unit")
        count("host_syncs", len(vals), site="cka_shape")
        return vals

    def _freeze_pass(self, params) -> bool:
        st, cfg = self.state, self.cfg
        vals = self._all_cka(params)
        changed = False
        for i, v in enumerate(vals):
            st.cka_history[i].append(v)
            if st.frozen[i]:
                continue  # paper §III-B: stay frozen within a scenario
            h = st.cka_history[i]
            if len(h) < cfg.min_history:
                continue
            prev = h[-2]
            variation = abs(v - prev) / max(abs(prev), 1e-8)
            if variation <= cfg.cka_threshold:
                st.frozen[i] = True
                st.freezes += 1
                changed = True
        return changed

    # -- Alg.1 l.22-26: unfreezing on scenario change -------------------------
    def scenario_changed(self, params, new_probe_batch) -> bool:
        """Re-evaluate frozen layers on the new scenario's probe data."""
        st, cfg = self.state, self.cfg
        old_vals = {i: st.cka_history[i][-1]
                    for i in range(self.num_units)
                    if st.frozen[i] and st.cka_history[i]}
        self.probe_batch = new_probe_batch
        self._ref_feats = self._reference_features(self.reference_params,
                                                   new_probe_batch)
        vals = self._all_cka(params)
        changed = False
        for i in range(self.num_units):
            if not st.frozen[i]:
                continue
            old = old_vals.get(i)
            if old is None:
                continue
            variation = abs(vals[i] - old) / max(abs(old), 1e-8)
            if variation > cfg.cka_threshold:
                st.frozen[i] = False
                st.unfreezes += 1
                changed = True
        for h in st.cka_history:
            h.clear()
        for i, v in enumerate(vals):
            st.cka_history[i].append(v)
        return changed

    # -- plan export -----------------------------------------------------------
    def plan(self):
        if self.scan_mode:
            return FreezePlan(groups=tuple(self.state.frozen))
        flags = list(self.state.frozen)
        if self.cfg.never_freeze_head:
            flags = flags[:-1] + [False] if len(flags) == self.num_units else flags
        return LayerFreezePlan(layers=tuple(flags))

    def frozen_fraction(self) -> float:
        return sum(self.state.frozen) / max(self.num_units, 1)
