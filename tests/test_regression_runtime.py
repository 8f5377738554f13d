"""Fixed-seed regression tests pinning the decomposed runtime to the
pre-refactor monolith.

``tests/data/golden_runtime.json`` was first captured on jax 0.4.37 by
running the original single-method ``ContinualRuntime.run`` (commit
780bab6's runtime) on small fixed-seed configs. It was re-captured on jax
0.9.0, where the decomposed runtime of commit 716250d and the current
runtime produce identical figures: XLA's CPU numerics and FLOP counts
moved between the jax versions, the runtime's behaviour did not. The
decomposed
scheduler/executor/ledger/server runtime must reproduce every recorded
figure — accuracy trace, round/recompile counts, and the full CostLedger
breakdown — with micro-batching disabled.

Also covers the micro-batched-serving equivalence claim: per-request
accuracies are unchanged by coalescing for models whose predict is
per-example independent (LayerNorm ViT here; batch-statistic models like
the BN CNNs see tiny deviations by construction — DESIGN.md §5).

The construction API is part of the pinned surface (DESIGN.md §11): the
golden trace must replay bit-exact through the declarative
`RuntimeConfig`/`from_config` front door, through an equivalent
fully-declarative policy-stack config, *and* through the deprecated
legacy kwarg constructor (which must warn).
"""
import json
import os

import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import (ETunerConfig, ETunerController, LazyTuneConfig,
                        SimFreezeConfig, etuner_stack_spec)
from repro.data import streams
from repro.models import build_model
from repro.runtime import RuntimeConfig, SlotConfig
from repro.runtime.continual import ContinualRuntime

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "golden_runtime.json")


def _model_bench():
    model = build_model(get_reduced("mobilenetv2"))
    bench = streams.nc_benchmark(num_classes=10, num_scenarios=3, batches=6,
                                 batch_size=8, seed=0)
    return model, bench


def _ctrl(model, method):
    ecfg = ETunerConfig(
        lazytune=method in ("lazy", "etuner"),
        simfreeze=method in ("freeze", "etuner"),
        detect_scenario_changes=False,
        lazytune_cfg=LazyTuneConfig(max_batches_needed=6),
        simfreeze_cfg=SimFreezeConfig(freeze_interval=6, min_history=2,
                                      cka_threshold=0.01))
    return ETunerController(model, ecfg)


def _config(**cfg_kw):
    hooks = cfg_kw.pop("hooks", ())
    return RuntimeConfig(slots={"default": SlotConfig(hooks=tuple(hooks))},
                         pretrain_epochs=1, seed=0, **cfg_kw)


def _run(method, hooks=(), legacy_kwargs=None, **cfg_kw):
    model, bench = _model_bench()
    ctrl = _ctrl(model, method)
    if legacy_kwargs is not None:
        rt = ContinualRuntime(model, bench, ctrl, pretrain_epochs=1,
                              seed=0, **legacy_kwargs)
    else:
        rt = ContinualRuntime.from_config(_config(hooks=hooks, **cfg_kw),
                                          model=model, benchmark=bench,
                                          controller=ctrl)
    return rt.run(inferences_total=16)


def _check(res, gold):
    assert res.rounds == gold["rounds"]
    assert res.recompiles == gold["recompiles"]
    np.testing.assert_allclose(res.avg_inference_acc,
                               gold["avg_inference_acc"], atol=1e-6)
    np.testing.assert_allclose(res.inference_accs, gold["inference_accs"],
                               atol=1e-6)
    np.testing.assert_allclose(res.val_curve, gold["val_curve"], atol=1e-5)
    np.testing.assert_allclose(res.total_time_s, gold["total_time_s"],
                               rtol=1e-5)
    np.testing.assert_allclose(res.total_energy_j, gold["total_energy_j"],
                               rtol=1e-5)
    np.testing.assert_allclose(res.compute_tflops, gold["compute_tflops"],
                               rtol=1e-5)
    assert set(res.breakdown) >= set(gold["breakdown"])
    for k, v in gold["breakdown"].items():
        np.testing.assert_allclose(res.breakdown[k], v, rtol=1e-5,
                                   atol=1e-9, err_msg=k)


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


def test_etuner_matches_pre_refactor_runtime(golden):
    """Full ETuner path: LazyTune + SimFreeze + CKA probe charges +
    replay sampling from the shared RNG stream."""
    _check(_run("etuner"), golden["etuner"])


def test_hooks_match_pre_refactor_runtime(golden):
    """SimSiam semi-supervised + fake-quant paths, now declarative
    per-slot HookSpecs, must reproduce the inlined originals exactly."""
    from repro.runtime import HookSpec

    _check(_run("immed", hooks=(HookSpec("fake-quant", {"bits": 8}),
                                HookSpec("simsiam", {"fraction": 0.5}))),
           golden["semi_quant"])


def test_preemptible_off_replays_golden(golden):
    """QoS off (`preemptible=False`, explicit) keeps the runtime on the
    synchronous round path: the golden trace replays bit-exact, so the
    QoS layer is provably inert unless opted into."""
    _check(_run("etuner", preemptible=False), golden["etuner"])


def test_legacy_kwarg_constructor_warns_and_replays_golden(golden):
    """Acceptance (ISSUE): the deprecated ~18-kwarg constructor still
    replays the `preemptible=False` golden run bit-exact — it delegates
    to the same RuntimeConfig resolution — while emitting a
    DeprecationWarning that steers callers to `from_config`."""
    with pytest.warns(DeprecationWarning, match="legacy kwarg"):
        res = _run("etuner", legacy_kwargs=dict(preemptible=False))
    _check(res, golden["etuner"])
    with pytest.warns(DeprecationWarning, match="legacy kwarg"):
        res = _run("immed", legacy_kwargs=dict(unlabeled_fraction=0.5,
                                               quant_bits=8))
    _check(res, golden["semi_quant"])


def test_declarative_policy_stack_replays_golden(golden):
    """Acceptance (ISSUE): an equivalent fully-declarative RuntimeConfig
    — ETuner expressed as a policy-stack spec, no controller object
    injected — replays the golden run bit-exact, and the built stack's
    stats() match the ETunerController composition's."""
    model, bench = _model_bench()
    cfg = RuntimeConfig(
        slots={"default": SlotConfig(policies=etuner_stack_spec(
            detect_scenario_changes=False,
            lazytune_params={"max_batches_needed": 6.0},
            simfreeze_params={"freeze_interval": 6, "min_history": 2,
                              "cka_threshold": 0.01}))},
        pretrain_epochs=1, seed=0, preemptible=False)
    rt = ContinualRuntime.from_config(cfg, model=model, benchmark=bench)
    res = rt.run(inferences_total=16)
    _check(res, golden["etuner"])
    # the generic PolicyStack and the ETunerController composition are
    # the same policy: identical stats after identical runs
    assert res.controller_stats == _run("etuner").controller_stats


# ---------------------------------------------------------------------------
# micro-batched serving equivalence


def _run_vit(window):
    model = build_model(get_reduced("deit-tiny"))
    bench = streams.nc_benchmark(num_classes=10, num_scenarios=3, batches=4,
                                 batch_size=8, seed=0)
    ctrl = ETunerController(model, ETunerConfig(
        lazytune=False, simfreeze=False, detect_scenario_changes=False))
    rt = ContinualRuntime.from_config(
        RuntimeConfig(slots={"default": SlotConfig()}, pretrain_epochs=1,
                      seed=0, inference_window=window, inference_batch=8),
        model=model, benchmark=bench, controller=ctrl)
    return rt.run(inferences_total=12)


def test_microbatched_serving_matches_per_request():
    per_request = _run_vit(0.0)
    coalesced = _run_vit(10.0)
    np.testing.assert_allclose(coalesced.inference_accs,
                               per_request.inference_accs, atol=1e-6)
    np.testing.assert_allclose(coalesced.avg_inference_acc,
                               per_request.avg_inference_acc, atol=1e-6)
    # cost accounting is independent of the serving path
    assert coalesced.rounds == per_request.rounds
    np.testing.assert_allclose(coalesced.total_energy_j,
                               per_request.total_energy_j, rtol=1e-6)
