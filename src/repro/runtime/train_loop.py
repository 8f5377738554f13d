"""Train-step factory: jitted, freeze-plan-aware, with a compiled-variant
cache (the "system initialization" LazyTune amortizes) and XLA-measured
FLOPs per plan for the cost model.

Compiled hot path (DESIGN.md §12): steps donate their `(params,
opt_state)` buffers, the compile ledger is keyed by *(plan, batch
shape)* so alternating streams/slots can't thrash it, and
`fused_call` runs a whole run of same-shape batches as one
`lax.scan` dispatch. Every compiled-mode update — even a single batch —
goes through the same scan body: a scan's while-loop HLO is
trip-count-independent, so k fused micro-steps are bit-identical to k
single-step calls of the same program, which is what makes segment
batching a pure dispatch optimization. Scan lengths are padded up to
power-of-two buckets with a per-step validity mask (`jnp.where(valid,
new, old)` keeps the carry — including the Adam step count — bitwise
unchanged on padding steps), bounding compiles to log2(max round length)
per (plan, shape).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.obs.host import count, span
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         sgdm_init, sgdm_update)

# CPU has no buffer-donation support: jit warns once per donated program
# and silently keeps the copy. The donation is still correct (and load-
# bearing on GPU/TPU), so the warning is noise here.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


# Process-global program registries. jit caches live on the jit-wrapped
# callable, so a fresh `jax.jit` per session would re-pay every XLA
# compile; keying the wrapped callables by (loss-fn identity, opt config,
# plan, ...) instead lets every session over the same (memoized) model
# share programs. Keys hold the loss function itself (not id()) so a
# live registry entry can never collide with a recycled id.
_STEPS: Dict[Tuple, Callable] = {}
_MULTI: Dict[Tuple, Callable] = {}
_MULTI_BUCKETS: Dict[Tuple, set] = {}
_FLOPS: Dict[Tuple, float] = {}


def batch_signature(batch: dict) -> Tuple:
    """Hashable (shape, dtype) signature of a host/device batch dict —
    the retrace key of every compiled step."""
    return tuple(sorted(
        (k, tuple(getattr(v, "shape", ())), str(getattr(v, "dtype", "")))
        for k, v in batch.items()))


def _bucket(n: int) -> int:
    """Next power of two >= n (scan-length / group-size padding bucket)."""
    return 1 << max(n - 1, 0).bit_length()


@dataclass
class TrainStepCache:
    """Per-freeze-plan compiled train steps + their HLO FLOPs.

    `recompiles` counts distinct (plan, batch-shape) programs: one per
    new plan, plus one per *additional* batch shape a plan is asked to
    handle (the first shape rides on the plan's own compile). `donate`
    marks params/opt_state as donated in every jitted step (a no-op on
    CPU, halves peak optimizer-state memory on accelerators).
    """
    model: Any
    opt_cfg: Any
    donate: bool = True
    _jits: Dict[Any, Callable] = field(default_factory=dict)
    _shapes: Dict[Any, set] = field(default_factory=dict)
    _flops: Dict[Any, float] = field(default_factory=dict)
    recompiles: int = 0

    def _raw_step(self, plan):
        opt_cfg = self.opt_cfg
        loss_fn = self.model.loss

        def step(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch, plan), has_aux=True)(params)
            if isinstance(opt_cfg, AdamWConfig):
                params, opt_state = adamw_update(grads, opt_state, params, opt_cfg)
            else:
                params, opt_state = sgdm_update(grads, opt_state, params, opt_cfg)
            return params, opt_state, metrics

        return step

    def _make_step(self, plan):
        key = ("step", self.model.loss, self.opt_cfg, plan, self.donate)
        fn = _STEPS.get(key)
        if fn is None:
            fn = _STEPS[key] = jax.jit(
                self._raw_step(plan),
                donate_argnums=(0, 1) if self.donate else ())
        return fn

    def get(self, plan, example_batch: dict = None) -> Callable:
        """The jitted single step for `plan`. Passing the batch about to
        be trained keeps the recompile ledger shape-accurate (jax retraces
        per shape under the hood; we only *count* here)."""
        if plan not in self._jits:
            self._jits[plan] = self._make_step(plan)
            self._shapes[plan] = set()
            self.recompiles += 1
        if example_batch is not None:
            sig = batch_signature(example_batch)
            shapes = self._shapes[plan]
            if sig not in shapes:
                if shapes:  # first shape rides on the plan's compile
                    self.recompiles += 1
                shapes.add(sig)
        return self._jits[plan]

    # ---- fused multi-batch step (compiled hot path) ----------------------
    def multi_step(self, plan, example_batch: dict,
                   length: int) -> Tuple[Callable, int]:
        """Jitted masked scan over a stacked run of `length` same-shape
        batches; returns (fn, bucket) where fn(params, opt_state,
        stacked, valid) expects `bucket` stacked batches and a [bucket]
        bool mask. Padding steps leave the carry bitwise untouched —
        which also lets a short run ride an already-compiled *larger*
        bucket instead of compiling its own rung. Reuse is capped at 2x
        the run's natural bucket so padding never more than doubles the
        scan's device work (a singleton round must not ride an 8-step
        program just because pretraining compiled one)."""
        base = (self.model.loss, self.opt_cfg, plan, self.donate,
                batch_signature(example_batch))
        need = _bucket(length)
        compiled = _MULTI_BUCKETS.setdefault(base, set())
        fits = [b for b in compiled if need <= b <= 2 * need]
        bucket = min(fits) if fits else need
        compiled.add(bucket)
        key = base + (bucket,)
        fn = _MULTI.get(key)
        if fn is None:
            raw = self._raw_step(plan)

            def body(carry, xs):
                params, opt_state = carry
                batch, valid = xs
                p2, o2, metrics = raw(params, opt_state, batch)
                keep = lambda new, old: jnp.where(valid, new, old)
                return (jax.tree.map(keep, p2, params),
                        jax.tree.map(keep, o2, opt_state)), metrics

            def multi(params, opt_state, stacked, valid):
                (params, opt_state), metrics = jax.lax.scan(
                    body, (params, opt_state), (stacked, valid))
                return params, opt_state, metrics

            fn = _MULTI[key] = jax.jit(
                multi, donate_argnums=(0, 1) if self.donate else ())
        return fn, bucket

    def fused_call(self, plan, params, opt_state, batches: Sequence[dict]):
        """Run a same-shape run of batches as ONE device dispatch. The
        single-batch case is the same scan program at trip count 1, so
        per-event and segment-batched execution agree bitwise."""
        self.get(plan, batches[0])  # recompile-ledger bookkeeping
        fn, bucket = self.multi_step(plan, batches[0], len(batches))
        pad = bucket - len(batches)
        with span("train/stage"):
            stacked = {k: jnp.stack([jnp.asarray(b[k]) for b in batches]
                                    + [jnp.asarray(batches[0][k])] * pad)
                       for k in batches[0]}
            valid = jnp.arange(bucket) < len(batches)
        with span("train/dispatch"):
            return fn(params, opt_state, stacked, valid)

    def flops(self, plan, example_batch) -> float:
        """XLA-measured FLOPs of one train step under `plan` (compiled once,
        cached). Used by EdgeCostModel so SimFreeze savings are *measured*,
        not assumed."""
        if plan not in self._flops:
            key = (self.model.loss, self.model.init, self.opt_cfg, plan,
                   batch_signature(example_batch))
            val = _FLOPS.get(key)
            if val is None:
                step = self.get(plan)
                # avals are enough to lower: skip materializing real params
                params = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
                opt_state = jax.eval_shape(
                    lambda p: make_optimizer_state(
                        self.model, self.opt_cfg, p),
                    params)
                lowered = step.lower(params, opt_state, example_batch)
                cost = lowered.compile().cost_analysis()
                val = _FLOPS[key] = float(cost.get("flops", 0.0))
            self._flops[plan] = val
        return self._flops[plan]


def same_shape_runs(batches: Sequence[dict]):
    """Yield the maximal runs of consecutive same-signature batches — the
    units segment batching fuses into single scan dispatches."""
    i, n = 0, len(batches)
    while i < n:
        j = i + 1
        sig = batch_signature(batches[i])
        while j < n and batch_signature(batches[j]) == sig:
            j += 1
        yield batches[i:j]
        i = j


@jax.jit
def _copy_leaves(tree):
    # `jnp.copy` binds `copy_p`, so no output is forwarded from an input:
    # XLA writes every leaf to a fresh buffer, aliased inputs included.
    return jax.tree.map(jnp.copy, tree)


def copy_tree(tree, site: str):
    """Device copy of `tree` (what a donating step may consume) in one
    dispatch of a jitted copy program, one per tree structure and avals.
    Every output leaf is a fresh buffer, distinct from every input and
    from every other output even where input leaves alias one another;
    the values are bitwise the inputs'. Counted as `device_copies{site}`
    (leaves) and `copy_programs{site}` (dispatches)."""
    count("device_copies", len(jax.tree.leaves(tree)), site=site)
    count("copy_programs", site=site)
    return _copy_leaves(tree)


def as_jnp(batch: dict) -> dict:
    """Host batch dict -> device arrays (shared by training and serving)."""
    return {k: jnp.asarray(v) for k, v in batch.items()}


def make_optimizer_state(model, opt_cfg, params):
    if isinstance(opt_cfg, AdamWConfig):
        return adamw_init(params, opt_cfg)
    return sgdm_init(params, opt_cfg)


_COMPILED_MODELS: Dict[Any, Any] = {}


def compiled_model(model):
    """Model whose predict/features dispatch through jit (per-shape XLA
    cache) — the compiled hot path's serving/probe side. `loss` stays
    raw: it is only ever traced inside train steps. Memoized on the
    (features, predict) closures so repeat wraps of the same model share
    one jit cache process-wide."""
    import dataclasses

    key = (model.features, model.predict)
    wrapped = _COMPILED_MODELS.get(key)
    if wrapped is None:
        kw = {"features": jax.jit(model.features)}
        if model.predict is not None:
            kw["predict"] = jax.jit(model.predict)
        wrapped = _COMPILED_MODELS[key] = dataclasses.replace(model, **kw)
    return wrapped


#: device-to-host pulls of one `evaluate`: the accuracy and the logits
EVALUATE_PULLS = 2


def evaluate(model, params, batch) -> Tuple[float, Any]:
    """Returns (accuracy, logits) on a labeled batch: two device-to-host
    pulls (`EVALUATE_PULLS`), which each caller counts as its
    `host_syncs{site}`."""
    logits = model.predict(params, batch) if model.predict is not None else None
    if logits is None:
        raise ValueError("model has no predict()")
    import numpy as np

    acc = float(jnp.mean((jnp.argmax(logits, -1) ==
                          jnp.asarray(batch["labels"])).astype(jnp.float32)))
    return acc, np.asarray(logits)


def grad_accum_step(loss_fn, params, batches, plan=None):
    """Gradient accumulation over microbatches via scan (large global
    batches on small meshes)."""
    def micro(carry, batch):
        gsum, lsum = carry
        (l, _), g = jax.value_and_grad(lambda p: loss_fn(p, batch, plan),
                                       has_aux=True)(params)
        return (jax.tree.map(jnp.add, gsum, g), lsum + l), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (gsum, lsum), _ = jax.lax.scan(micro, (zeros, 0.0), batches)
    n = jax.tree.leaves(batches)[0].shape[0]
    return (jax.tree.map(lambda g: g / n, gsum), lsum / n)
