"""The comparison that decides `correct`.

What the timed path produced, as the harness recorded it from one kept
window session (`harness.SessionLog`), is held against the
configuration's plain reference (`configs/<config>.py`) replayed outside
the timed path. The reference starts from the benchmark's own weights and
follows the session's own decisions (the freeze plan and the labelled
batches of every train-step call, in order), so it needs no weights the
program made:

- fine-tune layer (the train-step scan, under each freeze plan the
  session used): the pretraining call's losses and leaf norms, the first
  loss of every call, the leaf norms of the session's whole change;
- serving layer (the vmapped predict): the logits of every request of
  the session, each against the reference's params after the call whose
  params answered it;
- SimFreeze's CKA probe (the Pallas kernel): the kernel's terms on a
  sample of its calls, drawn from the seed, against the plain terms of
  the operands the session gave it (read back from the program: the
  kernel's own input, not its answer); and, recorded only, the CKA values
  of the first freezing pass from the reference's own features.

The reference computes in the precision the configuration states:
float32 storage, and matmuls and convolutions at the configuration's
`matmul_precision` ("default": one bfloat16 pass with float32
accumulation on the TPU's MXU, full float32 on a CPU); the kernel's terms
in float64 (the kernel states float32 at "highest"). The control
(`replay(..., control=True)`) computes in the precision below:
bfloat16 for the model, "high" for the kernel's terms. The numbers
compared, and their limits, are the configuration's `limits`; every
number of `NUMBERS` is read and printed.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

# every gap the check reads and prints; the configuration's `limits` name
# the ones compared
NUMBERS = ("first_loss_gap", "median_grad_gap", "worst_update_gap",
           "call_loss_gap", "session_update_gap", "serve_gap",
           "serve_acc_gap", "cka_kernel_gap")
# a leaf whose first reference gradient is below this share of the median
# leaf's moves under Adam by round-off alone: left out of the leaf gaps
NULL_GRAD_SHARE = 1e-3


@dataclass
class Observed:
    """What one side (the program, or a stand-in for it) produced."""
    losses: np.ndarray                  # pretraining steps, in order
    call_losses: np.ndarray             # first step of every train call
    m_norms: np.ndarray                 # per leaf, after the pretraining call
    dp_norms: np.ndarray                # per leaf, after the pretraining call
    final_dp_norms: np.ndarray          # per leaf, after the session
    logits: Dict[int, np.ndarray] = field(default_factory=dict)  # request -> logits
    cka: Optional[np.ndarray] = None    # per unit, first freezing pass
    kernel: Optional[np.ndarray] = None  # [sampled call, (hsic, nx, ny)]


def observed(log) -> Observed:
    """What the program produced in one kept session (`harness.SessionLog`)."""
    m_norms, dp_norms = log.first_norms
    first = log.calls[0]
    return Observed(
        losses=np.asarray(first.loss)[:len(first.batches)],
        call_losses=np.array([float(np.asarray(c.loss)[0]) for c in log.calls]),
        m_norms=np.asarray(m_norms), dp_norms=np.asarray(dp_norms),
        final_dp_norms=np.asarray(log.final_norms),
        logits={i: np.asarray(lg) for i, lg in enumerate(log.logits)},
        cka=np.asarray(log.cka[0][2]) if log.cka else None,
        kernel=np.array([[float(v) for v in out] for _, _, out in log.kernel])
        if log.kernel else None)


def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def _masks(params, flags):
    """Per-leaf gradient multipliers of a freeze plan: 0 on the leaves of
    a frozen unit (the head is the last flag), 1 elsewhere."""
    n = len(params["units"])
    if flags is None:
        flags = (False,) * (n + 1)
    return {"units": [jax.tree.map(lambda _: 0.0 if f else 1.0, u)
                      for u, f in zip(params["units"], flags[:n])],
            "head": jax.tree.map(lambda _: 0.0 if flags[n] else 1.0,
                                 params["head"])}


def make_step(ref, doc, dtype):
    """One AdamW step of the reference, as the configuration states it
    (global-norm clipping, bias correction, decoupled weight decay on
    every leaf), with a frozen unit's gradient held at zero. Computed in
    `dtype` throughout, at the configuration's matmul precision."""
    opt = doc["optimizer"]
    precision = doc["matmul_precision"]

    def loss_fn(params, images, labels):
        logits, _ = ref.forward(doc, params, images.astype(dtype))
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)
        return -jnp.mean(picked)

    def step(params, m, v, count, images, labels, masks):
        with jax.default_matmul_precision(precision):
            loss, grads = jax.value_and_grad(loss_fn)(params, images, labels)
        grads = jax.tree.map(lambda g, k: g * jnp.asarray(k, g.dtype), grads,
                             masks)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)) + 1e-30)
        scale = jnp.minimum(1.0, opt["clip_norm"] / gnorm).astype(dtype)
        grads = jax.tree.map(lambda g: g * scale, grads)
        count = count + 1
        bc1 = (1.0 - opt["b1"] ** count.astype(jnp.float32)).astype(dtype)
        bc2 = (1.0 - opt["b2"] ** count.astype(jnp.float32)).astype(dtype)
        m = jax.tree.map(lambda a, g: opt["b1"] * a + (1 - opt["b1"]) * g, m, grads)
        v = jax.tree.map(lambda a, g: opt["b2"] * a + (1 - opt["b2"]) * g * g,
                         v, grads)
        params = jax.tree.map(
            lambda p, a, b: p - opt["lr"] * ((a / bc1) / (jnp.sqrt(b / bc2)
                                                         + opt["eps"])
                                            + opt["weight_decay"] * p),
            params, m, v)
        return params, m, v, count, loss.astype(jnp.float32), leaf_norms(grads)

    return jax.jit(step)


def make_forward(ref, doc, dtype, collect=False):
    precision = doc["matmul_precision"]

    def fwd(params, images):
        with jax.default_matmul_precision(precision):
            logits, feats = ref.forward(doc, params, images.astype(dtype),
                                        collect=collect)
        return (logits.astype(jnp.float32),
                [f.astype(jnp.float32) for f in feats]) if collect \
            else logits.astype(jnp.float32)

    return jax.jit(fwd)


def cka(x, y):
    """Linear CKA of two [n, ...] activations, example form, float32 at
    the highest precision (Kornblith et al. 2019)."""
    x = x.reshape(x.shape[0], -1).astype(jnp.float32)
    y = y.reshape(y.shape[0], -1).astype(jnp.float32)
    x = x - x.mean(axis=0)
    y = y - y.mean(axis=0)
    with jax.default_matmul_precision("highest"):
        k, l = x @ x.T, y @ y.T
    return jnp.sum(k * l) / jnp.sqrt(jnp.sum(k * k) * jnp.sum(l * l))


def kernel_terms(x, y, precision: Optional[str] = None) -> np.ndarray:
    """The CKA kernel's terms of two centred [n, d] operands: (<XX^T,
    YY^T>, |XX^T|, |YY^T|), Frobenius. With no `precision`, in float64 on
    the host (exact to the kernel's float32); else float32 on the device
    at that matmul precision (the control: "high", three bfloat16 passes,
    the step below the kernel's stated float32 at "highest")."""
    if precision is None:
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        k, l = x @ x.T, y @ y.T
        return np.array([np.sum(k * l), np.sqrt(np.sum(k * k)),
                         np.sqrt(np.sum(l * l))])
    x, y = jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32)
    k = jnp.dot(x, x.T, precision=precision)
    l = jnp.dot(y, y.T, precision=precision)
    return np.array([float(jnp.sum(k * l)), float(jnp.sqrt(jnp.sum(k * k))),
                     float(jnp.sqrt(jnp.sum(l * l)))])


@dataclass
class Replay:
    """The reference's own trajectory through the session's decisions."""
    observed: Observed                  # the replay's own readings
    first_grad_norms: np.ndarray        # per leaf, first step
    labels: Dict[int, np.ndarray] = field(default_factory=dict)


def replay(ref, doc, init_params, log, *, control: bool = False,
           batch_rows: Optional[int] = None, masks_off: bool = False,
           publish_lag: int = 0) -> Replay:
    """Replay every train-step call of a kept session from `init_params`,
    each under the freeze plan the session used, and read what the
    program's side reports: the pretraining call's losses and leaf norms,
    every call's first loss, the leaf norms of the session's change, the
    logits of every request served (by the params after the call that
    answered it), the first freezing pass's CKA values, and the CKA
    kernel's terms from the operands the session gave it.

    `control` computes in the precision below the configuration's:
    bfloat16 for the model, "high" for the kernel. The faults, each the
    reference put in the program's place: `batch_rows` keeps only the
    first rows of every batch (half a batch), `masks_off` trains frozen
    units, `publish_lag` serves with the params of that many calls
    earlier."""
    dtype = jnp.bfloat16 if control else jnp.float32
    step = make_step(ref, doc, dtype)
    fwd = make_forward(ref, doc, dtype)
    params = jax.tree.map(lambda p: p.astype(dtype), init_params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.int32)
    served_by: Dict[int, List[int]] = {}
    labels = {}
    for ri, req in enumerate(log.requests):
        if req.images is not None:
            served_by.setdefault(max(req.params_index - publish_lag, -1),
                                 []).append(ri)
            labels[ri] = np.asarray(req.labels)
    logits = {}

    def serve(index, p):
        for ri in served_by.get(index, ()):
            logits[ri] = np.asarray(fwd(p, jnp.asarray(log.requests[ri].images)))

    first = log.first_cka()
    kept = {}                           # call index -> params, for the CKA pass
    if first is not None:
        kept = dict.fromkeys((first[0] - 1, first[1] - 1))
    serve(-1, params)
    kept[-1] = params
    losses, call_losses, first_grads = [], [], None
    for ci, call in enumerate(log.calls):
        masks = _masks(params, None if masks_off else call.flags)
        for si, b in enumerate(call.batches):
            images, labs = b["images"], b["labels"]
            if batch_rows is not None:
                images, labs = images[:batch_rows], labs[:batch_rows]
            params, m, v, count, loss, gnorms = step(
                params, m, v, count, jnp.asarray(images), jnp.asarray(labs),
                masks)
            if si == 0:
                call_losses.append(loss)
            if ci == 0:
                losses.append(loss)
                if first_grads is None:
                    first_grads = gnorms
        if ci == 0:
            dp = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b,
                              params, init_params)
            m_norms, dp_norms = leaf_norms(m), leaf_norms(dp)
        if ci in kept:
            kept[ci] = params
        serve(ci, params)
    final = leaf_norms(jax.tree.map(lambda a, b: a.astype(jnp.float32) - b,
                                    params, init_params))
    cka_vals = None
    if first is not None:
        at, done, probe = first
        feats = make_forward(ref, doc, dtype, collect=True)
        _, ref_feats = feats(kept[at - 1], jnp.asarray(probe))
        _, cur_feats = feats(kept[done - 1], jnp.asarray(probe))
        cka_vals = np.array([float(cka(a, b)) for a, b in
                             zip(cur_feats, ref_feats)])
    kernel = None
    if log.kernel:
        kernel = np.stack([kernel_terms(x, y, "high" if control else None)
                           for x, y, _ in log.kernel])
    obs = Observed(losses=np.asarray(jnp.stack(losses)),
                   call_losses=np.asarray(jnp.stack(call_losses)),
                   m_norms=np.asarray(m_norms), dp_norms=np.asarray(dp_norms),
                   final_dp_norms=np.asarray(final), logits=logits,
                   cka=cka_vals, kernel=kernel)
    return Replay(obs, np.asarray(first_grads), labels)


def _leaf_gaps(got, want, keep):
    got, want = got[keep], want[keep]
    floor = np.maximum(want, np.median(want))
    return np.abs(got - want) / floor


def _accuracy(logits, labels, keys):
    hits = [np.argmax(logits[i], -1) == labels[i] for i in keys]
    return float(np.mean(np.concatenate(hits))) if hits else None


def compare(got: Observed, want: Replay) -> Dict[str, float]:
    """The numbers (`NUMBERS`, each a gap, 0 for identical), then readings
    recorded beside them.

    Training, through every train-step call of the session: the first
    pretraining step's loss (both sides hold the same weights there); the
    median leaf's gap of Adam's first moment and the worst leaf's gap of
    the parameters' change after the pretraining call; the median over
    the calls of the first step's loss gap; the median leaf's gap of the
    parameters' change over the whole session. Serving, every request of
    the session: the worst request's relative logit gap, and the gap of
    the accuracy served. The CKA kernel: the worst relative gap of its
    terms over the sampled calls.

    Recorded beside them: the loss gap of every pretraining step, the
    worst call's loss gap, the worst leaf's moment gap, the median leaf's
    change gap after the pretraining call, the worst leaf's over the
    session, the median request's logit gap, the served answers' gap
    below the reference's best, the accuracy served, and the CKA values
    of the first freezing pass, which the reference computes from its
    own features."""
    ref = want.observed
    keep = want.first_grad_norms >= NULL_GRAD_SHARE * np.median(
        want.first_grad_norms)
    n = min(len(got.losses), len(ref.losses))
    losses = np.abs(got.losses[:n] - ref.losses[:n]) / np.abs(ref.losses[:n])
    n = min(len(got.call_losses), len(ref.call_losses))
    calls = np.abs(got.call_losses[:n] - ref.call_losses[:n]) / \
        np.abs(ref.call_losses[:n])
    grads = _leaf_gaps(got.m_norms, ref.m_norms, keep)
    updates = _leaf_gaps(got.dp_norms, ref.dp_norms, keep)
    session = _leaf_gaps(got.final_dp_norms, ref.final_dp_norms, keep)
    out = {"first_loss_gap": float(losses[0]),
           "median_grad_gap": float(np.median(grads)),
           "worst_update_gap": float(np.max(updates)),
           "call_loss_gap": float(np.median(calls)),
           "session_update_gap": float(np.median(session))}
    common = sorted(set(got.logits) & set(ref.logits))
    serve = [float(np.linalg.norm(got.logits[i] - ref.logits[i])
                   / np.linalg.norm(ref.logits[i])) for i in common]
    if serve:
        out["serve_gap"] = max(serve)
        out["serve_acc_gap"] = abs(_accuracy(got.logits, want.labels, common)
                                   - _accuracy(ref.logits, want.labels, common))
    if got.kernel is not None and ref.kernel is not None \
            and got.kernel.shape == ref.kernel.shape:
        out["cka_kernel_gap"] = float(np.max(
            np.abs(got.kernel - ref.kernel) / np.abs(ref.kernel)))
    top = []
    for i in common:
        r, g = ref.logits[i], got.logits[i]
        best = r.max(-1)
        served = np.take_along_axis(r, g.argmax(-1)[:, None], -1)[:, 0]
        top.append(float(np.max((best - served) / (best - r.min(-1)))))
    out.update(loss_gaps=losses.tolist(), worst_call_loss_gap=float(
                   np.max(calls)), worst_grad_gap=float(np.max(grads)),
               median_update_gap=float(np.median(updates)),
               worst_session_update_gap=float(np.max(session)),
               median_serve_gap=float(np.median(serve)) if serve else None,
               serve_top_gap=max(top, default=None),
               acc=_accuracy(got.logits, want.labels, common),
               calls=int(n), requests=len(common),
               kernel_calls=0 if got.kernel is None else len(got.kernel),
               leaves_kept=int(keep.sum()), leaves=int(keep.size))
    if got.cka is not None and ref.cka is not None:
        k = min(len(got.cka), len(ref.cka))
        out["cka_gap"] = float(np.max(np.abs(got.cka[:k] - ref.cka[:k])))
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, lines): every number of `limits` present and within its
    limit, and one `name=value limit=...` line per number."""
    lines, ok = [], True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok &= bool(good)
        lines.append(f"{name}={value!r} limit={limit!r}"
                     + ("" if good else "  FAIL"))
    return ok, lines
