"""Jitted wrapper for the flash-attention kernel with shape padding. The
kernel runs compiled on TPU and in Pallas interpret mode on CPU
(`repro.kernels.resolve_interpret`); pass `interpret=` to force either."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.attention.kernel import flash_attention_pallas


@partial(jax.jit, static_argnames=("causal", "window", "softcap", "bq", "bk",
                                   "interpret"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, bq: int = 512, bk: int = 512,
                    interpret: Optional[bool] = None):
    """Padding-safe wrapper: pads Sq/Sk up to block multiples (padded kv
    positions are masked out by the causal test since they sit beyond the
    real sequence)."""
    B, Sq, Hq, hd = q.shape
    Sk = k.shape[1]
    bq = min(bq, max(Sq, 1))
    bk = min(bk, max(Sk, 1))
    pq = (-Sq) % bq
    pk = (-Sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 softcap=softcap, bq=bq, bk=bk,
                                 interpret=resolve_interpret(interpret))
    return out[:, :Sq]
