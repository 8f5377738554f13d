"""Jitted wrapper for the WKV6 kernel. The kernel runs compiled on TPU and
in Pallas interpret mode on CPU (`repro.kernels.resolve_interpret`);
pass `interpret=` to force either."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.rwkv.kernel import wkv_pallas


@partial(jax.jit, static_argnames=("bt", "interpret"))
def wkv(r, k, v, logw, u, bt: int = 512, interpret: Optional[bool] = None):
    """Pads T to a block multiple; padded tokens have w=1 (logw=0), k=0 so
    the state and real outputs are untouched."""
    B, T, H, n = r.shape
    bt = min(bt, max(T, 1))
    pt = (-T) % bt
    if pt:
        pad = ((0, 0), (0, pt), (0, 0), (0, 0))
        r = jnp.pad(r, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        logw = jnp.pad(logw, pad)
    out = wkv_pallas(r, k, v, logw, u, bt=bt,
                     interpret=resolve_interpret(interpret))
    return out[:, :T]
