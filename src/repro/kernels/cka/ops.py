"""Jitted wrapper for the CKA Gram-term kernel: centering, padding to tile
multiples, and the CKA ratio. The kernel runs compiled on TPU and in
Pallas interpret mode on CPU (`repro.kernels.resolve_interpret`); pass
`interpret=` to force either."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.cka.kernel import cka_terms_pallas


def _prepare(x: jax.Array, bn: int, bk: int) -> jax.Array:
    x = x.reshape(-1, x.shape[-1]) if x.ndim != 2 else x
    x = x.astype(jnp.float32)
    x = x - x.mean(axis=0, keepdims=True)
    n, d = x.shape
    pn = (-n) % bn
    pd = (-d) % bk
    if pn or pd:
        x = jnp.pad(x, ((0, pn), (0, pd)))  # zero rows/cols don't change Grams
    return x


@partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def cka_terms(x: jax.Array, y: jax.Array, bn: int = 128, bk: int = 512,
              interpret: Optional[bool] = None):
    """Returns (hsic, sqrt(kk), sqrt(ll)) matching core.cka conventions."""
    xp = _prepare(x, bn, bk)
    yp = _prepare(y, bn, bk)
    # pad feature dims to a common width (zero features are Gram-neutral)
    d = max(xp.shape[1], yp.shape[1])
    xp = jnp.pad(xp, ((0, 0), (0, d - xp.shape[1])))
    yp = jnp.pad(yp, ((0, 0), (0, d - yp.shape[1])))
    n = max(xp.shape[0], yp.shape[0])
    xp = jnp.pad(xp, ((0, n - xp.shape[0]), (0, 0)))
    yp = jnp.pad(yp, ((0, n - yp.shape[0]), (0, 0)))
    hsic, kk, ll = cka_terms_pallas(xp, yp, bn=bn, bk=bk,
                                    interpret=resolve_interpret(interpret))
    return hsic, jnp.sqrt(kk), jnp.sqrt(ll)


def cka(x: jax.Array, y: jax.Array, bn: int = 128, bk: int = 512,
        interpret: Optional[bool] = None) -> jax.Array:
    hsic, nx, ny = cka_terms(x, y, bn=bn, bk=bk, interpret=interpret)
    return hsic / jnp.maximum(nx * ny, 1e-12)
