"""Pallas TPU kernel for the CKA Gram terms.

Computes (hsic, kk, ll) for row-centered X, Y [n, d] without ever
materializing the n x n Gram matrices in HBM: the grid tiles the Gram into
(bn x bn) blocks; each block is accumulated over the feature dim in
bk-chunks inside VMEM scratch (MXU-aligned tiles), then squared /
cross-multiplied and reduced into three (1,1) outputs that every grid step
revisits. All three grid axes are "arbitrary" (sequential): the (1,1)
output blocks stay resident in VMEM across the whole grid and are written
back once, so no axis may be split across cores. Each reduced scalar is
stored as a (1,1) vector — Mosaic cannot store a scalar into VMEM. The
Gram matmuls ask for fp32 contract precision, so the compiled kernel is
held to the fp32 tolerance of ref.py on every backend.

VMEM budget per step: 4 x (bn x bk) input tiles + 2 x (bn x bn) f32
accumulators ≈ 1.2 MB at the default bn=128, bk=512 — well inside the
~16 MB/core VMEM envelope, with the contraction dim >= 128 for the MXU."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cka_kernel(xi_ref, xj_ref, yi_ref, yj_ref, hsic_ref, kk_ref, ll_ref,
                k_acc, l_acc, *, nk: int):
    i, j, kstep = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        k_acc[...] = jnp.zeros_like(k_acc)
        l_acc[...] = jnp.zeros_like(l_acc)

    @pl.when((i == 0) & (j == 0) & (kstep == 0))
    def _zero_outputs():
        hsic_ref[...] = jnp.zeros_like(hsic_ref)
        kk_ref[...] = jnp.zeros_like(kk_ref)
        ll_ref[...] = jnp.zeros_like(ll_ref)

    xi = xi_ref[...].astype(jnp.float32)
    xj = xj_ref[...].astype(jnp.float32)
    yi = yi_ref[...].astype(jnp.float32)
    yj = yj_ref[...].astype(jnp.float32)
    gram = functools.partial(jax.lax.dot_general,
                             dimension_numbers=(((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    k_acc[...] += gram(xi, xj)
    l_acc[...] += gram(yi, yj)

    @pl.when(kstep == nk - 1)
    def _reduce():
        kt = k_acc[...]
        lt = l_acc[...]
        hsic_ref[...] += jnp.sum(kt * lt).reshape(1, 1)
        kk_ref[...] += jnp.sum(kt * kt).reshape(1, 1)
        ll_ref[...] += jnp.sum(lt * lt).reshape(1, 1)


def cka_terms_pallas(x: jax.Array, y: jax.Array, *, bn: int = 128,
                     bk: int = 512, interpret: bool):
    """x, y: [n, d] row-centered (ops.py pads/centers). -> (hsic, kk, ll)."""
    n, d = x.shape
    assert y.shape == (n, d), (x.shape, y.shape)
    assert n % bn == 0 and d % bk == 0, (n, d, bn, bk)
    ni, nk = n // bn, d // bk
    grid = (ni, ni, nk)

    def row_block(i, j, k):
        return (i, k)

    def col_block(i, j, k):
        return (j, k)

    scalar_spec = pl.BlockSpec((1, 1), lambda i, j, k: (0, 0))

    hsic, kk, ll = pl.pallas_call(
        functools.partial(_cka_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bk), row_block),
            pl.BlockSpec((bn, bk), col_block),
            pl.BlockSpec((bn, bk), row_block),
            pl.BlockSpec((bn, bk), col_block),
        ],
        out_specs=[scalar_spec, scalar_spec, scalar_spec],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32)] * 3,
        scratch_shapes=[pltpu.VMEM((bn, bn), jnp.float32),
                        pltpu.VMEM((bn, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(x, x, y, y)
    return hsic[0, 0], kk[0, 0], ll[0, 0]
