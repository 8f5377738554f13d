"""Flash-attention forward Pallas TPU kernel (causal, sliding-window,
logit-softcap, GQA).

Grid: (B * Hq, nQ, nK) with the kv axis innermost ("arbitrary"/sequential
on TPU) so the online-softmax running state (acc, m, l) lives in VMEM
scratch across kv steps. Blocks:
  q:   (1, bq, hd)  indexed (b*Hq + h, iq)      from [B*Hq, Sq, hd]
  k/v: (1, bk, hd)  indexed (b*Hkv + h//g, ik)  from [B*Hkv, Sk, hd]
  o:   (1, bq, hd)  written at ik == nK-1
VMEM per step ≈ bq*hd + 2*bk*hd + bq*hd(acc) + 2*bq  floats — with
bq=bk=512, hd=128 that's ~0.9 MB, MXU-aligned (hd multiple of 128).
Fully-masked kv blocks (beyond the causal diagonal / outside the sliding
window) are skipped with pl.when — same static-band saving the XLA
blockwise path exploits (models/attention.py)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s, *,
                  bq: int, bk: int, nk: int, causal: bool, window: int,
                  softcap: float, scale: float):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q_lo = iq * bq
    k_lo = ik * bk
    # live unless entirely above the diagonal or below the window band
    live = jnp.bool_(True)
    if causal:
        live &= k_lo <= q_lo + bq - 1
    if window:
        live &= (k_lo + bk - 1) >= (q_lo - window + 1)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)          # [bq, hd]
        k = k_ref[0].astype(jnp.float32)          # [bk, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.bool_(True)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1)
        acc[...] = acc[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(ik == nk - 1)
    def _emit():
        denom = jnp.maximum(l_s[...], 1e-30)[:, None]
        o_ref[0] = (acc[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           softcap: float = 0.0, bq: int = 512, bk: int = 512,
                           interpret: bool):
    """q: [B, Sq, Hq, hd]; k/v: [B, Sk, Hkv, hd] -> [B, Sq, Hq, hd]."""
    B, Sq, Hq, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / (hd ** 0.5)

    qf = jnp.moveaxis(q, 2, 1).reshape(B * Hq, Sq, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * Hkv, Sk, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * Hkv, Sk, hd)

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik):
        b = bh // Hq
        h = bh % Hq
        return (b * Hkv + h // g, ik, 0)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
                          window=window, softcap=softcap, scale=scale),
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), q_map),
            pl.BlockSpec((1, bk, hd), kv_map),
            pl.BlockSpec((1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf)
    return jnp.moveaxis(out.reshape(B, Hq, Sq, hd), 1, 2)
