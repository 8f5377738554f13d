"""WKV6 Pallas TPU kernel: exact recurrence with the [n, n] state resident
in VMEM.

The CUDA wkv6 kernel keeps the per-head state in registers/shared memory
and streams tokens; the TPU adaptation keeps S in VMEM scratch and streams
the sequence through in (1, bt, n) blocks: grid (B*H, nT) with the time
axis sequential, so S persists across time-blocks without ever touching
HBM — only r/k/v/w blocks stream in and o blocks stream out. Inside a
block a fori_loop applies the exact per-token update (no decay-product
approximation — this kernel is the *exact* path; the XLA chunked closed
form in models/rwkv6.py clamps log-decay products, see its docstring).

VMEM per step: 4 x (bt x n) inputs + (bt x n) output + (n x n) state ≈
5*512*64*4 + 64*64*4 ≈ 0.7 MB at bt=512, n=64."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_scr, *,
                bt: int):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)    # [bt, n]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)    # [1, n] -> broadcast

    def step(t, carry):
        S, o_acc = carry                 # S: [n, n]; o_acc: [bt, n]
        rt = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)     # [1, n]
        kt = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)
        vt = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)
        wt = jnp.exp(jax.lax.dynamic_slice_in_dim(lw, t, 1, 0))  # [1, n]
        kv = kt.T @ vt                                    # [n, n]
        o_t = rt @ (S + u.reshape(1, -1).T * kv)          # [1, n]
        S = wt.T * S + kv
        o_acc = jax.lax.dynamic_update_slice_in_dim(o_acc, o_t, t, 0)
        return S, o_acc

    S, o = jax.lax.fori_loop(0, bt, step,
                             (s_scr[...], jnp.zeros((bt, r.shape[1]),
                                                    jnp.float32)))
    s_scr[...] = S
    o_ref[0] = o.astype(o_ref.dtype)


def wkv_pallas(r, k, v, logw, u, *, bt: int = 512, interpret: bool):
    """r/k/v/logw: [B, T, H, n]; u: [H, n]. Returns o [B, T, H, n] fp32."""
    B, T, H, n = r.shape
    bt = min(bt, T)
    assert T % bt == 0, (T, bt)
    nt = T // bt

    def flat(a):
        return jnp.moveaxis(a, 2, 1).reshape(B * H, T, n)

    rf, kf, vf, lwf = map(flat, (r, k, v, logw))

    def seq_map(bh, it):
        return (bh, it, 0)

    def u_map(bh, it):
        return (bh % H, 0)

    out = pl.pallas_call(
        functools.partial(_wkv_kernel, bt=bt),
        grid=(B * H, nt),
        in_specs=[
            pl.BlockSpec((1, bt, n), seq_map),
            pl.BlockSpec((1, bt, n), seq_map),
            pl.BlockSpec((1, bt, n), seq_map),
            pl.BlockSpec((1, bt, n), seq_map),
            pl.BlockSpec((1, n), u_map),
        ],
        out_specs=pl.BlockSpec((1, bt, n), seq_map),
        out_shape=jax.ShapeDtypeStruct((B * H, T, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, lwf, u)
    return jnp.moveaxis(out.reshape(B, H, T, n), 1, 2)
