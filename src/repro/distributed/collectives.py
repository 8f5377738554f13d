"""Explicit collective paths used where GSPMD's implicit ones are not
enough:

- `sync_grads_shard_map`: data-parallel gradient sum via shard_map psum,
  with optional int8 error-feedback compression (all-gather the compressed
  payloads, decompress-and-sum locally — the standard compressed-allreduce
  construction) and freeze-aware *skipping*: frozen chunks are never
  communicated at all (ETuner's collective-term saving; DESIGN.md §2).
- `hierarchical_grad_sync`: reduce within pod first (fast ICI), then
  across pods (slow DCN) — composable axes for the multi-pod mesh.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.optim import compression


def sync_grads_shard_map(mesh: Mesh, grads, *, axis: str = "data",
                         compress: bool = False, residual=None,
                         freeze_mask=None):
    """grads: per-device local grads (replicated tree structure). Returns
    (synced grads averaged over `axis`, new residual).

    freeze_mask: optional 0/1 pytree; leaves with mask==0 are returned
    untouched (zeros) and produce NO collective traffic."""

    def select(tree, keep: bool):
        if freeze_mask is None:
            return tree if keep else None
        flat, treedef = jax.tree_util.tree_flatten(tree)
        mflat = jax.tree_util.tree_flatten(freeze_mask)[0]
        out = [l for l, m in zip(flat, mflat)
               if (bool(jnp.all(m == 0)) != keep)]
        return out

    n = mesh.shape[axis]

    if not compress:
        @partial(shard_map, mesh=mesh, in_specs=(P(),), out_specs=P(),
                 check_vma=False)
        def sync(g):
            return jax.tree.map(lambda x: jax.lax.psum(x, axis) / n, g)

        if freeze_mask is not None:
            flat, treedef = jax.tree_util.tree_flatten(grads)
            mflat = jax.tree_util.tree_flatten(freeze_mask)[0]
            active = [l for l, m in zip(flat, mflat) if not bool(jnp.all(m == 0))]
            synced = sync(tuple(active)) if active else ()
            it = iter(synced)
            out = [next(it) if not bool(jnp.all(m == 0)) else jnp.zeros_like(l)
                   for l, m in zip(flat, mflat)]
            return jax.tree_util.tree_unflatten(treedef, out), residual
        return sync(grads), residual

    # compressed path: quantize locally (+error feedback), all-gather the
    # int8 payloads over the axis, dequantize-and-mean locally.
    if residual is None:
        residual = compression.init_residual(grads)
    q_tree, s_tree, new_residual = compression.int8_compress_tree(grads, residual)

    @partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
             check_vma=False)
    def gather_sum(q, s):
        def leaf(qi, si):
            qs = jax.lax.all_gather(qi, axis)           # [n, ...] int8
            ss = jax.lax.all_gather(si, axis)           # [n]
            deq = qs.astype(jnp.float32) * ss.reshape((-1,) + (1,) * qi.ndim)
            return jnp.mean(deq, axis=0)

        return jax.tree.map(leaf, q, s)

    return gather_sum(q_tree, s_tree), new_residual


def hierarchical_grad_sync(mesh: Mesh, grads):
    """Reduce over 'data' (intra-pod ICI) then 'pod' (inter-pod DCN)."""
    axes = [a for a in ("data", "pod") if a in mesh.axis_names]

    @partial(shard_map, mesh=mesh, in_specs=(P(),), out_specs=P(),
             check_vma=False)
    def sync(g):
        out = g
        for a in axes:
            out = jax.tree.map(lambda x, a=a: jax.lax.psum(x, a), out)
        denom = 1
        for a in axes:
            denom *= mesh.shape[a]
        return jax.tree.map(lambda x: x / denom, out)

    return sync(grads)
