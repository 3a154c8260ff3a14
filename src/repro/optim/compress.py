"""Gradient compression for cross-pod reduction: int8 quantization with
error feedback (1-bit-Adam-style noise shaping, at 8 bits).

The cross-pod data-parallel all-reduce is the longest-haul collective in a
multi-pod job (DCN or optical links, far slower than intra-pod ICI).
`compressed_psum` runs it at int8 instead of bf16/f32 — 2-4x fewer bytes on
the slowest link — and the residual quantization error is carried into the
next step (error feedback keeps the *accumulated* update unbiased; plain
quantized SGD provably stalls without it).

Under jit on a multi-pod mesh the all-gather below lowers to an int8
collective on the 'pod' axis — visible (and counted) in the dry-run HLO.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

F32 = jnp.float32


def quantize_int8(x):
    """Per-tensor symmetric int8 quantization. Returns (q, scale)."""
    xf = x.astype(F32)
    amax = jnp.max(jnp.abs(xf))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.rint(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(F32) * scale


def ef_compress(g, residual):
    """Error-feedback compression of one tensor: returns (q, scale, new_res)."""
    corrected = g.astype(F32) + residual
    q, scale = quantize_int8(corrected)
    new_res = corrected - dequantize_int8(q, scale)
    return q, scale, new_res


def ef_init(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)


def compressed_psum(tree, axis_name):
    """shard_map-compatible mean-all-reduce at int8 precision.

    Each participant quantizes its local contribution, the int8 payloads are
    all-gathered over `axis_name`, dequantized and averaged locally.
    """
    n = jax.lax.axis_size(axis_name)

    def one(x):
        q, scale = quantize_int8(x)
        qs = jax.lax.all_gather(q, axis_name)              # int8 on the wire
        ss = jax.lax.all_gather(scale, axis_name)
        return jnp.sum(qs.astype(F32) * ss.reshape((n,) + (1,) * x.ndim),
                       axis=0) / n

    return jax.tree.map(one, tree)


def cross_pod_grad_sync(grads, residuals, mesh, enabled=True):
    """Error-feedback int8 mean-reduction of grads across the 'pod' axis.

    grads must be pod-local (i.e. produced under shard_map over 'pod' or with
    batch-per-pod loss).  Returns (synced_grads, new_residuals).
    """
    if not enabled or "pod" not in mesh.axis_names:
        return grads, residuals

    def inner(g_tree, r_tree):
        qs = jax.tree.map(ef_compress, g_tree, r_tree)
        q = jax.tree.map(lambda t: t[0], qs, is_leaf=lambda x: isinstance(x, tuple))
        s = jax.tree.map(lambda t: t[1], qs, is_leaf=lambda x: isinstance(x, tuple))
        new_r = jax.tree.map(lambda t: t[2], qs, is_leaf=lambda x: isinstance(x, tuple))
        n = jax.lax.axis_size("pod")

        def reduce_one(qi, si):
            qg = jax.lax.all_gather(qi, "pod")
            sg = jax.lax.all_gather(si, "pod")
            return jnp.sum(qg.astype(F32)
                           * sg.reshape((n,) + (1,) * qi.ndim), axis=0) / n

        synced = jax.tree.map(reduce_one, q, s)
        return synced, new_r

    spec = jax.tree.map(lambda _: P(), grads)
    # no replication check: the int8 payload differs per participant
    fn = jax.shard_map(inner, mesh=mesh, in_specs=(spec, spec),
                       out_specs=(spec, spec), check_vma=False)
    return fn(grads, residuals)
