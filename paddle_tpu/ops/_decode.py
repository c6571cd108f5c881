"""Decode-step attention dispatch (single query token over a KV cache).

Reference analog: masked_multihead_attention_kernel
(fused_multi_transformer_op.cu.h:745). MHA routes to the tiled Pallas
decode kernel on TPU; GQA uses a grouped einsum composition — the decode
step is HBM-bandwidth-bound (the whole cache streams once either way), so
XLA's fused gather+softmax is within noise of a hand kernel for grouped
heads while keeping the KV cache un-repeated.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

__all__ = ["gqa_decode_attention"]


def gqa_decode_attention(q, k_cache, v_cache, seq_lens, tp=None):
    """q: [B, Hq, D]; k/v_cache: [B, S, Hkv, D]; seq_lens: [B] valid rows
    (the current token's K/V already written at seq_lens-1).
    Returns [B, Hq, D] in q's dtype.

    ``tp=(mesh, axis)`` wraps the step in ``shard_map`` over the head
    axis (q on Hq, caches on Hkv, lens replicated): attention is
    head-parallel, so each mesh shard runs this exact function on its
    local slice with zero communication — the tensor-parallel serving
    engines' dense-cache decode path (see ``inference/tp.py``)."""
    if tp is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh, ax = tp
        head, kv = P(None, ax, None), P(None, None, ax, None)
        return shard_map(
            lambda q_, k_, v_, l_: gqa_decode_attention(q_, k_, v_, l_),
            mesh=mesh, in_specs=(head, kv, kv, P()), out_specs=head,
            check_vma=False)(q, k_cache, v_cache, seq_lens)
    b, hq, d = q.shape
    s_max, hkv = k_cache.shape[1], k_cache.shape[2]
    from .pallas import _kernel_routable

    if hq == hkv and _kernel_routable(q):
        from .pallas_kernels import decode_mha

        return decode_mha(q, k_cache, v_cache, seq_lens)
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    q4 = q.reshape(b, hkv, g, d).astype(jnp.float32)
    kc = k_cache.astype(jnp.float32)
    vc = v_cache.astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", q4, kc) * scale     # [B, Hkv, G, S]
    mask = jnp.arange(s_max)[None, None, None, :] < seq_lens[:, None, None,
                                                             None]
    s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
    p = jnp.where(mask, p, 0.0)
    p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    o = jnp.einsum("bkgs,bskd->bkgd", p, vc)
    return o.reshape(b, hq, d).astype(q.dtype)
