"""Attention functionals: flash / scaled-dot-product / sparse-block.

Parity: python/paddle/nn/functional/flash_attention.py:125 (reference dynloads
libflashattn, phi/kernels/gpu/flash_attn_kernel.cu:213). On TPU the fast path
is a Pallas splash/flash kernel (paddle_tpu.ops.pallas); this module routes to
it on TPU backends and falls back to the XLA softmax(QK^T)V composition —
which XLA already fuses well — on CPU.

Layout note: paddle's flash_attention takes [batch, seqlen, nheads, head_dim].
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ...core.autograd import apply_op
from ...core.random import default_generator
from ...core.tensor import Tensor
from ...ops._helpers import unwrap

__all__ = [
    "flash_attention", "flash_attn_unpadded", "scaled_dot_product_attention",
    "sdp_kernel", "sparse_attention",
]


def _sdpa_ref(q, k, v, mask=None, causal=False, dropout_p=0.0, scale=None,
              dropout_key=None):
    """[B, S, H, D] reference composition; f32 softmax accumulation.
    GQA allowed: K/V with fewer heads are repeated up to Q's head count."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # [B, H, S, D]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * s
    scores = scores.astype(jnp.float32)
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        cmask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(cmask, scores, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, -jnp.inf)
        else:
            scores = scores + mask.astype(scores.dtype)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)  # back to [B, S, H, D]


def flash_attention(query, key, value, dropout: float = 0.0, causal: bool = False,
                    return_softmax: bool = False, fixed_seed_offset=None,
                    rng_name: str = "", training: bool = True, name=None):
    """paddle.nn.functional.flash_attention parity. Returns (out, softmax).

    GQA allowed (key/value with fewer heads). Routing:
    - TPU, block-divisible seq lens → own Pallas flash kernel
      (ops/flash_attention_kernel.py), dropout applied IN-KERNEL via a
      counter-based RNG (no second attention pass, no S² buffer);
    - CPU with dropout → the same kernel in interpret mode (so tests
      exercise the real dropout code path);
    - otherwise → XLA reference composition.
    """
    p = dropout if training else 0.0
    from ...ops.flash_attention_kernel import supports
    from ...ops.pallas import _kernel_routable
    from ...ops.pallas import flash_attention as pallas_flash

    sq, sk = query.shape[1], key.shape[1]
    use_kernel = supports(sq, sk) and (_kernel_routable(unwrap(query))
                                       or p > 0.0)
    if use_kernel:
        if p > 0.0:
            seed = jax.random.randint(default_generator.next_key(), (1,),
                                      0, 2**31 - 1, dtype=jnp.int32)
        else:
            seed = None
        out = apply_op(
            lambda q, k, v: pallas_flash(q, k, v, causal=causal,
                                         dropout_p=p, seed=seed),
            query, key, value, op_name="flash_attention")
    else:
        dk = default_generator.next_key() if p > 0.0 else None
        out = apply_op(
            lambda q, k, v: _sdpa_ref(q, k, v, causal=causal, dropout_p=p,
                                      dropout_key=dk),
            query, key, value, op_name="flash_attention")
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale: float,
                        dropout: float = 0.0, causal: bool = False,
                        return_softmax: bool = False, fixed_seed_offset=None,
                        rng_name: str = "", training: bool = True, name=None):
    """Varlen flash attention: [total_tokens, H, D] + cu_seqlens.

    TPU-native form: segment-masked dense attention (ragged batches become a
    segment-id mask — dynamic shapes are hostile to XLA, masks are free).
    """
    cq = unwrap(cu_seqlens_q)
    ck = unwrap(cu_seqlens_k)

    def f(q, k, v):
        tq = q.shape[0]
        tk = k.shape[0]
        seg_q = jnp.cumsum(
            jnp.zeros(tq, jnp.int32).at[cq[1:-1]].add(1)) if cq.shape[0] > 2 else jnp.zeros(tq, jnp.int32)
        seg_k = jnp.cumsum(
            jnp.zeros(tk, jnp.int32).at[ck[1:-1]].add(1)) if ck.shape[0] > 2 else jnp.zeros(tk, jnp.int32)
        scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
        scores = scores.astype(jnp.float32)
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            pos_q = jnp.arange(tq) - jnp.take(cq, seg_q)
            pos_k = jnp.arange(tk) - jnp.take(ck, seg_k)
            mask = mask & (pos_q[:, None] >= pos_k[None, :])
        scores = jnp.where(mask[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        probs = jnp.where(mask[None], probs, 0.0)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = apply_op(f, query, key, value, op_name="flash_attn_unpadded")
    return out, None


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p: float = 0.0, is_causal: bool = False,
                                 training: bool = True, name=None):
    """paddle layout [B, S, H, D]; mask broadcastable to [B, H, Sq, Sk]."""
    dk = default_generator.next_key() if (dropout_p > 0.0 and training) else None
    m = unwrap(attn_mask) if attn_mask is not None else None

    def f(q, k, v):
        return _sdpa_ref(q, k, v, mask=m, causal=is_causal,
                         dropout_p=dropout_p if training else 0.0, dropout_key=dk)

    return apply_op(f, query, key, value, op_name="scaled_dot_product_attention")


class sdp_kernel:
    """Context manager selecting attention backends (API parity; routing is
    automatic on TPU)."""

    def __init__(self, enable_flash=True, enable_math=True, enable_mem_efficient=True):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def sparse_attention(query, key, value, sparse_csr_offset, sparse_csr_columns,
                     key_padding_mask=None, attn_mask=None, name=None):
    """CSR-pattern attention (reference nn/functional/sparse_attention.py,
    phi/kernels/sparse/gpu/sparse_attention — computes ONLY the stored
    (q, k) pairs).

    Gather path (default): per-row key/value gathers at static capacity
    R = max row nnz (rounded to the 8-sublane tile), scores [bh, s, R] —
    memory O(s·R·d), never the dense [s, s] score matrix, matching the
    reference kernel's point. Falls back to the dense masked form when
    the pattern is near-dense (R > s/2 — the gather would cost more than
    it saves) or when the CSR arrays are tracers (row capacity must be
    static)."""
    offs = unwrap(sparse_csr_offset)
    cols = unwrap(sparse_csr_columns)

    def dense_f(q, k, v):
        b, h, s, d = q.shape
        # CSR pattern → boolean mask by scattering (vectorized over batch*head)
        bh = b * h
        offs2 = offs.reshape(bh, s + 1)
        cols2 = cols.reshape(bh, -1)
        nnz = cols2.shape[-1]
        pos = jnp.arange(nnz)
        row_of = jax.vmap(
            lambda o: jnp.searchsorted(o, pos, side="right") - 1
        )(offs2)  # [bh, nnz]
        valid = pos[None, :] < offs2[:, -1:]
        bidx = jnp.repeat(jnp.arange(bh)[:, None], nnz, 1)
        mask2 = jnp.zeros((bh, s, s), bool)
        mask2 = mask2.at[bidx, jnp.clip(row_of, 0, s - 1), cols2].max(valid)
        mask = mask2.reshape(b, h, s, s)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (d ** 0.5)
        scores = jnp.where(mask, scores.astype(jnp.float32), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        probs = jnp.where(mask, probs, 0.0).astype(q.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v)

    def gather_f(q, k, v, R):
        b, h, s, d = q.shape
        bh = b * h
        offs2 = offs.reshape(bh, s + 1)
        cols2 = cols.reshape(bh, -1)
        lens = offs2[:, 1:] - offs2[:, :-1]                 # [bh, s]
        r = jnp.arange(R)
        base = offs2[:, :-1, None] + r[None, None, :]       # [bh, s, R]
        nnz = cols2.shape[-1]
        idx = jnp.take_along_axis(
            cols2[:, None, :], jnp.clip(base, 0, max(nnz - 1, 0)),
            axis=2)                                          # [bh, s, R]
        valid = r[None, None, :] < lens[:, :, None]
        q2 = q.reshape(bh, s, d)
        k2 = k.reshape(bh, s, d)
        v2 = v.reshape(bh, s, d)
        kg = jax.vmap(lambda kk, ii: kk[ii])(k2, idx)        # [bh, s, R, d]
        vg = jax.vmap(lambda vv, ii: vv[ii])(v2, idx)
        scores = jnp.einsum("bqd,bqrd->bqr", q2.astype(jnp.float32),
                            kg.astype(jnp.float32)) / (d ** 0.5)
        scores = jnp.where(valid, scores, -jnp.inf)
        m = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores - jnp.where(jnp.isfinite(m), m, 0.0))
        p = jnp.where(valid, p, 0.0)
        denom = jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
        out = jnp.einsum("bqr,bqrd->bqd", (p / denom).astype(v.dtype), vg)
        return out.reshape(b, h, s, d)

    # static row capacity decides the path; tracers can't give one
    R = None
    try:
        import numpy as _np

        o = _np.asarray(offs)
        R = int((o.reshape(-1, o.shape[-1])[:, 1:]
                 - o.reshape(-1, o.shape[-1])[:, :-1]).max())
    except (TypeError, jax.errors.TracerArrayConversionError,
            jax.errors.ConcretizationTypeError):
        pass

    def f(q, k, v):
        s = q.shape[2]
        if R is not None and 0 < R <= s // 2:
            # round capacity to the sublane tile so the gather lanes align
            return gather_f(q, k, v, min(s, -(-R // 8) * 8))
        return dense_f(q, k, v)

    return apply_op(f, query, key, value, op_name="sparse_attention")
