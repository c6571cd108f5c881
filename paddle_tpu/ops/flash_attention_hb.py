"""Head-batched flash attention: native ``[B, S, H, D]`` layout.

The BHSD kernel (flash_attention_kernel.py) forces BSHD->BHSD transposes
around every attention call — ~11ms/step of pure HBM relayout at the
350M bench shapes (PERF.md). A per-head BSHD block (1, bq, 1, D) is
illegal on TPU (the H dim breaks the (8,128) tiling), but a HEAD-BATCHED
block (1, bq, H, D) is legal: the last two dims are (H, D) = (8, 128).
This kernel processes ALL heads per grid step:

- scores are a statically-unrolled Python loop of per-head 2D dots over
  ``[:, i, :]`` slices of the native block, stacked to (H, bq, bk) in
  VMEM (the original H-batched 3D ``dot_general`` was Mosaic-rejected
  on-chip 2026-07-31 — "Bad lhs type"; see ``_per_head``),
- online-softmax stats are (H, bq, 1),
- the grid drops the head dimension: (B, nq, nk) — H x fewer grid steps.

VMEM bounds the block size: scores+probs at fp32 are 2·H·bq·bk·4 bytes
(8MB at H=8, bq=bk=512), so default blocks are 512 here vs 1024 for the
per-head kernel. Whether the transpose savings beat the smaller blocks is
an EMPIRICAL question — `experiments/exp_flash_hb.py` measures it; the
router (ops/pallas.py) keeps this path opt-in via FLAGS_flash_head_batched
until the TPU numbers say otherwise.

Scope: Hq == Hkv (the bench config), dropout-free. GQA/dropout route to
the per-head kernel.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention_kernel import (_NEG_INF, _VMEM, _apply_causal_mask,
                                     _interpret, _pick_block)

__all__ = ["flash_attention_bshd_hb", "supports_hb"]

# scores+probs live in VMEM at fp32: 2 * H * bq * bk * 4 bytes must fit
# alongside q/k/v blocks and the fp32 accumulators (~16MB VMEM/core)
_VMEM_SCORE_BUDGET = 16 << 20


def supports_hb(q_shape, k_shape, dropout_p: float,
                interpret: Optional[bool] = None,
                block: int = 512) -> bool:
    b, sq, h, d = q_shape
    hkv, sk = k_shape[2], k_shape[1]
    it = _interpret() if interpret is None else interpret
    # 2026-07-31 on-chip finding: Mosaic on the v5e toolchain rejected
    # the H-batched 3D tpu.matmul the original kernel was built around
    # ("Bad lhs type") at every block size tried.  The kernel has since
    # been restructured to statically-unrolled per-head 2D dots (whose
    # slice/store forms are themselves unverified on hardware — see
    # _per_head), so device routing stays off until
    # PADDLE_TPU_HB_ON_DEVICE=1 (note exp_flash_hb calls the kernel
    # DIRECTLY and never consults this gate) verifies it; flip the
    # default only after a measured win. Per-head remains the device
    # path (ROADMAP D4).
    if not it and os.environ.get("PADDLE_TPU_HB_ON_DEVICE", "") != "1":
        return False
    # this kernel does bf16 D-contracting dots WITHOUT the _sublane_plan
    # padding the per-head kernels apply — at D % 128 != 0 Mosaic would
    # reject them ("Bad lhs type"), so refuse device routing there (the
    # per-head path handles those shapes natively via its pad plan)
    if not it and d % 128 != 0:
        return False
    return (h == hkv and dropout_p == 0.0
            and 2 * h * block * block * 4 <= _VMEM_SCORE_BUDGET
            and _pick_block(sq, block, it) is not None
            and _pick_block(sk, block, it) is not None)


def _dot2d(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _per_head(fn, h):
    """Static Python loop over heads, stacked to (H, ...): Mosaic on the
    v5e toolchain rejects H-batched 3D tpu.matmul ("Bad lhs type",
    2026-07-31 on-chip).  The replacement 2D dot forms match the per-head
    kernel's on-chip-proven dots; the per-head STATIC slices of the
    native (bq, H, D) block ([:, i, :] — no transposes, no materialized
    head-leading copies) are themselves unverified on hardware until the
    session script's on-chip test step runs.  H is a trace-time constant,
    so this unrolls — kernel code size grows H×, MXU work is
    identical."""
    return jnp.stack([fn(i) for i in range(h)], 0)


def _scores_hb(q, k, sm_scale, causal, iq, ik, bq, bk, offset):
    """(H, bq, bk) fp32 scores; masking shared with the per-head kernel
    (_apply_causal_mask) so the alignment convention cannot diverge.
    ``q``/``k`` arrive in the NATIVE block layout (bq|bk, H, D); heads
    are sliced statically, one 2D NT dot each."""
    h = q.shape[1]
    s = _per_head(
        lambda i: _dot2d(q[:, i, :], k[:, i, :], ((1,), (1,))), h) \
        * sm_scale
    return _apply_causal_mask(s, causal, iq, ik, bq, bk, offset,
                              lead_batch=True)


def _fwd_kernel_hb(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                   l_ref, *, sm_scale, causal, offset, bq, bk):
    b, iq, ik = (pl.program_id(i) for i in range(3))
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        q = q_ref[0]                          # (bq, H, D) native layout
        k = k_ref[0]                          # (bk, H, D)
        v = v_ref[0]
        h = q.shape[1]
        s, valid = _scores_hb(q, k, sm_scale, causal, iq, ik, bq, bk,
                              offset)         # (H, bq, bk)
        m_prev = m_ref[:, :, 0:1]             # (H, bq, 1)
        l_prev = l_ref[:, :, 0:1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :, 0:1] = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
        # per-head P_h @ V_h: (bq, bk) x (bk, D) -> stacked (H, bq, D)
        pv = _per_head(
            lambda i: _dot2d(p[i].astype(v.dtype), v[:, i, :],
                             ((1,), (0,))), h)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[:, :, 0:1] = m_new

    if causal:
        needed = ik * bk <= iq * bq + bq - 1 + offset
        pl.when(needed)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, :, 0:1]
        l_safe = jnp.maximum(l, 1e-30)
        for i in range(acc_ref.shape[0]):     # per-head static stores —
            o_ref[0, :, i, :] = (acc_ref[i] / l_safe[i]).astype(
                o_ref.dtype)                  # no (H,bq,D) transpose
        lse_ref[0] = (m_ref[:, :, 0:1] + jnp.log(l_safe))[:, :, 0]


def _fwd_impl_hb(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    bsz, sq, h, d = q.shape
    sk = k.shape[1]
    bq = _pick_block(sq, block_q, interpret)
    bk = _pick_block(sk, block_k, interpret)
    nq, nk = sq // bq, sk // bk
    offset = sk - sq
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_hb, sm_scale=sm_scale, causal=causal,
                          offset=offset, bq=bq, bk=bk),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((bsz, h, sq), jnp.float32)],
        grid=(bsz, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, h, d), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, bk, h, d), lambda b, i, j: (b, j, 0, 0)),
            pl.BlockSpec((1, bk, h, d), lambda b, i, j: (b, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, h, d), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, h, bq), lambda b, i, j: (b, 0, i)),
        ],
        scratch_shapes=[
            _VMEM((h, bq, d), jnp.float32),
            _VMEM((h, bq, 128), jnp.float32),
            _VMEM((h, bq, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


def _bwd_dq_kernel_hb(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, acc_ref, *, sm_scale, causal, offset, bq, bk):
    b, iq, ik = (pl.program_id(i) for i in range(3))
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0]                                  # (bq, H, D) native
        k = k_ref[0]                                  # (bk, H, D)
        v = v_ref[0]
        do = do_ref[0]                                # (bq, H, D)
        h = q.shape[1]
        lse = lse_ref[0][:, :, None]                  # (H, bq, 1)
        delta = delta_ref[0][:, :, None]
        s, valid = _scores_hb(q, k, sm_scale, causal, iq, ik, bq, bk,
                              offset)
        p = jnp.exp(s - lse)
        if causal and offset < 0:
            p = jnp.where(valid, p, 0.0)
        # per-head dP_h = dO_h @ V_h^T: (bq, D) x (bk, D) -> (H, bq, bk)
        dpd = _per_head(
            lambda i: _dot2d(do[:, i, :], v[:, i, :], ((1,), (1,))), h)
        ds = p * (dpd - delta)
        # per-head dQ_h += dS_h @ K_h: (bq, bk) x (bk, D) -> (H, bq, D)
        acc_ref[...] += _per_head(
            lambda i: _dot2d(ds[i].astype(k.dtype), k[:, i, :],
                             ((1,), (0,))), h) * sm_scale

    if causal:
        needed = ik * bk <= iq * bq + bq - 1 + offset
        pl.when(needed)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finalize():
        for i in range(acc_ref.shape[0]):     # per-head static stores
            dq_ref[0, :, i, :] = acc_ref[i].astype(dq_ref.dtype)


def _bwd_dkv_kernel_hb(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, causal,
                       offset, bq, bk):
    b, ik, iq = (pl.program_id(i) for i in range(3))
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute():
        q = q_ref[0]                                  # (bq, H, D) native
        k = k_ref[0]                                  # (bk, H, D)
        v = v_ref[0]
        do = do_ref[0]                                # (bq, H, D)
        h = q.shape[1]
        lse = lse_ref[0][:, :, None]
        delta = delta_ref[0][:, :, None]
        s, valid = _scores_hb(q, k, sm_scale, causal, iq, ik, bq, bk,
                              offset)
        p = jnp.exp(s - lse)
        if causal and offset < 0:
            p = jnp.where(valid, p, 0.0)
        dpd = _per_head(
            lambda i: _dot2d(do[:, i, :], v[:, i, :], ((1,), (1,))), h)
        ds = p * (dpd - delta)
        # per-head dV_h += P_h^T @ dO_h: (bq, bk) x (bq, D) -> (H, bk, D)
        dv_acc[...] += _per_head(
            lambda i: _dot2d(p[i].astype(do.dtype), do[:, i, :],
                             ((0,), (0,))), h)
        # per-head dK_h += dS_h^T @ Q_h: (bq, bk) x (bq, D) -> (H, bk, D)
        dk_acc[...] += _per_head(
            lambda i: _dot2d(ds[i].astype(q.dtype), q[:, i, :],
                             ((0,), (0,))), h) * sm_scale

    if causal:
        needed = ik * bk <= iq * bq + bq - 1 + offset
        pl.when(needed)(_compute)
    else:
        _compute()

    @pl.when(iq == nq - 1)
    def _finalize():
        for i in range(dk_acc.shape[0]):      # per-head static stores
            dk_ref[0, :, i, :] = dk_acc[i].astype(dk_ref.dtype)
            dv_ref[0, :, i, :] = dv_acc[i].astype(dv_ref.dtype)


def _bwd_impl_hb(q, k, v, out, lse, do, causal, sm_scale, block_q, block_k,
                 interpret):
    bsz, sq, h, d = q.shape
    sk = k.shape[1]
    bq = _pick_block(sq, block_q, interpret)
    bk = _pick_block(sk, block_k, interpret)
    nq, nk = sq // bq, sk // bk
    offset = sk - sq
    # delta = rowsum(dO * O): [B, H, S]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                            # [B, S, H]
    delta = jnp.transpose(delta, (0, 2, 1))             # [B, H, S] (small)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_hb, sm_scale=sm_scale,
                          causal=causal, offset=offset, bq=bq, bk=bk),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(bsz, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, h, d), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, bk, h, d), lambda b, i, j: (b, j, 0, 0)),
            pl.BlockSpec((1, bk, h, d), lambda b, i, j: (b, j, 0, 0)),
            pl.BlockSpec((1, bq, h, d), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, h, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, h, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, h, d), lambda b, i, j: (b, i, 0, 0)),
        scratch_shapes=[_VMEM((h, bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_hb, sm_scale=sm_scale,
                          causal=causal, offset=offset, bq=bq, bk=bk),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        grid=(bsz, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, h, d), lambda b, j, i: (b, i, 0, 0)),
            pl.BlockSpec((1, bk, h, d), lambda b, j, i: (b, j, 0, 0)),
            pl.BlockSpec((1, bk, h, d), lambda b, j, i: (b, j, 0, 0)),
            pl.BlockSpec((1, bq, h, d), lambda b, j, i: (b, i, 0, 0)),
            pl.BlockSpec((1, h, bq), lambda b, j, i: (b, 0, i)),
            pl.BlockSpec((1, h, bq), lambda b, j, i: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, h, d), lambda b, j, i: (b, j, 0, 0)),
            pl.BlockSpec((1, bk, h, d), lambda b, j, i: (b, j, 0, 0)),
        ],
        scratch_shapes=[_VMEM((h, bk, d), jnp.float32),
                        _VMEM((h, bk, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_hb(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, _ = _fwd_impl_hb(q, k, v, causal, sm_scale, block_q, block_k,
                          interpret)
    return out


def _flash_hb_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse = _fwd_impl_hb(q, k, v, causal, sm_scale, block_q, block_k,
                            interpret)
    return out, (q, k, v, out, lse)


def _flash_hb_bwd(causal, sm_scale, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    return _bwd_impl_hb(q, k, v, out, lse, do, causal, sm_scale,
                        block_q, block_k, interpret)


_flash_hb.defvjp(_flash_hb_fwd, _flash_hb_bwd)


def flash_attention_bshd_hb(q, k, v, *, causal: bool = False,
                            sm_scale: Optional[float] = None,
                            block_q: int = 512, block_k: int = 512,
                            interpret: Optional[bool] = None):
    """Head-batched flash attention over native ``[B, S, H, D]`` tensors
    (no layout transposes). Requires Hq == Hkv and no dropout — the router
    falls back to :func:`flash_attention_bhsd` otherwise."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    it = _interpret() if interpret is None else interpret
    # re-validate VMEM score budget against the ACTUAL blocks this call
    # will run (supports_hb only checks its default block=512; a direct
    # call with larger blocks must not silently exceed the budget)
    h = q.shape[2]
    bq = _pick_block(q.shape[1], block_q, it)
    bk = _pick_block(k.shape[1], block_k, it)
    if bq is None or bk is None:
        raise ValueError(
            f"flash_attention_bshd_hb: seq lens {q.shape[1]}/{k.shape[1]} "
            f"not tileable by block_q={block_q}/block_k={block_k}")
    if 2 * h * bq * bk * 4 > _VMEM_SCORE_BUDGET:
        raise ValueError(
            f"flash_attention_bshd_hb: scores+probs VMEM "
            f"2*{h}*{bq}*{bk}*4 = {2 * h * bq * bk * 4} bytes exceeds the "
            f"{_VMEM_SCORE_BUDGET} budget; use smaller block_q/block_k or "
            "the per-head kernel (flash_attention_bhsd)")
    return _flash_hb(q, k, v, causal, float(sm_scale), block_q, block_k, it)
