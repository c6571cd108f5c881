"""GQA-native flash attention, forward + backward, as Pallas TPU kernels.

This is the framework's own flash kernel (replacing the stock
``jax.experimental.pallas.ops.tpu.flash_attention`` routing of round 1).
Reference analog: ``phi/kernels/gpu/flash_attn_kernel.cu:213`` (fwd) and
``flash_attn_grad_kernel.cu`` (bwd) which dynload libflashattn; here the
same online-softmax tiling is expressed for the MXU/VMEM machine model.

Design points (and why they differ from the stock JAX kernel):

- **Compact residuals.** The only saved values are the output and a
  log-sum-exp per row stored as ``[B, H, S]`` fp32.  The stock kernel keeps
  separate ``m``/``l`` tensors padded to a 128-lane trailing dim —
  ``f32[B, H, S, 128]`` each — which is exactly the HLO-temp blow-up that
  OOMed round 1's benchmark.
- **GQA in the index maps.** Q may have ``Hq = G * Hkv`` heads; K/V blocks
  are selected with ``h // G`` so grouped heads share KV *without*
  materialising ``jnp.repeat``-ed keys (the reference handles GQA inside
  libflashattn the same way).
- **In-kernel dropout.** A counter-based hash RNG (murmur3 finalizer over
  ``(seed, batch, head, q, k)``) generates the keep-mask inside the kernel,
  identically in forward and both backward kernels, so dropout costs no
  extra memory and no second attention pass.  (``pltpu.prng_*`` is not used
  because it has no interpret-mode lowering — the hash runs everywhere.)
- **Bottom-right causal alignment**: query ``i`` attends keys
  ``<= i + (Sk - Sq)`` — the decode-with-KV-cache convention used across
  this repo (see ``ops/pallas.py::_chunked_attention``).  Fully-masked
  blocks are skipped via ``pl.when``.

Layout: ``[B, H, S, D]`` (callers transpose from paddle's ``[B, S, H, D]``).
fp32 accumulation throughout; bf16 in/out supported.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM
_SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)

__all__ = ["flash_attention_bhsd", "supports"]

_NEG_INF = -1e30  # large-negative mask value; avoids inf-inf NaNs


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def _pick_block(s: int, target: int, interpret: bool) -> Optional[int]:
    """Largest divisor of s that is <= target and (on real TPU) a multiple
    of 128 sublanes; None if no usable block exists."""
    if interpret:
        from .pallas_kernels import _row_block

        return _row_block(s, target)
    for b in (target, 1024, 512, 256, 128):
        if b <= target and s % b == 0:
            return b
    return None


def supports(sq: int, sk: int, interpret: Optional[bool] = None) -> bool:
    """Whether the Pallas kernel can handle these sequence lengths."""
    it = _interpret() if interpret is None else interpret
    return (_pick_block(sq, 1024, it) is not None
            and _pick_block(sk, 1024, it) is not None)


def _sublane_plan(d: int, dtype, interpret: bool):
    """Mosaic (v5e libtpu) rejects bf16 dots whose CONTRACTION dim is not
    a lane multiple ("Bad lhs type" on the D-contracting q·kᵀ / dO·vᵀ
    dots when D % 128 != 0, found on-chip 2026-07-31).  Returns
    ``(mode, dpad)``:

    - ``(None, d)``  — native path, nothing to do (D already a lane
      multiple, fp32 input, or interpret mode).
    - ``('pad', dp)``  — zero-pad D to ``dp`` OUTSIDE the kernel: the
      kernel then runs the exact D=128 bf16 shapes that were on-chip
      green from the start.  Full-rate bf16 MXU dots; costs ~2x q/k/v/o
      HBM bytes at D=64.  The default.
    - ``('kpad', dp)`` — zero-pad INSIDE the kernel (VMEM concat after
      load, slice before store): same full-rate dots with NO extra HBM
      traffic, but needs Mosaic's in-kernel concatenate lowering — run
      the staged on-chip parity check before trusting it on hardware.
    - ``('fp32', d)``  — the r4 guard: upcast everything to fp32
      (compiles everywhere, but fp32 dots run at a fraction of bf16
      MXU rate on the hottest kernel).  Escape hatch.

    Select via ``PADDLE_TPU_FLASH_SUBLANE`` (pad|kpad|fp32).  Padding
    with zeros is exact: zero lanes contribute 0 to every D-contraction,
    and the padded tail of each output is sliced off (fwd) or provably
    zero (grads).

    ``PADDLE_TPU_FLASH_SUBLANE_FORCE=1`` applies the plan in interpret
    mode too — that is how the CPU suite exercises the pad/kpad numerics
    the device path will run.

    PROCESS-LIFETIME BINDING: the env var is read at TRACE time and the
    chosen mode is frozen into the cached jit program for each
    (shape, dtype) signature. Changing ``PADDLE_TPU_FLASH_SUBLANE``
    after a shape has compiled silently has NO effect on that shape for
    the rest of the process, and two modes cannot coexist for the same
    shape — set the env var before the first flash call and leave it.
    When the monitor is enabled, every selection is recorded as
    ``paddle_tpu_flash_sublane_mode_total{mode=...}`` so a mid-process
    mismatch between the env var and the compiled programs is visible
    in the metrics instead of silent.
    """
    force = os.environ.get("PADDLE_TPU_FLASH_SUBLANE_FORCE") == "1"
    if ((interpret and not force) or d % 128 == 0
            or jnp.dtype(dtype) == jnp.float32):
        return None, d
    mode = os.environ.get("PADDLE_TPU_FLASH_SUBLANE", "pad")
    if mode not in ("pad", "kpad", "fp32"):
        raise ValueError(
            f"PADDLE_TPU_FLASH_SUBLANE={mode!r}: expected pad|kpad|fp32")
    _record_sublane_mode(mode)
    return mode, -(-d // 128) * 128


def _record_sublane_mode(mode: str) -> None:
    """Publish the sublane plan frozen into this trace (monitor label;
    runs at trace time only, never per step)."""
    try:
        from .. import monitor

        if monitor.enabled():
            monitor.counter(
                "paddle_tpu_flash_sublane_mode_total",
                "flash-attention sublane plans frozen into compiled "
                "programs, by mode (process-lifetime env binding)",
                ("mode",)).labels(mode=mode).inc()
    except Exception:  # metrics must never break a kernel trace
        pass


def _pad_d(x, dpad: int):
    """Zero-pad the trailing (head) dim to ``dpad`` lanes."""
    if x.shape[-1] == dpad:
        return x
    return jnp.concatenate(
        [x, jnp.zeros(x.shape[:-1] + (dpad - x.shape[-1],), x.dtype)],
        axis=-1)


# ---------------------------------------------------------------------------
# Counter-based RNG for dropout (murmur3 finalizer)
# ---------------------------------------------------------------------------


def _mix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _keep_mask(seed, b, h, q0, k0, bq, bk, dropout_p):
    """Boolean keep-mask for the (bq, bk) score block whose top-left element
    is global (q0, k0). Deterministic in (seed, b, h, global q, global k)."""
    s0 = _mix(seed.astype(jnp.uint32)
              ^ (b.astype(jnp.uint32) * jnp.uint32(0x9E3779B9))
              ^ (h.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)))
    qi = (q0.astype(jnp.uint32)
          + jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 0))
    ki = (k0.astype(jnp.uint32)
          + jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 1))
    bits = _mix(_mix(qi + s0) ^ ki)
    thresh = jnp.uint32(min(int(dropout_p * 4294967296.0), 4294967295))
    return bits >= thresh  # P(keep) = 1 - dropout_p


def _causal_valid(iq, ik, block_q, block_k, offset, window=None):
    """Bottom-right-aligned validity for the (iq, ik) score block: query i
    attends keys <= i + offset, and with a ``window`` only the last
    ``window`` of them (keys > i + offset - window). Shared by fwd and
    both bwd kernels so the alignment convention can never diverge
    between them."""
    qpos = (iq * block_q
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))
    kpos = (ik * block_k
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
    valid = kpos <= qpos + offset
    if window is not None:
        valid = valid & (kpos > qpos + offset - window)
    return valid


def _apply_causal_mask(s, causal, iq, ik, block_q, block_k, offset,
                       lead_batch: bool = False, window=None):
    """Causal masking for a score block (``s`` is (bq, bk), or (H, bq, bk)
    with ``lead_batch``), SPECIALIZED to diagonal blocks: blocks entirely
    below the causal boundary skip the iota/compare/select passes (at
    1024x1024 those are 3 extra VPU sweeps — most blocks of a long-
    sequence causal kernel are fully valid). Shared by the per-head AND
    head-batched kernels so the alignment convention cannot diverge.

    Returns (s, valid); valid is non-None only when offset < 0, the one
    case where rows can be globally all-masked and the caller must re-mask
    probabilities (there the mask is applied unconditionally — the valid
    matrix is needed anyway, so the cond would buy nothing)."""
    if not causal:
        return s, None

    def mask(x):
        v = _causal_valid(iq, ik, block_q, block_k, offset, window)
        return jnp.where(v[None] if lead_batch else v, x, _NEG_INF), v

    if offset < 0:
        s, v = mask(s)
        return s, (v[None] if lead_batch else v)
    # does this block contain ANY masked entry? (bottom-right alignment:
    # the block's last key position vs its first query's boundary)
    is_diag = (ik * block_k + block_k - 1) > (iq * block_q + offset)
    if window is not None:
        # ... or the window's lower edge: the block's first key is at or
        # under the lowest key its LAST query still sees
        is_diag = is_diag | (ik * block_k <= iq * block_q + block_q - 1
                             + offset - window)
    s = jax.lax.cond(is_diag, lambda x: mask(x)[0], lambda x: x, s)
    return s, None


def _block_scores(q, k, sm_scale, causal, iq, ik, block_q, block_k, offset,
                  window=None):
    """Masked fp32 score block for the per-head kernel."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    return _apply_causal_mask(s, causal, iq, ik, block_q, block_k, offset,
                              window=window)


def _dropped(p, seed, b, h, iq, ik, block_q, block_k, dropout_p):
    """p with the dropout keep-mask applied and 1/(1-p) upscaling — the
    SAME mask in fwd and both bwd kernels (hash of global coordinates)."""
    keep = _keep_mask(seed, b, h, iq * block_q, ik * block_k,
                      block_q, block_k, dropout_p)
    return jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_p))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, sm_scale, causal, dropout_p,
                offset, block_q, block_k, dpad, window=None):
    b, h, iq, ik = (pl.program_id(i) for i in range(4))
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        q = _pad_d(q_ref[0, 0], dpad)        # (bq, Dp)
        k = _pad_d(k_ref[0, 0], dpad)        # (bk, Dp)
        v = _pad_d(v_ref[0, 0], dpad)
        s, valid = _block_scores(q, k, sm_scale, causal, iq, ik,
                                 block_q, block_k, offset, window)
        # single-column running stats: alpha's exp runs on (bq, 1), not the
        # (bq, 128) replicated buffer — transcendentals are the VPU cost
        m_prev = m_ref[:, 0:1]               # (bq, 1)
        l_prev = l_ref[:, 0:1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)      # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        if valid is not None:
            # offset < 0 only: globally all-masked rows have m_new == -inf
            # and exp(0) == 1 garbage; offset >= 0 needs no re-mask — the
            # masked s give exp(-1e30 - finite) == 0 exactly
            p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                 # (bq, 1)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_p > 0.0:
            # l accumulates UNdropped p (softmax normalizer is exact); only
            # the value contraction sees the mask, pre-scaled by 1/(1-p)
            pv = _dropped(p, seed_ref[0], b, h, iq, ik, block_q, block_k,
                          dropout_p)
        else:
            pv = p
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(
                            pv.astype(v.dtype), v,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        needed = ik * block_k <= iq * block_q + block_q - 1 + offset
        if window is not None:
            # a block wholly under the window of its FIRST query (the
            # lowest any of its queries sees) is skipped, not masked
            needed = needed & (ik * block_k + block_k - 1
                               > iq * block_q + offset - window)
        pl.when(needed)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        l_safe = jnp.maximum(l, 1e-30)
        # [:, :D] is a no-op unless dpad padded the accumulator (kpad)
        o_ref[0, 0] = ((acc_ref[...] / l_safe)[:, :o_ref.shape[-1]]
                       .astype(o_ref.dtype))
        lse_ref[0, 0] = m_ref[:, 0:1] + jnp.log(l_safe)  # (bq, 1)


def _window_blocks(i, block_q, block_k, offset, window, nk):
    """First and last key block that query block ``i`` of a causal window
    needs (``_fwd_kernel``'s ``needed``, solved for ``ik``)."""
    lo = jnp.maximum((i * block_q + offset - window + 1) // block_k, 0)
    hi = jnp.minimum((i * block_q + block_q - 1 + offset) // block_k, nk - 1)
    return lo, hi


def _fwd_impl(q, k, v, seed, causal, sm_scale, dropout_p, block_q, block_k,
              interpret, window=None):
    in_dtype = q.dtype
    d_orig = q.shape[-1]
    mode, dp = _sublane_plan(d_orig, in_dtype, interpret)
    if mode == "fp32":
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    elif mode == "pad":
        q, k, v = (_pad_d(x, dp) for x in (q, k, v))
    bsz, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    bq = _pick_block(sq, block_q, interpret)
    bk = _pick_block(sk, block_k, interpret)
    nq, nk = sq // bq, sk // bk
    offset = sk - sq
    dpad = dp if mode == "kpad" else d
    def kv_map(b, h, i, j, g=group):
        if window is None:
            return (b, h // g, j, 0)
        # a skipped step aims at the nearest block the row needs: the
        # same block as its neighbour's, so no copy is issued for it
        lo, hi = _window_blocks(i, bq, bk, offset, window, nk)
        return (b, h // g, jnp.clip(j, lo, hi), 0)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          dropout_p=dropout_p, offset=offset,
                          block_q=bq, block_k=bk, dpad=dpad,
                          window=window),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((bsz, hq, sq, 1), jnp.float32)],
        grid=(bsz, hq, nq, nk),
        in_specs=[
            _SMEM_SPEC,
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_map),
            pl.BlockSpec((1, 1, bk, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        scratch_shapes=[
            _VMEM((bq, dpad), jnp.float32),
            _VMEM((bq, 128), jnp.float32),
            _VMEM((bq, 128), jnp.float32),
        ],
        interpret=interpret,
    )(seed, q, k, v)
    if mode == "pad":
        out = out[..., :d_orig]
    elif mode == "fp32":
        out = out.astype(in_dtype)
    return out, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_ref, *, sm_scale, causal, dropout_p, offset,
                   block_q, block_k, dpad):
    b, h, iq, ik = (pl.program_id(i) for i in range(4))
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = _pad_d(q_ref[0, 0], dpad)
        k = _pad_d(k_ref[0, 0], dpad)
        v = _pad_d(v_ref[0, 0], dpad)
        do = _pad_d(do_ref[0, 0], dpad)
        lse = lse_ref[0, 0]                             # (bq, 1)
        delta = delta_ref[0, 0]
        s, valid = _block_scores(q, k, sm_scale, causal, iq, ik,
                                 block_q, block_k, offset)
        p = jnp.exp(s - lse)                            # normalized probs
        if causal and offset < 0:
            # offset >= 0 guarantees every row saw >= 1 valid key, so lse
            # is finite and masked scores give exp(-1e30 - lse) == 0 with
            # no re-mask; offset < 0 has all-masked rows (lse ~ -1e30,
            # exp(~0) = 1) that must be zeroed explicitly
            p = jnp.where(valid, p, 0.0)
        dpd = jax.lax.dot_general(                      # dO @ V^T
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            pd = _dropped(p, seed_ref[0], b, h, iq, ik, block_q, block_k,
                          dropout_p)
            ds = pd * dpd - p * delta
        else:
            ds = p * (dpd - delta)
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

    if causal:
        needed = ik * block_k <= iq * block_q + block_q - 1 + offset
        pl.when(needed)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = (acc_ref[...][:, :dq_ref.shape[-1]]
                        .astype(dq_ref.dtype))


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, causal,
                    dropout_p, offset, block_q, block_k, group, dpad):
    b, hkv, ik, g, iq = (pl.program_id(i) for i in range(5))
    nq = pl.num_programs(4)
    h = hkv * group + g

    @pl.when((g == 0) & (iq == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute():
        q = _pad_d(q_ref[0, 0], dpad)
        k = _pad_d(k_ref[0, 0], dpad)
        v = _pad_d(v_ref[0, 0], dpad)
        do = _pad_d(do_ref[0, 0], dpad)
        lse = lse_ref[0, 0]                             # (bq, 1)
        delta = delta_ref[0, 0]
        s, valid = _block_scores(q, k, sm_scale, causal, iq, ik,
                                 block_q, block_k, offset)
        p = jnp.exp(s - lse)  # masked s → exp(-1e30 - lse) == 0 (offset>=0)
        if causal and offset < 0:
            p = jnp.where(valid, p, 0.0)  # all-masked rows: lse ~ -1e30
        dpd = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            pd = _dropped(p, seed_ref[0], b, h, iq, ik, block_q, block_k,
                          dropout_p)
            ds = pd * dpd - p * delta
        else:
            pd = p
            ds = p * (dpd - delta)
        dv_acc[...] += jax.lax.dot_general(             # P_drop^T @ dO
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(             # dS^T @ Q
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

    if causal:
        needed = ik * block_k <= iq * block_q + block_q - 1 + offset
        pl.when(needed)(_compute)
    else:
        _compute()

    @pl.when((g == group - 1) & (iq == nq - 1))
    def _finalize():
        dk_ref[0, 0] = (dk_acc[...][:, :dk_ref.shape[-1]]
                        .astype(dk_ref.dtype))
        dv_ref[0, 0] = (dv_acc[...][:, :dv_ref.shape[-1]]
                        .astype(dv_ref.dtype))


def _bwd_impl(q, k, v, seed, out, lse, do, causal, sm_scale, dropout_p,
              block_q, block_k, interpret):
    in_dtype = q.dtype
    d_orig = q.shape[-1]
    # delta from the ORIGINAL tensors (padding is exact but pointless
    # here — the row-sum is over real lanes either way)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)             # [B, Hq, Sq, 1]
    mode, dp = _sublane_plan(d_orig, in_dtype, interpret)
    if mode == "fp32":
        q, k, v, do = (x.astype(jnp.float32) for x in (q, k, v, do))
    elif mode == "pad":
        q, k, v, do = (_pad_d(x, dp) for x in (q, k, v, do))
    bsz, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    bq = _pick_block(sq, block_q, interpret)
    bk = _pick_block(sk, block_k, interpret)
    nq, nk = sq // bq, sk // bk
    offset = sk - sq
    dpad = dp if mode == "kpad" else d

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          dropout_p=dropout_p, offset=offset,
                          block_q=bq, block_k=bk, dpad=dpad),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(bsz, hq, nq, nk),
        in_specs=[
            _SMEM_SPEC,
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        scratch_shapes=[_VMEM((bq, dpad), jnp.float32)],
        interpret=interpret,
    )(seed, q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          dropout_p=dropout_p, offset=offset,
                          block_q=bq, block_k=bk, group=group, dpad=dpad),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        grid=(bsz, hkv, nk, group, nq),
        in_specs=[
            _SMEM_SPEC,
            pl.BlockSpec((1, 1, bq, d),
                         lambda b, hk, j, g, i, G=group: (b, hk * G + g, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, hk, j, g, i: (b, hk, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, hk, j, g, i: (b, hk, j, 0)),
            pl.BlockSpec((1, 1, bq, d),
                         lambda b, hk, j, g, i, G=group: (b, hk * G + g, i, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b, hk, j, g, i, G=group: (b, hk * G + g, i, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b, hk, j, g, i, G=group: (b, hk * G + g, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b, hk, j, g, i: (b, hk, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, hk, j, g, i: (b, hk, j, 0)),
        ],
        scratch_shapes=[_VMEM((bk, dpad), jnp.float32),
                        _VMEM((bk, dpad), jnp.float32)],
        interpret=interpret,
    )(seed, q, k, v, do, lse, delta)
    if mode == "pad":
        dq, dk, dv = (x[..., :d_orig] for x in (dq, dk, dv))
    elif mode == "fp32":
        dq, dk, dv = (x.astype(in_dtype) for x in (dq, dk, dv))
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, seed, causal, sm_scale, dropout_p, block_q, block_k,
           interpret):
    out, _ = _fwd_impl(q, k, v, seed, causal, sm_scale, dropout_p,
                       block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, seed, causal, sm_scale, dropout_p, block_q, block_k,
               interpret):
    out, lse = _fwd_impl(q, k, v, seed, causal, sm_scale, dropout_p,
                         block_q, block_k, interpret)
    return out, (q, k, v, seed, out, lse)


def _flash_bwd(causal, sm_scale, dropout_p, block_q, block_k, interpret,
               res, do):
    q, k, v, seed, out, lse = res
    dq, dk, dv = _bwd_impl(q, k, v, seed, out, lse, do, causal, sm_scale,
                           dropout_p, block_q, block_k, interpret)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_bhsd(q, k, v, *, causal: bool = False,
                         sm_scale: Optional[float] = None,
                         dropout_p: float = 0.0, seed=None,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None,
                         interpret: Optional[bool] = None,
                         window: Optional[int] = None):
    """Flash attention over ``[B, H, S, D]`` tensors (GQA allowed: K/V may
    have ``Hq / G`` heads). Differentiable; bwd recomputes attention from
    the saved ``[B, H, S]`` fp32 log-sum-exp.

    ``window`` (static, causal only): query i sees keys in
    ``(i - window, i]`` (bottom-right aligned like the causal edge); key
    blocks wholly outside a query block's windows are neither computed
    nor copied. Forward only: the serving prefill's path.

    ``dropout_p`` applies attention-probability dropout inside the kernel,
    seeded by ``seed`` (int32 scalar/array); the same mask is regenerated in
    the backward kernels.
    """
    hq, hkv = q.shape[1], k.shape[1]
    if hq % hkv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    it = _interpret() if interpret is None else interpret
    if not supports(q.shape[2], k.shape[2], it):
        raise ValueError(
            f"unsupported seq lens ({q.shape[2]}, {k.shape[2]}) — caller "
            "should fall back to the chunked XLA path")
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    else:
        seed = jnp.asarray(seed, jnp.int32).reshape((1,))
    # sub-lane head dims (D % 128 != 0, bf16, on device) are handled
    # INSIDE _fwd_impl/_bwd_impl (_sublane_plan: zero-pad to a lane
    # multiple by default, keeping native bf16 MXU dots) so the
    # explicit-residual callers (ops/flash_residual.py) get the same
    # treatment as this custom_vjp path.
    if block_q is None or block_k is None:
        # consult the autotune cache (ops/autotune.py); 1024x1024 is the
        # measured default at llama shapes on v5e
        from .autotune import flash_signature, lookup

        tuned = lookup("flash_attention",
                       flash_signature(q.shape[2], k.shape[2], q.shape[-1],
                                       causal, jnp.dtype(q.dtype).name)) \
            or {}
        block_q = block_q or tuned.get("block_q", 1024)
        block_k = block_k or tuned.get("block_k", 1024)
    if window is not None:
        if not causal or dropout_p:
            raise ValueError("window needs causal=True and no dropout")
        return _fwd_impl(q, k, v, seed, True, float(sm_scale), 0.0,
                         block_q, block_k, it, window=int(window))[0]
    return _flash(q, k, v, seed, causal, float(sm_scale), float(dropout_p),
                  block_q, block_k, it)
