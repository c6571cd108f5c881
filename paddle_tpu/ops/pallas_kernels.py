"""Hand-written Pallas TPU kernels for the reference's fused-op set.

Reference north-star kernels (SURVEY.md §2.2 fused ops):
- fused_rms_norm / fused_layer_norm ≙ fused_bias_dropout_residual_layer_norm
  (operators/fused/fused_bias_dropout_residual_layer_norm_op.cu,
   fused_layernorm_residual_dropout_bias.h)
- fused_rope ≙ fused_rotary_position_embedding (phi fusion/gpu/fused_rope_kernel.cu:87)
- fused_linear_param_grad_add (phi fusion fused_linear_param_grad_add_kernel.cu)
- decode_mha ≙ masked_multihead_attention_kernel decode-time MHA over a KV
  cache (fused_multi_transformer_op.cu.h:745)

Design: each kernel is a `pl.pallas_call` tiled for VMEM with the row/lane
constraints from the TPU tiling table (last dim 128-aligned blocks where it
matters); off-TPU the SAME kernel runs in interpreter mode so CPU tests
exercise the real kernel code path, not a separate fallback. fp32 accumulation
throughout; bf16 in/out supported.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# pltpu ships with every jax build (memory-space enums and scratch shapes
# work under interpret mode too) — import unconditionally so kernels can use
# SMEM operands and VMEM scratch without per-call-site fallbacks
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

__all__ = ["rms_norm", "fused_layer_norm", "fused_rope", "decode_mha",
           "fused_linear_param_grad_add"]


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def _row_block(n_rows: int, target: int = 256) -> int:
    b = min(n_rows, target)
    while n_rows % b:
        b -= 1
    return max(b, 1)


# What the pipelined (double-buffered) operand blocks of one grid step may
# take of the 16 MiB of scoped VMEM Mosaic grants a kernel on v5e; the
# rest is left to the kernel body's fp32 temporaries. Compiles for the
# described chip (tests/test_chip_compile.py) hold the sizes below to it.
_VMEM_BLOCK_BUDGET = 12 << 20


def _vmem_rows(kernel: str, n_rows: int, bytes_per_row: int, target: int,
               interpret: bool) -> int:
    """Rows per block for a kernel that streams ``n_rows`` rows of
    ``bytes_per_row`` (all pipeline buffers of one row counted): the
    largest divisor of ``n_rows`` that is <= ``target``, fits
    ``_VMEM_BLOCK_BUDGET`` and, when compiled for the chip, is a sublane
    multiple (8) or the whole axis. Raises where no such block exists —
    the chip's compiler would refuse the kernel anyway, less legibly."""
    cap = min(target, max(_VMEM_BLOCK_BUDGET // bytes_per_row, 1))
    for b in range(min(n_rows, cap), 0, -1):
        if n_rows % b == 0 and (interpret or b % 8 == 0 or b == n_rows):
            return b
    raise ValueError(
        f"{kernel}: no row block for {n_rows} rows of {bytes_per_row} "
        f"bytes — needs a divisor <= {cap} that is a multiple of 8")


def _lane_block(kernel: str, n: int, target: int, interpret: bool) -> int:
    """Tile width along a lane (last) or contraction dim: the largest
    128-multiple divisor of ``n`` that is <= ``target`` (5504 = 43 x 128
    gives 128, 11008 gives 256). Interpret mode takes any divisor. On
    the chip a width with no such divisor raises: Mosaic refuses
    sub-lane-multiple bf16 matmul tiles, and nothing here pads or
    upcasts behind the caller's back."""
    if interpret:
        return _row_block(n, target)
    for b in range(target - target % 128, 0, -128):
        if n % b == 0:
            return b
    raise ValueError(
        f"{kernel}: dim {n} has no 128-multiple tile <= {target}; pad "
        "the operand to a lane multiple before calling the kernel")


# ---------------------------------------------------------------------------
# RMSNorm (Llama hot path)
# ---------------------------------------------------------------------------


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _rms_fwd_impl(x, weight, eps):
    shape = x.shape
    h = shape[-1]
    x2 = x.reshape(-1, h)
    interpret = _interpret()
    # x block in + out, each double-buffered: 256 rows at 4096 bf16, 128
    # at 4096 fp32 (256 ran out of VMEM)
    rb = _vmem_rows("rms_norm", x2.shape[0],
                    4 * h * jnp.dtype(x.dtype).itemsize, 256, interpret)
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        grid=(x2.shape[0] // rb,),
        in_specs=[pl.BlockSpec((rb, h), lambda i: (i, 0)),
                  pl.BlockSpec((h,), lambda i: (0,))],
        out_specs=pl.BlockSpec((rb, h), lambda i: (i, 0)),
        interpret=interpret,
    )(x2, weight)
    return out.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm(x, weight, eps):
    return _rms_fwd_impl(x, weight, eps)


def _rms_vjp_fwd(x, weight, eps):
    return _rms_fwd_impl(x, weight, eps), (x, weight)


def _rms_vjp_bwd(eps, res, g):
    # pallas fwd, XLA bwd: out = x·r·w with r = rsqrt(mean(x²)+eps);
    # dx = w·g·r − x·r³/H·Σ(g·w·x);  dw = Σ_rows g·x·r
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    h = xf.shape[-1]
    r = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    gw = gf * wf
    dx = gw * r - xf * (r ** 3 / h) * jnp.sum(gw * xf, -1, keepdims=True)
    dw = jnp.sum((gf * xf * r).reshape(-1, h), axis=0)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_rms_norm.defvjp(_rms_vjp_fwd, _rms_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("eps",))
def rms_norm(x, weight, eps: float = 1e-6):
    """y = x / sqrt(mean(x², -1) + eps) * w. x: [..., H]. Differentiable
    (custom VJP: Pallas forward, XLA backward)."""
    return _rms_norm(x, weight, eps)


# ---------------------------------------------------------------------------
# Fused bias + residual + LayerNorm  (dropout composed outside under jit —
# XLA fuses the mask multiply into this kernel's input)
# ---------------------------------------------------------------------------


def _ln_kernel(x_ref, r_ref, b_ref, g_ref, beta_ref, o_ref, *, eps,
               has_resid, has_bias):
    x = x_ref[...].astype(jnp.float32)
    if has_bias:
        x = x + b_ref[...].astype(jnp.float32)
    if has_resid:
        x = x + r_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    y = y * g_ref[...].astype(jnp.float32) + beta_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _ln_fwd_impl(x, residual, bias, gamma, beta, eps):
    shape = x.shape
    h = shape[-1]
    x2 = x.reshape(-1, h)
    n = x2.shape[0]
    has_resid = residual is not None
    interpret = _interpret()
    # x (+ residual) in and out, each double-buffered
    rb = _vmem_rows("fused_layer_norm", n,
                    (6 if has_resid else 4) * h
                    * jnp.dtype(x.dtype).itemsize, 256, interpret)
    has_bias = bias is not None
    r2 = residual.reshape(-1, h) if has_resid else jnp.zeros((1, h), x.dtype)
    b = bias if has_bias else jnp.zeros((h,), x.dtype)
    out = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps, has_resid=has_resid,
                          has_bias=has_bias),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        grid=(n // rb,),
        in_specs=[
            pl.BlockSpec((rb, h), lambda i: (i, 0)),
            (pl.BlockSpec((rb, h), lambda i: (i, 0)) if has_resid
             else pl.BlockSpec((1, h), lambda i: (0, 0))),
            pl.BlockSpec((h,), lambda i: (0,)),
            pl.BlockSpec((h,), lambda i: (0,)),
            pl.BlockSpec((h,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((rb, h), lambda i: (i, 0)),
        interpret=interpret,
    )(x2, r2, b, gamma, beta)
    return out.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _fused_ln(x, residual, bias, gamma, beta, eps):
    return _ln_fwd_impl(x, residual, bias, gamma, beta, eps)


def _ln_vjp_fwd(x, residual, bias, gamma, beta, eps):
    return (_ln_fwd_impl(x, residual, bias, gamma, beta, eps),
            (x, residual, bias, gamma))


def _ln_vjp_bwd(eps, res, g):
    x, residual, bias, gamma = res
    shape = x.shape
    h = shape[-1]
    z = x.astype(jnp.float32)
    if bias is not None:
        z = z + bias.astype(jnp.float32)
    if residual is not None:
        z = z + residual.astype(jnp.float32)
    mu = jnp.mean(z, -1, keepdims=True)
    zc = z - mu
    rstd = jax.lax.rsqrt(jnp.mean(zc * zc, -1, keepdims=True) + eps)
    xhat = zc * rstd
    gf = g.astype(jnp.float32)
    dgamma = jnp.sum((gf * xhat).reshape(-1, h), axis=0)
    dbeta_full = jnp.sum(gf.reshape(-1, h), axis=0)
    dxhat = gf * gamma.astype(jnp.float32)
    dz = rstd * (dxhat - jnp.mean(dxhat, -1, keepdims=True)
                 - xhat * jnp.mean(dxhat * xhat, -1, keepdims=True))
    dx = dz.astype(x.dtype)
    dresid = dz.astype(residual.dtype) if residual is not None else None
    dbias = (jnp.sum(dz.reshape(-1, h), axis=0).astype(bias.dtype)
             if bias is not None else None)
    return (dx, dresid, dbias, dgamma.astype(gamma.dtype),
            dbeta_full.astype(gamma.dtype))


_fused_ln.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("eps",))
def fused_layer_norm(x, residual=None, bias=None, gamma=None, beta=None,
                     eps: float = 1e-5):
    """LN(x [+ bias] [+ residual]) * gamma + beta — the core of the
    reference's fused_bias_dropout_residual_layer_norm. Differentiable
    (Pallas forward, XLA backward)."""
    h = x.shape[-1]
    if gamma is None:
        gamma = jnp.ones((h,), x.dtype)
    if beta is None:
        beta = jnp.zeros((h,), x.dtype)
    return _fused_ln(x, residual, bias, gamma, beta, eps)


# ---------------------------------------------------------------------------
# Rotary position embedding (NeoX interleaved-halves convention, matching
# the reference fused_rope_kernel.cu:87 use_neox_rotary_style)
# ---------------------------------------------------------------------------


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref):
    """Roll-form rotation: out = x·C + roll(x, D/2)·S with C = [cos|cos],
    S = [-sin|sin] — one multiply-add pass, no lane-dim split/concat (the
    half-slice forms relayout the 128-lane head_dim twice)."""
    x = x_ref[...].astype(jnp.float32)          # [1, bs_rows, H, D]
    c = cos_ref[...].astype(jnp.float32)[..., None, :]  # [1, bs, 1, D]
    s = sin_ref[...].astype(jnp.float32)[..., None, :]
    d2 = x.shape[-1] // 2
    xr = jnp.roll(x, d2, axis=-1) if _interpret() \
        else pltpu.roll(x, d2, 3)
    o_ref[...] = (x * c + xr * s).astype(o_ref.dtype)


def _rope_impl(x, cos, sin):
    b_, s_, h_, d_ = x.shape
    # full-width tables: C = [cos|cos], S = [-sin|sin]; with roll(x, d2)
    # this reproduces (x1·c − x2·s | x2·c + x1·s)
    cos_f = jnp.concatenate([cos, cos], axis=-1)
    sin_f = jnp.concatenate([-sin, sin], axis=-1)
    cos_b = jnp.broadcast_to(cos_f[None], (b_, s_, d_))
    sin_b = jnp.broadcast_to(sin_f[None], (b_, s_, d_))
    # x block in + out, each double-buffered, sets the row block: 512
    # rows at 16 heads x 128 bf16, 256 at 32 heads (512 ran out of VMEM)
    interpret = _interpret()
    sb = _vmem_rows("fused_rope", s_,
                    4 * h_ * d_ * jnp.dtype(x.dtype).itemsize, 512,
                    interpret)
    out = pl.pallas_call(
        _rope_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(b_, s_ // sb),
        in_specs=[
            pl.BlockSpec((1, sb, h_, d_), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, sb, d_), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sb, d_), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, sb, h_, d_), lambda i, j: (i, j, 0, 0)),
        interpret=interpret,
    )(x, cos_b, sin_b)
    return out


@jax.custom_vjp
def _rope(x, cos, sin):
    return _rope_impl(x, cos, sin)


def _rope_vjp_fwd(x, cos, sin):
    return _rope_impl(x, cos, sin), (cos, sin)


def _rope_vjp_bwd(res, g):
    # rotation transpose = rotation by −θ: reuse the SAME kernel with −sin
    cos, sin = res
    dx = _rope_impl(g, cos, -sin)
    return dx, jnp.zeros_like(cos), jnp.zeros_like(sin)


_rope.defvjp(_rope_vjp_fwd, _rope_vjp_bwd)


@jax.jit
def fused_rope(x, cos, sin):
    """Apply rotary embedding. x: [B, S, H, D]; cos/sin: [S, D/2].
    Differentiable (the VJP reuses the kernel with −sin)."""
    return _rope(x, cos, sin)


# ---------------------------------------------------------------------------
# Decode-time MHA over a KV cache (one query token per sequence)
# ---------------------------------------------------------------------------


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale, block_s):
    """Online-softmax decode step over one S-block of the KV cache.

    Grid (B, nS) — S innermost, accumulated in VMEM scratch so arbitrarily
    long caches stream through a bounded working set (round-1 version loaded
    the whole [S, H, D] slab per batch row and spilled at 7B+ shapes).
    """
    ib, js = pl.program_id(0), pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(js == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    ln = len_ref[ib]

    # skip blocks entirely past the valid length
    @pl.when(js * block_s < ln)
    def _compute():
        # decode is HBM-bandwidth-bound: all math is VPU-shaped (no batched
        # dots), keeping the cache streaming at full rate. Layout (bs, H):
        # per-head softmax reduces over sublanes, heads stay in lanes.
        q = q_ref[0].astype(jnp.float32)            # [H, D]
        k = k_ref[0].astype(jnp.float32)            # [bs, H, D]
        v = v_ref[0].astype(jnp.float32)
        s = jnp.sum(q[None] * k, axis=-1) * scale   # [bs, H]
        pos = js * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (block_s, 1), 0)
        mask = pos < ln                             # [bs, 1]
        s = jnp.where(mask, s, -1e30)
        m_prev = m_ref[...]                         # [1, H]
        m_cur = jnp.max(s, axis=0, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                      # [bs, H]
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)             # [1, H]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=0, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = (acc_ref[...] * jnp.transpose(alpha)
                        + jnp.sum(p[:, :, None] * v, axis=0))  # [H, D]

    @pl.when(js == ns - 1)
    def _finalize():
        l_safe = jnp.maximum(jnp.transpose(l_ref[...]), 1e-30)  # [H, 1]
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def decode_mha(q, k_cache, v_cache, seq_lens, block_s=None):
    """Single-step decode attention (≙ masked_multihead_attention_kernel,
    fused_multi_transformer_op.cu.h:745).

    q: [B, H, D] (this step's query) — k/v_cache: [B, S, H, D] — seq_lens:
    [B] valid lengths (the new token's k/v must already be written at
    position seq_lens-1). Returns [B, H, D]. The cache streams through VMEM
    in S-blocks with online-softmax accumulation (flash recurrence), so
    S is bounded by HBM, not VMEM. ``block_s=None`` consults the autotune
    cache (experiments/exp_autotune_sweep.py populates it), default 512.
    """
    if block_s is None:
        from .autotune import decode_signature, lookup

        tuned = lookup("decode_mha", decode_signature(
            k_cache.shape[1], q.shape[1], q.shape[2],
            jnp.dtype(q.dtype).name)) or {}
        block_s = tuned.get("block_s", 512)
    return _decode_mha_jit(q, k_cache, v_cache, seq_lens, block_s)


@functools.partial(jax.jit, static_argnums=(4,))
def _decode_mha_jit(q, k_cache, v_cache, seq_lens, block_s):
    b_, h_, d_ = q.shape
    s_max = k_cache.shape[1]
    scale = 1.0 / math.sqrt(d_)
    # K and V blocks, each double-buffered, bound the S-block: block_s
    # rows at 16 heads x 128 bf16, 256 at 32 heads (512 ran out of VMEM)
    interpret = _interpret()
    bs = _vmem_rows("decode_mha", s_max,
                    4 * h_ * d_ * jnp.dtype(k_cache.dtype).itemsize,
                    block_s, interpret)
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_s=bs),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(b_, s_max // bs),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, h_, d_), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, bs, h_, d_), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, bs, h_, d_), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h_, d_), lambda i, j: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h_, d_), jnp.float32),
            pltpu.VMEM((1, h_), jnp.float32),
            pltpu.VMEM((1, h_), jnp.float32),
        ],
        interpret=interpret,
    )(seq_lens, q, k_cache, v_cache)


# ---------------------------------------------------------------------------
# fused_linear_param_grad_add: dW += xᵀ·dy (fp32 accum, in-place on dW)
# ---------------------------------------------------------------------------


def _grad_add_kernel(x_ref, dy_ref, dw_ref, o_ref, acc_ref):
    """One (K-block, N-block) output tile accumulated over T-blocks.

    Grid (nK, nN, nT) — T innermost; the fp32 accumulator lives in VMEM
    scratch, the prior dweight value is folded in at the first T step, and
    the tile is written once at the last (round-1 version mapped whole
    operands into VMEM with no grid and spilled at 4096x11008 fp32)."""
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        acc_ref[...] = dw_ref[...]

    x = x_ref[...]
    dy = dy_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        x, dy, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(it == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = acc_ref[...]


@jax.jit
def fused_linear_param_grad_add(x, dy, dweight):
    """dweight(fp32) += xᵀ @ dy — the reference's main-grad accumulation
    kernel (fused_linear_param_grad_add_kernel.cu): bf16 activations/grad,
    fp32 accumulator, single fused pass, aliased in-place output. Tiled
    over (K, N, T) in 128-multiple tiles so 7B-scale weights (e.g.
    4096x11008) accumulate through a bounded VMEM working set; on the
    chip K, N and T must each be a multiple of 128 (``_lane_block``)."""
    x2 = x.reshape(-1, x.shape[-1])
    dy2 = dy.reshape(-1, dy.shape[-1])
    kdim, ndim = dweight.shape
    tdim = x2.shape[0]
    interpret = _interpret()
    name = "fused_linear_param_grad_add"
    bk = _lane_block(name, kdim, 512, interpret)
    bn = _lane_block(name, ndim, 512, interpret)
    bt = _lane_block(name, tdim, 512, interpret)
    dw32 = dweight.astype(jnp.float32)
    return pl.pallas_call(
        _grad_add_kernel,
        out_shape=jax.ShapeDtypeStruct(dweight.shape, jnp.float32),
        grid=(kdim // bk, ndim // bn, tdim // bt),
        in_specs=[
            pl.BlockSpec((bt, bk), lambda i, j, t: (t, i)),
            pl.BlockSpec((bt, bn), lambda i, j, t: (t, j)),
            pl.BlockSpec((bk, bn), lambda i, j, t: (i, j)),
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda i, j, t: (i, j)),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        input_output_aliases={2: 0},
        interpret=interpret,
    )(x2, dy2, dw32)
