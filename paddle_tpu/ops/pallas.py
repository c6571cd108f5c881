"""Pallas TPU kernel surface.

The fused-op set the reference implements as hand-written CUDA
(fluid/operators/fused/fused_multi_transformer_op.cu, phi/kernels/gpu/
flash_attn_kernel.cu, fused_rope_kernel.cu, ...) maps here to Pallas TPU
kernels. Flash/paged attention and MoE grouped-matmul use the Pallas kernels
shipped with JAX (jax.experimental.pallas.ops.tpu — maintained, MXU-tuned);
the remaining fused set (rope, bias-dropout-residual-LN, KV-cache decode
step) are hand-written in paddle_tpu/ops/pallas_kernels/.

Non-TPU backends fall back to a chunked XLA composition (no S² HBM
materialisation) so tests run anywhere.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["flash_attention", "paged_attention", "grouped_matmul",
           "prefix_chunk_attention"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _kernel_routable(x) -> bool:
    """Whether a Pallas kernel may take the (traced) array ``x`` here: on
    a TPU, and ``x`` not laid out over a multi-device mesh that GSPMD
    partitions. Mosaic kernels cannot be partitioned automatically —
    lowering refuses them ("wrap the call in a shard_map") — so under
    such a mesh the XLA composition runs instead, which GSPMD does
    partition; inside a ``shard_map`` (every mesh axis Manual, as the
    ``tp=`` wraps below make it) the kernel sees a local block and is
    fine. The mesh is read off the value's type, where jit puts it for
    arguments committed to a ``NamedSharding``."""
    if not _on_tpu():
        return False
    mesh = jax.typeof(x).sharding.mesh
    return (mesh.empty or mesh.size == 1
            or all(t == jax.sharding.AxisType.Manual
                   for t in mesh.axis_types))


def _chunked_attention(q, k, v, causal: bool, sm_scale: float,
                       chunk: int = 512, q_offset=None, window=None):
    """Memory-efficient attention fallback: online-softmax over key chunks
    (the flash-attention recurrence expressed in XLA; no [S,S] buffer).

    ``q_offset`` (a traced int32, or None) switches the causal mask to
    ABSOLUTE positions: query row i sits at position ``q_offset + i`` and
    attends keys at ``kpos <= q_offset + i`` — the chunked-prefill form,
    where q is one fixed-shape chunk of a prompt and k/v are the whole
    (partially written) KV cache. ``window`` (causal only) keeps the last
    ``window`` keys of each query's causal range."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nchunk = max(1, (sk + chunk - 1) // chunk)
    csize = (sk + nchunk - 1) // nchunk
    # pad keys to multiple
    pad = nchunk * csize - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kc = k.reshape(b, h, nchunk, csize, d)
    vc = v.reshape(b, h, nchunk, csize, d)
    qpos = jnp.arange(sq)

    def body(carry, idx):
        acc, m, l = carry
        kk = kc[:, :, idx]
        vv = vc[:, :, idx]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * sm_scale
        s = s.astype(jnp.float32)
        kpos = idx * csize + jnp.arange(csize)
        valid = kpos < sk
        if q_offset is not None:
            # absolute-position causal: the chunked-prefill mask
            valid = valid[None, :] & (
                q_offset + qpos[:, None] >= kpos[None, :])
        elif causal:
            # bottom-right alignment (queries end at the last key): the
            # decode-with-KV-cache convention, matching _sdpa_ref's
            # tril(k=sk-sq) — query i attends keys <= i + (sk - sq)
            valid = valid[None, :] & (
                qpos[:, None] + (sk - sq) >= kpos[None, :])
            if window is not None:
                valid = valid & (kpos[None, :]
                                 > qpos[:, None] + (sk - sq) - window)
        else:
            valid = jnp.broadcast_to(valid[None, :], (sq, csize))
        s = jnp.where(valid[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard all -inf rows
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(valid[None, None], p, 0.0)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        alpha = jnp.where(jnp.isfinite(alpha), alpha, 0.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v.dtype), vv).astype(jnp.float32)
        return (acc, m_new, l), None

    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), jnp.arange(nchunk))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def flash_attention(q, k, v, causal: bool = False, sm_scale: float = None,
                    dropout_p: float = 0.0, seed=None, tp=None,
                    window: int = None):
    """[B, S, H, D] paddle layout; GQA allowed (K/V may carry fewer heads).

    ``window`` (static; causal, no dropout, forward only): query i sees
    keys in ``(i - window, i]`` — a sliding-window layer's prefill. The
    kernel skips key blocks wholly outside it.

    ``tp=(mesh, axis)`` shard_maps the whole call over the head axis
    (q on H, k/v on their own Hkv) — the tensor-parallel serving
    engines' prefill path: each mesh shard runs the unmodified
    kernel/fallback on its local head slice, zero attention-side
    communication (see ``inference/tp.py``).

    TPU: this framework's own Pallas flash kernel
    (ops/flash_attention_kernel.py — reference analog:
    phi/kernels/gpu/flash_attn_kernel.cu:213) with bottom-right causal
    alignment, grouped KV in the index maps, and in-kernel dropout.
    Unsupported shapes / non-TPU: chunked online-softmax XLA fallback
    (dropout not available there — callers route dropout elsewhere).
    """
    if tp is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh, ax = tp
        hs = P(None, None, ax, None)
        return shard_map(
            lambda q_, k_, v_: flash_attention(
                q_, k_, v_, causal=causal, sm_scale=sm_scale,
                dropout_p=dropout_p, seed=seed, window=window),
            mesh=mesh, in_specs=(hs, hs, hs), out_specs=hs,
            check_vma=False)(q, k, v)
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)

    # head-batched BSHD-native path: no layout transposes (PERF.md ~11ms/
    # step at bench shapes). Opt-in until TPU-measured faster — flip
    # FLAGS_flash_head_batched once experiments/exp_flash_hb.py says so.
    from ..framework.flags import get_flags

    if get_flags("FLAGS_flash_head_batched")["FLAGS_flash_head_batched"] \
            and _on_tpu() and window is None:
        from .flash_attention_hb import (flash_attention_bshd_hb,
                                         supports_hb)

        if supports_hb(q.shape, k.shape, dropout_p):
            return flash_attention_bshd_hb(q, k, v, causal=causal,
                                           sm_scale=scale)

    qt = jnp.swapaxes(q, 1, 2)  # [B, H, S, D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    from .flash_attention_kernel import flash_attention_bhsd, supports

    # off-TPU the kernel runs in interpret mode (~17x slower than the XLA
    # fallback) — only worth it when in-kernel dropout semantics are needed
    use_kernel = supports(qt.shape[2], kt.shape[2]) and (
        _kernel_routable(q) or dropout_p > 0.0)
    if use_kernel:
        out = flash_attention_bhsd(qt, kt, vt, causal=causal, sm_scale=scale,
                                   dropout_p=dropout_p, seed=seed,
                                   window=window)
    else:
        if dropout_p > 0.0:
            raise ValueError("dropout requires the Pallas kernel path "
                             "(seq lens must be block-divisible)")
        if kt.shape[1] != qt.shape[1]:  # GQA fallback: materialize groups
            rep = qt.shape[1] // kt.shape[1]
            kt = jnp.repeat(kt, rep, axis=1)
            vt = jnp.repeat(vt, rep, axis=1)
        out = _chunked_attention(qt, kt, vt, causal, scale, window=window)
    return jnp.swapaxes(out, 1, 2)


def prefix_chunk_attention(q, k_cache, v_cache, pos, sm_scale: float = None,
                           tp=None):
    """Chunked/padded-prefill attention: queries at ABSOLUTE positions
    ``[pos, pos+S)`` attend causally over the written prefix of a KV
    cache (the chunk's own K/V already written at ``[pos, pos+S)``).

    ``tp=(mesh, axis)`` shard_maps the recurrence over the head axis
    (``pos`` replicates) — the tensor-parallel chunked-prefill /
    warm-admission / spec-verify path (see ``inference/tp.py``).

    q: [B, S, H, D]; k/v_cache: [B, W, Hkv, D] (GQA allowed); pos: traced
    int32 scalar. Returns [B, S, H, D] in q's dtype.

    This is the SAME online-softmax recurrence as the one-shot
    ``flash_attention`` fallback — masked-out cache columns contribute
    exact float zeros to every reduction — so at cache widths within one
    key chunk (<= 512) a prompt prefilled in fixed-shape chunks at traced
    offsets, or padded up to a length bucket, reproduces single-shot
    prefill logits and KV BITWISE (beyond one chunk the key-chunk
    boundaries differ between widths and identity degrades to ~1-ulp).
    The serving engines' bounded-compile prefill rides on this: one
    compiled program per (chunk shape, cache width), reused at every
    offset, instead of one per distinct prompt length.
    """
    if tp is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh, ax = tp
        hs = P(None, None, ax, None)
        return shard_map(
            lambda q_, k_, v_, p_: prefix_chunk_attention(
                q_, k_, v_, p_, sm_scale=sm_scale),
            mesh=mesh, in_specs=(hs, hs, hs, P()), out_specs=hs,
            check_vma=False)(q, k_cache, v_cache, pos)
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qt = jnp.swapaxes(q, 1, 2)          # [B, H, S, D]
    kt = jnp.swapaxes(k_cache, 1, 2)
    vt = jnp.swapaxes(v_cache, 1, 2)
    if kt.shape[1] != qt.shape[1]:      # GQA fallback: materialize groups
        rep = qt.shape[1] // kt.shape[1]
        kt = jnp.repeat(kt, rep, axis=1)
        vt = jnp.repeat(vt, rep, axis=1)
    out = _chunked_attention(qt, kt, vt, causal=False, sm_scale=scale,
                             q_offset=pos)
    return jnp.swapaxes(out, 1, 2)


def paged_attention(q, k_pages, v_pages, lengths, page_indices, **kw):
    """Decode-time KV-cache attention over paged KV (reference analog:
    masked_multihead_attention_kernel in fused_multi_transformer_op.cu.h:745).
    TPU: JAX Pallas paged_attention kernel, over pools laid out
    ``[Hkv, pages, page_size, D]``. The serving engines use the
    framework's own ``ops/paged_attention.py::paged_decode_mha`` (a page
    holds all KV heads; runs in interpret mode too, integrates with
    inference.PagedKVCache).
    Quantized (int8) pools are NOT supported here — the stock kernel
    has no scale inputs; the serving engines' ``kv_dtype="int8"`` path
    uses ``paged_decode_mha``'s fused dequant instead."""
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        paged_attention as _pa)

    return _pa(q, k_pages, v_pages, lengths, page_indices, **kw)


# The scoped VMEM a pallas_call gets on v5e when it sets no
# vmem_limit_bytes, as the megablox kernel's does not.
GMM_VMEM_BYTES = 16 * 2**20


def _gmm_vmem_bytes(tm: int, tk: int, tn: int) -> int:
    """VMEM the megablox kernel's blocks take at tiles (tm, tk, tn): the
    double-buffered bf16 [tm, tk] rows and [tk, tn] weights, the
    double-buffered [tm, tn] output (counted as float32, the widest a
    caller asks for) and the float32 [tm, tn] accumulator."""
    return 2 * (tm * tk + tk * tn) * 2 + 2 * tm * tn * 4 + tm * tn * 4


def _widest_tile(dim: int, fits) -> int | None:
    """The widest multiple of 128 that divides ``dim`` and ``fits``."""
    for t in range(dim - dim % 128, 0, -128):
        if dim % t == 0 and fits(t):
            return t
    return None


def _gmm_tiling(m: int, k: int, n: int):
    """Tile sizes for the megablox kernel at (m, k, n): few, wide steps,
    so that one step streams one group's [tk, tn] slab of weights, and
    tiles that divide k and n. A tk that leaves a remainder makes the
    kernel's last k-tile of every group mask its whole [tm, tk] rows and
    [tk, tn] weights on the vector unit before a full-depth product; a tn
    that leaves one adds a partial output tile.

    tm is 128, and 256 from 4,096 rows; 512 rows a tile with a 1024-wide
    slab runs out of VMEM. tk is min(k, 2048) and tn min(n, 1024) where
    they divide (128 groups, k 2048, n 1024: a decode batch of 256 rows
    streams 690 GB/s at (128, 2048, 1024), a prefill of 4,096 to 65,536
    rows is fastest at (256, 2048, 1024)); where one does not, it is the
    widest multiple of 128 that divides its dimension and keeps
    :func:`_gmm_vmem_bytes` under ``GMM_VMEM_BYTES``. By the chip's clock
    (experiments/exp_grouped_matmul.py, v5e, ms a call, uniform routing):

        rows x k x n, groups    remainder tile     divisors (* = picked)
        144 x 2560 x 768, 64    tk 2048: 0.506     1280: 0.408  *2560: 0.353
        12288 x 2560 x 768      tk 2048: 1.227     1280: 0.835  *2560: 0.682
        144 x 768 x 2560        tn 1024: 0.380     1280: 0.357  *2560: 0.356
        12288 x 768 x 2560      tn 1024: 1.011     1280: 0.904  *2560: 0.866
        128 x 7168 x 2048, 16   tk 2048: 0.342     1024: 0.335  *1792: 0.336
        16384 x 7168 x 2048     tk 2048: 1.136     1024: 1.034  *1792: 1.024

    (at k 7168, 16 of 256 experts are held: a sixteenth of the rows is in
    a group). Shapes that divide keep their tiles: at 256 x 1024 x 2048 a
    tn of 2048 reads the same as 1024 (0.670 ms)."""
    tm = 256 if m >= 4096 else 128
    tk, tn = min(k, 2048), min(n, 1024)
    if k % tk:
        tk = _widest_tile(
            k, lambda t: _gmm_vmem_bytes(tm, t, tn) <= GMM_VMEM_BYTES) or tk
    if n % tn:
        tn = _widest_tile(
            n, lambda t: _gmm_vmem_bytes(tm, tk, t) <= GMM_VMEM_BYTES) or tn
    return tm, tk, tn


def grouped_matmul(lhs, rhs, group_sizes, preferred_element_type=jnp.float32):
    """MoE expert grouped GEMM (reference analog:
    phi/kernels/fusion/cutlass/moe_kernel.cu): rows of ``lhs`` [m, k],
    sorted by group, times their group's matrix of ``rhs`` [groups, k, n];
    ``group_sizes`` [groups] int32. Rows past ``sum(group_sizes)`` belong
    to no group and their result is UNDEFINED (the kernel never visits
    them): mask them. Only the groups that have rows are read.

    TPU: the megablox gmm kernel, tiled by :func:`_gmm_tiling`.
    Elsewhere: ``jax.lax.ragged_dot``."""
    if _on_tpu():
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        m, k = lhs.shape
        tm, tk, tn = _gmm_tiling(m, k, rhs.shape[2])
        pad = -m % tm
        if pad:
            lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        out = gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                  preferred_element_type=preferred_element_type,
                  tiling=(tm, tk, tn))
        return out[:m] if pad else out
    # off the TPU: XLA's own ragged product (no [rows, K, N] gather of the
    # weights, so the routing code runs at sizes that fit a CPU test)
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes.astype(jnp.int32),
        preferred_element_type=preferred_element_type)
