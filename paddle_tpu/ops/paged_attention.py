"""Paged decode attention: KV cache as a shared page pool.

Reference analog: the fused_multi_transformer decode path
(paddle/phi/kernels/fusion/fused_multi_transformer_op.cu.h:745 masked
MHA over a per-batch cache slab). The reference allocates each
sequence's cache contiguously at ``max_len``; THIS module completes the
SURVEY §7 hard part ("KV-cache decode kernel with paged/ragged
batching"): cache pages of ``page_size`` tokens live in one shared pool
``[num_pages, page_size, H, D]`` and a sequence's cache is the page-id
row of a ``page_table`` — so HBM holds the tokens actually in flight
(rounded up to pages), not ``max_batch * max_len``, and admission never
fails on fragmentation (any free page serves any slot).

TPU-native mechanism: the page table rides Pallas SCALAR PREFETCH
(``pltpu.PrefetchScalarGridSpec``) — block index maps read the
prefetched table to aim each K/V page DMA, which is the idiomatic TPU
form of paged attention (indirect addressing happens at DMA-issue time,
not as a gather in the kernel body). The softmax math is byte-for-byte
the ragged ``decode_mha`` recurrence (pallas_kernels.py): online
softmax over pages, block-skip past each row's length, so a short row
costs O(its length).

``PagedKVCache`` (inference/paged_cache.py) owns the pool + free-list;
this module is the pure compute.

QUANTIZED pools (``kv_dtype="int8"`` serving): pass the per-(page,
kv_head) absmax scale arrays and the kernel dequantizes AFTER the page
DMA (``paddle_tpu.quantization.kv`` conventions) — decode's HBM read
is half the bytes, which is the whole lever on bandwidth-bound decode.

Relationship to ``ops/pallas.py::paged_attention``: that function wraps
the STOCK ``jax.experimental.pallas.ops.tpu.paged_attention`` kernel
(same pool/page-table layout) and is the TPU-only, tuned option; THIS
kernel is the framework's own from-scratch implementation — it also
runs in interpret mode (CPU tests) and is the one the parity suite and
PagedKVCache exercise. Numerics agree; fixes to the page-table
convention (-1 unmapped, clamp-on-skip) must land in both.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..quantization.kv import KV_QMAX as _KV_QMAX

__all__ = ["paged_decode_mha"]


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def _paged_decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, scale, page_size,
                         ks_ref=None, vs_ref=None, window=None):
    """One (batch row, page) step of the online-softmax recurrence.

    ``pt_ref``/``len_ref`` are scalar-prefetched; the K/V blocks arriving
    here were already DMA'd from the page the index map selected. With
    ``ks_ref``/``vs_ref`` bound (int8 pools) the K/V block is int8 and
    the per-(page, kv_head) absmax scales dequantize it HERE, after the
    DMA — the HBM read is half the bytes, which is the whole point on
    bandwidth-bound decode."""
    ib, jp = pl.program_id(0), pl.program_id(1)
    npg = pl.num_programs(1)

    @pl.when(jp == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    ln = len_ref[ib]
    # skip pages entirely past the valid length (same contract as
    # decode_mha: short rows cost O(their length))
    needed = jp * page_size < ln
    if window is not None:
        # ... and pages entirely under the window [ln - window, ln)
        lo = jnp.maximum(ln - window, 0)
        needed = needed & ((jp + 1) * page_size > lo)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [Hq, D]
        k = k_ref[0].astype(jnp.float32)            # [ps, Hkv, D]
        v = v_ref[0].astype(jnp.float32)
        if ks_ref is not None:
            # fused dequant (quantization.kv conventions): the scale
            # block is this page's [Hkv] absmax row, selected by the
            # same prefetched-table index map that aimed the K/V DMA
            k = k * (ks_ref[0, 0] * (1.0 / _KV_QMAX))[None, :, None]
            v = v * (vs_ref[0, 0] * (1.0 / _KV_QMAX))[None, :, None]
        g = q.shape[0] // k.shape[1]
        if g > 1:                                   # GQA: share KV heads
            k = jnp.repeat(k, g, axis=1)            # VMEM-local repeat
            v = jnp.repeat(v, g, axis=1)
        s = jnp.sum(q[None] * k, axis=-1) * scale   # [ps, Hq]
        pos = jp * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0)
        mask = pos < ln                             # [ps, 1]
        if window is not None:
            mask = mask & (pos >= lo)
        s = jnp.where(mask, s, -1e30)
        m_prev = m_ref[...]                         # [1, H]
        m_cur = jnp.max(s, axis=0, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                      # [ps, H]
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)             # [1, H]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=0, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = (acc_ref[...] * jnp.transpose(alpha)
                        + jnp.sum(p[:, :, None] * v, axis=0))  # [H, D]

    @pl.when(jp == npg - 1)
    def _finalize():
        l_safe = jnp.maximum(jnp.transpose(l_ref[...]), 1e-30)  # [H, 1]
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _paged_decode_ref(q, k_pool, v_pool, page_table, seq_lens,
                      k_scale=None, v_scale=None):
    """Pure-jnp reference the tests hold the kernel to: gather each
    row's pages dense and run a masked softmax — numerically equivalent
    to the kernel (same f32 math, plain instead of online softmax), NOT
    byte-identical, and it materializes [B, max_pages*page_size] KV.
    Quantized pools dequant here with the same ``quantization.kv``
    conventions the fused kernel uses. No serving path calls it."""
    b, h, d = q.shape
    hkv = k_pool.shape[2]
    ps = k_pool.shape[1]
    idx = jnp.maximum(page_table, 0)                 # [B, maxp]
    k = k_pool[idx].astype(jnp.float32)              # [B, maxp, ps, Hkv, D]
    v = v_pool[idx].astype(jnp.float32)
    if k_scale is not None:
        k = k * (k_scale[idx] / _KV_QMAX)[:, :, None, :, None]
        v = v * (v_scale[idx] / _KV_QMAX)[:, :, None, :, None]
    L = idx.shape[1] * ps
    k = k.reshape(b, L, hkv, d)
    v = v.reshape(b, L, hkv, d)
    g = h // hkv
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bhd,blhd->blh", q.astype(jnp.float32), k)
    s = s * (1.0 / math.sqrt(d))
    mask = (jnp.arange(L, dtype=jnp.int32)[None, :, None]
            < seq_lens[:, None, None])
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=1)
    p = jnp.where(mask, p, 0.0)
    return jnp.einsum("blh,blhd->bhd", p, v).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "tp", "window"))
def paged_decode_mha(q, k_pool, v_pool, page_table, seq_lens,
                     k_scale=None, v_scale=None, interpret=None,
                     tp=None, window=None):
    """Single-step decode attention over a paged KV pool.

    q: [B, Hq, D] (this step's query)
    k_pool/v_pool: [num_pages, page_size, Hkv, D] shared pools (GQA:
        Hq may be a multiple of Hkv — KV heads are shared in-kernel)
    page_table: [B, max_pages] int32 — page ids per sequence, in order;
        entries past a row's length are never dereferenced (clamped to 0
        for the skipped DMA)
    seq_lens: [B] int32 valid lengths (the new token's k/v must already
        be written at position seq_lens-1 via PagedKVCache.write_tokens)
    k_scale/v_scale: [num_pages, Hkv] f32 per-page-per-head absmax
        scales for INT8 pools (quantization.kv conventions) — pass both
        or neither. Dequant fuses into the kernel after the page DMA,
        so the HBM read stays int8 (the bandwidth win quantized KV
        exists for); the output is f32-accumulated either way.
    tp: tensor-parallel handle ``(mesh, axis)`` (static) — wraps the
        kernel in ``shard_map`` over the head axis: q shards on Hq,
        pools (and scales) on Hkv, table/lens replicate, and each mesh
        shard runs the UNMODIFIED kernel on its local head slice (pages
        are never split, so the page-table indirection is per-shard
        identical). Zero communication inside attention; on TPU this is
        what keeps the sharded pools' HBM win real — without it the
        Mosaic custom call would force an all-gather of the pool every
        decode step.
    window: static int — a row attends only its last ``window``
        positions, ``[seq_len - window, seq_len)``; pages wholly under
        that range are neither computed nor copied. A table whose first
        column is not position 0 (a sliding layer's ring turned to its
        window's first page) passes lengths counted from that column.
    Returns [B, H, D].
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    if tp is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh, ax = tp
        head, pool, sc = (P(None, ax, None), P(None, None, ax, None),
                          P(None, ax))
        operands = [q, k_pool, v_pool, page_table, seq_lens]
        in_specs = [head, pool, pool, P(), P()]
        if k_scale is not None:
            operands += [k_scale, v_scale]
            in_specs += [sc, sc]
        return shard_map(
            lambda *a: paged_decode_mha(*a, interpret=interpret,
                                        window=window),
            mesh=mesh, in_specs=tuple(in_specs), out_specs=head,
            check_vma=False)(*operands)
    b, h, d = q.shape
    hkv = k_pool.shape[2]
    if h % hkv:
        raise ValueError(f"Hq={h} not a multiple of Hkv={hkv}")
    page_size = k_pool.shape[1]
    npages = page_table.shape[1]
    scale = 1.0 / math.sqrt(d)
    it = _interpret() if interpret is None else interpret

    def _column(bi, pi, lens):
        if window is None:
            return pi
        # a skipped step aims at the nearest page the row needs: its
        # neighbour's page, so no copy is issued for it
        ln = lens[bi]
        return jnp.clip(pi, jnp.maximum(ln - window, 0) // page_size,
                        jnp.maximum(ln - 1, 0) // page_size)

    def _page(bi, pi, pt, lens):
        # clamp: skipped steps (page beyond seq_len, table entry -1)
        # still issue a DMA — aim it at page 0 harmlessly
        return (jnp.maximum(pt[bi, _column(bi, pi, lens)], 0), 0, 0, 0)

    def _page_scale(bi, pi, pt, lens):
        return (jnp.maximum(pt[bi, _column(bi, pi, lens)], 0), 0, 0)

    quant = k_scale is not None
    in_specs = [
        pl.BlockSpec((1, h, d), lambda bi, pi, pt, ln: (bi, 0, 0)),
        pl.BlockSpec((1, page_size, hkv, d), _page),
        pl.BlockSpec((1, page_size, hkv, d), _page),
    ]
    operands = [q, k_pool, v_pool]
    if quant:
        # one page's [Hkv] scale row as a (1, 1, Hkv) block of a
        # [num_pages, 1, Hkv] view: a (1, Hkv) block of the 2-D array is
        # refused by the TPU lowering (second-to-last block dim must be
        # a multiple of 8 or the whole axis)
        in_specs += [pl.BlockSpec((1, 1, hkv), _page_scale),
                     pl.BlockSpec((1, 1, hkv), _page_scale)]
        operands += [k_scale[:, None, :], v_scale[:, None, :]]

    def kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, *rest):
        if quant:
            ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
        else:
            ks_ref = vs_ref = None
            o_ref, acc_ref, m_ref, l_ref = rest
        _paged_decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref,
                             o_ref, acc_ref, m_ref, l_ref, scale=scale,
                             page_size=page_size, ks_ref=ks_ref,
                             vs_ref=vs_ref, window=window)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, npages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, d), lambda bi, pi, pt, ln: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((1, h), jnp.float32),
            pltpu.VMEM((1, h), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
        interpret=it,
        # a stable name: compiled text and profiler traces find the
        # kernel by it (the body is a closure called ``kernel``)
        name="paged_decode",
    )(page_table, seq_lens, q, *operands[1:])
