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

TPU-native mechanism: the kernel's work follows the pages live rows
hold. Page table and lengths ride Pallas SCALAR PREFETCH
(``pltpu.PrefetchScalarGridSpec``); the pools stay in HBM
(``memory_space=pl.ANY``). The grid runs over batch rows only, and a
row's step loops over the pages that row attends, ``[first, last)`` of
its table row (``last`` from its length, ``first`` from the window), in
compute blocks of several pages: each page is one copy the kernel issues
itself (``pltpu.make_async_copy`` from ``pool.at[table[row, j]]``, a
``[page_size, Hkv, D]`` slab that lies contiguous) into one of two VMEM
slots, the next block's copies, or the next live row's first, in flight
while this block is computed. A block's copies are straight-line code
with no bounds check, and every block copies as many pages (a row's
last block repeats its last page), so the core waits for a block with
one wait a stream. Trip counts come from the lengths, so one compiled
program serves every length; a table entry outside
``[first, last)`` is never looked at, a row of length 0 costs one grid
step and no copy. (The kernel this replaced had a ``(rows, table pages)``
grid with a block spec a page: it skipped the arithmetic of a page past a
row's length but still walked every block of the table, about 0.1 us
each, so its time followed the table's size and not what was live: 2.9 %
of its bandwidth roofline with a tenth of the table live, PERF.md PR 28.)
A block's arithmetic is the online-softmax recurrence of the ragged
``decode_mha`` (pallas_kernels.py) with both products on the matrix unit
(``_block_update``): bf16 operands as stored, everything else float32.

``PagedKVCache`` (inference/paged_cache.py) owns the pool + free-list;
this module is the pure compute.

QUANTIZED pools (``kv_dtype="int8"`` serving): pass the per-(page,
kv_head) absmax scale arrays and the kernel dequantizes AFTER the page
copy (``paddle_tpu.quantization.kv`` conventions): decode's HBM read
is half the bytes, which is the whole lever on bandwidth-bound decode.

``ops/pallas.py::paged_attention`` wraps the STOCK
``jax.experimental.pallas.ops.tpu.paged_attention`` kernel, whose copy
pattern this one shares; the pool layout (a page holds all KV heads),
the -1 table convention, the window and the fused dequant are this
module's own, and it also runs in interpret mode (CPU tests).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..quantization.kv import KV_QMAX as _KV_QMAX

__all__ = ["paged_decode_mha", "pages_copied"]


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def _pages_per_block(page_size, hkv):
    """Pages of one compute block: about 1024 (token, KV head) rows, so
    a block's scores are one ``[Hq, 1024]`` product whatever the head
    geometry."""
    return max(1, 1024 // (page_size * hkv))


def pages_copied(lens, page_size, hkv, window=None):
    """Pages one ``paged_decode_mha`` call copies for rows of lengths
    ``lens`` (host ints, as the kernel is handed them), each its K and its
    V: a row copies whole compute blocks of ``_pages_per_block`` pages over
    the pages it attends, and a row of length 0 copies none."""
    cpb = _pages_per_block(page_size, hkv)
    n = 0
    for ln in lens:
        first = 0 if window is None else max(ln - window, 0) // page_size
        n += -(-(-(-ln // page_size) - first) // cpb) * cpb
    return n


def _dot(a, b, contract):
    """``a x b`` on the matrix unit, accumulated in float32. bf16 operands
    multiply exactly; float32 operands are not rounded to bf16 (Mosaic
    refuses that precision on a bf16 product, so it is asked for only
    where it says something)."""
    exact = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=exact,
                               preferred_element_type=jnp.float32)


def _block_update(carry, q, k, v, pos0, lo, hi, scale):
    """One compute block of the online-softmax recurrence, both products
    on the matrix unit over the whole block.

    ``q``: ``[Hq, D]``; ``k``/``v``: ``[T, Hkv, D]``, the block's pages as
    they lie, token ``t`` at position ``pos0 + t``; the row attends
    positions ``[lo, hi)``. K and V are read as ``[T x Hkv, D]`` and the
    scores are ``q x k^T`` for EVERY (query head, KV head) pair, of which
    the mask keeps a query head's own KV head: ``Hkv`` times the products
    on a unit that would idle, and no page is transposed or repeated. A
    masked ``p`` is 0, so ``p x v`` sums a query head's own KV head only;
    that product is float32 by float32 (``v`` widened), so ``p`` is not
    rounded. ``carry``: running maximum and sum ``[Hq, 1]`` and the
    accumulator ``[Hq, D]``."""
    m_prev, l_prev, acc = carry
    (hq, d), (t, hkv) = q.shape, k.shape[:2]
    if q.dtype != k.dtype:        # a dequantized pool, a float32 query
        q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, t * hkv), 1)
    pos = pos0 + col // hkv
    own = jax.lax.broadcasted_iota(jnp.int32, (hq, 1), 0) // (hq // hkv)
    mask = (col % hkv == own) & (pos >= lo) & (pos < hi)     # [Hq, N]
    k, v = k.reshape(t * hkv, d), v.reshape(t * hkv, d)
    s = jnp.where(mask, _dot(q, k, ((1,), (1,))) * scale, -1e30)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc = acc * alpha + _dot(p, v.astype(jnp.float32), ((1,), (0,)))
    return m_new, l_new, acc


def _paged_decode_kernel(pt_ref, len_ref, q_ref, k_hbm, v_hbm, *rest,
                         scale, window, quant, cols, unroll):
    """One batch row a grid step; the body walks the pages the row
    attends, ``[first, last)`` of its table row, in compute blocks, and
    issues every page copy itself.

    ``pt_ref`` (the table flattened, row ``r`` at ``r * cols``, every
    entry inside the pool) and ``len_ref`` are scalar-prefetched, the
    pools stay in HBM (an int8 pool's scales arrive as the row's block,
    gathered by its table). A block's pages land in one of two VMEM
    slots; while a block is computed the copies of the row's next block,
    or of the next live row's first, are in flight (``state`` carries the
    slot across grid steps, which run in order on one core; the first
    live row's first block is issued at row 0). A row of length 0 issues
    no copy and writes zeros. Every block copies ``cpb`` pages a stream,
    issued with no branch on the length and waited for with one wait a
    stream: a column past ``last`` in a row's last block copies the row's
    last page again, whose finite values the positions' mask leaves
    out."""
    if quant:
        ks_ref, vs_ref, o_ref, k_buf, v_buf, sem, state = rest
    else:
        o_ref, k_buf, v_buf, sem, state = rest
    streams = ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1))
    row, nrows = pl.program_id(0), pl.num_programs(0)
    _, cpb, ps, hkv, d = k_buf.shape
    hq = q_ref.shape[1]

    def length(r):
        # a length past the table's end attends what the table holds
        return jnp.minimum(len_ref[r], cols * ps)

    def page_range(r):
        ln = length(r)
        first = 0 if window is None else jnp.maximum(ln - window, 0) // ps
        return first, (ln + ps - 1) // ps

    def next_live(r):
        """The first row at or after ``r`` that holds a page (``nrows``
        if none does)."""
        return jax.lax.while_loop(
            lambda i: (i < nrows) & (len_ref[jnp.minimum(i, nrows - 1)] == 0),
            lambda i: i + 1, r)

    def start_block(r, blk, slot):
        """Start the copies of block ``blk`` of row ``r``: ``cpb`` pages a
        stream, with no branch on the row's length (straight-line code
        where ``unroll``). A column past the row's last page copies that
        page again: the positions' mask leaves it out, and every block
        signals its slot's semaphores the bytes of a whole slot."""
        first, last = page_range(r)
        col0 = first + blk * cpb

        def copy(i, _):
            page = pt_ref[r * cols + jnp.minimum(col0 + i, last - 1)]
            for src, dst, s in streams:
                pltpu.make_async_copy(src.at[page], dst.at[slot, i],
                                      sem.at[s, slot]).start()

        jax.lax.fori_loop(0, cpb, copy, None, unroll=unroll)

    def wait_block(slot):
        """One wait a stream for a block's ``cpb`` copies: a descriptor
        the size of the whole slot (a DMA semaphore counts bytes), so no
        table read and no descriptor a page."""
        for _, dst, s in streams:
            pltpu.make_async_copy(dst.at[slot], dst.at[slot],
                                  sem.at[s, slot]).wait()

    @pl.when(row == 0)
    def _init():
        state[0] = 0           # the slot the next block lands in
        r0 = next_live(0)

        @pl.when(r0 < nrows)
        def _():
            start_block(r0, 0, 0)

    ln = length(row)
    first, last = page_range(row)
    nblk = (last - first + cpb - 1) // cpb
    slot0 = state[0]
    nxt = next_live(row + 1)
    lo = 0 if window is None else jnp.maximum(ln - window, 0)
    q = q_ref[0]

    def block(blk, carry):
        slot = (slot0 + blk) % 2

        # in flight while this block is computed: the row's next block,
        # or after its last the first block of the next live row
        ends = blk + 1 == nblk
        nr = jnp.where(ends, nxt, row)

        @pl.when(nr < nrows)
        def _prefetch():
            start_block(nr, jnp.where(ends, 0, blk + 1), 1 - slot)

        wait_block(slot)
        k, v = k_buf[slot], v_buf[slot]                # [cpb, ps, Hkv, D]
        if quant:
            # fused dequant (quantization.kv conventions), after the copy:
            # the HBM read stays int8
            at = pl.ds(first + blk * cpb, cpb)
            ksc = ks_ref[0, at, :] * (1.0 / _KV_QMAX)      # [cpb, Hkv]
            vsc = vs_ref[0, at, :] * (1.0 / _KV_QMAX)
            k = k.astype(jnp.float32) * ksc[:, None, :, None]
            v = v.astype(jnp.float32) * vsc[:, None, :, None]
        return _block_update(
            carry, q, k.reshape(cpb * ps, hkv, d), v.reshape(cpb * ps, hkv, d),
            (first + blk * cpb) * ps, lo, ln, scale)

    init = (jnp.full((hq, 1), -1e30, jnp.float32),
            jnp.zeros((hq, 1), jnp.float32),
            jnp.zeros((hq, d), jnp.float32))
    _, l_fin, acc = jax.lax.fori_loop(0, nblk, block, init)
    state[0] = (slot0 + nblk) % 2
    o_ref[0] = (acc / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)


def _paged_decode_ref(q, k_pool, v_pool, page_table, seq_lens,
                      k_scale=None, v_scale=None, window=None):
    """Pure-jnp reference the tests hold the kernel to: gather each
    row's pages dense and run a masked softmax — numerically equivalent
    to the kernel (same f32 math, plain instead of online softmax), NOT
    byte-identical, and it materializes [B, max_pages*page_size] KV.
    Quantized pools dequant here with the same ``quantization.kv``
    conventions the fused kernel uses; ``window`` as the kernel's. No
    serving path calls it."""
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
    pos = jnp.arange(L, dtype=jnp.int32)[None, :, None]
    mask = pos < seq_lens[:, None, None]
    if window is not None:
        mask &= pos >= seq_lens[:, None, None] - window
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
        only the entries of the pages a row attends are read (the rest
        may be -1, or anything)
    seq_lens: [B] int32 valid lengths (the new token's k/v must already
        be written at position seq_lens-1 via PagedKVCache.write_tokens).
        A row of length 0 (a dead slot: the models pass 0 for
        ``live=False``) costs no page and returns zeros
    k_scale/v_scale: [num_pages, Hkv] f32 per-page-per-head absmax
        scales for INT8 pools (quantization.kv conventions) — pass both
        or neither. Dequant fuses into the kernel after the page copy,
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
    quant = k_scale is not None
    cpb = _pages_per_block(page_size, hkv)
    it = _interpret() if interpret is None else interpret

    row = pl.BlockSpec((1, h, d), lambda bi, pt, ln: (bi, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = [q, k_pool, v_pool]
    scratch = [pltpu.VMEM((2, cpb) + k_pool.shape[1:], k_pool.dtype),
               pltpu.VMEM((2, cpb) + v_pool.shape[1:], v_pool.dtype)]
    in_specs = [row, hbm, hbm]
    # every entry inside the pool: an unmapped (-1) entry inside a row's
    # range is the caller's fault, and reads page 0, not outside the pool
    table = jnp.clip(page_table, 0, k_pool.shape[0] - 1)
    if quant:
        # a row's scales, gathered by its table outside the kernel (32
        # bytes a page: too narrow a slab for a copy of its own) and
        # padded by a block, so the last block's slice stays inside
        ids = jnp.pad(table, ((0, 0), (0, cpb)))
        operands += [k_scale[ids], v_scale[ids]]
        in_specs += [pl.BlockSpec((1, ids.shape[1], hkv),
                                  lambda bi, pt, ln: (bi, 0, 0))] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=in_specs,
        out_specs=row,
        scratch_shapes=scratch + [pltpu.SemaphoreType.DMA((2, 2)),
                                  pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=1.0 / math.sqrt(d),
                          window=window, quant=quant,
                          cols=page_table.shape[1],
                          # straight-line copies for the chip's compiler;
                          # the interpreter would compile each copy of an
                          # unrolled block as code of its own (a minute at
                          # the 256 pages a block of tiny test models)
                          unroll=not it),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
        # rows in order on one core: the slots and ``state`` carry a
        # prefetch from one row to the next. No bounds checks: every
        # copy's page is an entry of the clipped table, and every table
        # read is at a column below the row's ``last`` (the length is
        # clipped to the table), so each address is inside its buffer;
        # the checks cost two compare chains a copy on the core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True),
        interpret=it,
        # a stable name: compiled text and profiler traces find the
        # kernel by it
        name="paged_decode",
    )(table.reshape(-1), seq_lens, *operands)
