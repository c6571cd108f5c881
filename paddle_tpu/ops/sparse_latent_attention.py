"""Learned sparse attention over a latent paged cache: the indexer's
scores, the exact top-k selection, and the attention over the chosen rows.

A latent-attention layer (``models/deepseek_v32.py``) caches ONE row a
token, shared by every head: the compressed KV ``c`` and the rotated key
part ``k_rope`` side by side (``kv_lora_rank + qk_rope_head_dim`` values),
and beside it ONE indexer key ``kI``. A query attends only the
``index_topk`` positions whose indexer score

    I[t, u] = sum_j w[t, j] * relu(qI[t, j] . kI[u])

is largest. Five pieces, each pure and jittable:

- :func:`dsa_index_scores` (decode): a Pallas kernel that scores one query
  a row against every indexer key the row's pages hold. Its copy pattern
  is ``paged_decode``'s (``ops/paged_attention.py``): table and lengths ride
  scalar prefetch, the pool stays in HBM, a grid step is a row, and the
  body walks the row's pages in compute blocks of 1024 positions, each page
  one copy the kernel issues itself into one of two VMEM slots, the next
  block's copies in flight while this one is computed. A table entry past a
  row's length is never looked at; a row of length 0 costs no copy.
- :func:`index_scores` (prefill): the same scores for a block of queries
  against keys that lie side by side, as one product.
- :func:`top_k_mask` (prefill): the mask of each row's k largest scores,
  exact, by bisection on the scores' bit patterns (32 counting passes; no
  sort of a [queries, keys] block).
- :func:`selected_attention` (prefill): a Pallas kernel, online softmax
  over blocks of keys under the selection's mask, for heads whose keys are
  wider than their values. Its grid walks the block pairs at or under the
  diagonal and no other; a block of queries past the prompt's last position
  is neither computed nor fetched. A grid step turns its block of the mask
  into an additive bias once for all its heads (the causal compare in the
  diagonal block alone) and walks each head's queries in sub-tiles, whose
  softmax statistics lie along the lanes (one reduction across lanes a
  sub-tile); the scale rides in the queries.
- :func:`sparse_latent_decode` (decode): XLA's gather of the chosen rows by
  their flat index in the pool, and the attention over them in latent
  space (the up-projection absorbed into the query and the output).
- :func:`paged_latent_decode` (decode, no selection): a Pallas kernel on
  ``dsa_index_scores``'s copy pattern that attends one absorbed query a
  row, all heads against the one shared row of each position, over EVERY
  position of the row's pages: an online softmax over the compute blocks,
  the context out in latent space.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _dot, _interpret

__all__ = ["dsa_index_scores", "index_scores", "top_k_mask",
           "sparse_latent_decode", "paged_latent_decode",
           "selected_attention"]

BLOCK_POSITIONS = 1024      # positions of one compute block of the kernel
NEG = -1e30


def index_scores(q, w, keys):
    """The scores of a block of queries against keys that lie side by side
    (prefill): q [Q, H, D] in the keys' dtype, w [Q, H] float32, keys
    [U, D] -> [Q, U] float32, ``sum_h w[t, h] * relu(q[t, h] . keys[u])``."""
    n, h, d = q.shape
    s = jnp.matmul(q.reshape(n * h, d), keys.T,
                   preferred_element_type=jnp.float32).reshape(n, h, -1)
    return jnp.sum(jnp.maximum(s, 0.0) * w[:, :, None], axis=1)


def _page_walk(pt_ref, len_ref, k_hbm, k_buf, sem, state):
    """The copy pattern of this module's paged kernels, for the grid step's
    row: its pages in compute blocks of ``cpb`` pages, each page one copy
    into one of two VMEM slots, the next block's copies (or the next live
    row's first block's) in flight while this one is computed. A table
    entry past a row's length is never looked at; a row of length 0 costs
    no copy. ``state[0]`` carries the slot across grid steps
    (``paged_decode``'s). Returns ``run(compute)``, which walks the row's
    blocks and calls ``compute(blk, slot)`` once a block's pages are in
    ``k_buf[slot]``."""
    row, nrows = pl.program_id(0), pl.num_programs(0)
    _, cpb, ps, _ = k_buf.shape

    def pages_of(r):
        ln = jnp.minimum(len_ref[r], pt_ref.shape[1] * ps)
        return (ln + ps - 1) // ps

    def next_live(r):
        return jax.lax.while_loop(
            lambda i: (i < nrows) & (len_ref[jnp.minimum(i, nrows - 1)] == 0),
            lambda i: i + 1, r)

    def block_copies(r, blk, slot, do):
        col0 = blk * cpb

        def page_copy(i, _):
            page = jnp.maximum(pt_ref[r, col0 + i], 0)
            do(pltpu.make_async_copy(k_hbm.at[page], k_buf.at[slot, i],
                                     sem.at[slot]))

        jax.lax.fori_loop(0, jnp.minimum(pages_of(r) - col0, cpb),
                          page_copy, None)

    @pl.when(row == 0)
    def _init():
        state[0] = 0
        k_buf[...] = jnp.zeros_like(k_buf)
        r0 = next_live(0)

        @pl.when(r0 < nrows)
        def _():
            block_copies(r0, 0, 0, lambda c: c.start())

    nblk = (pages_of(row) + cpb - 1) // cpb
    slot0 = state[0]
    nxt = next_live(row + 1)

    def run(compute):
        def block(blk, _):
            slot = (slot0 + blk) % 2
            ends = blk + 1 == nblk
            nr = jnp.where(ends, nxt, row)

            @pl.when(nr < nrows)
            def _prefetch():
                block_copies(nr, jnp.where(ends, 0, blk + 1), 1 - slot,
                             lambda c: c.start())

            block_copies(row, blk, slot, lambda c: c.wait())
            compute(blk, slot)
            return None

        jax.lax.fori_loop(0, nblk, block, None)
        state[0] = (slot0 + nblk) % 2

    return run


def _index_scores_kernel(pt_ref, len_ref, q_ref, w_ref, k_hbm, o_ref, k_buf,
                         sem, state):
    """One batch row a grid step: relu(q k^T) weighted over the heads, for
    every position of the row's pages, block by block (see the module's
    docstring; the slot carried across grid steps is ``paged_decode``'s)."""
    _, cpb, ps, d = k_buf.shape
    run = _page_walk(pt_ref, len_ref, k_hbm, k_buf, sem, state)
    q, w = q_ref[0], w_ref[0]                      # [H, D], [H, 1]
    o_ref[0] = jnp.full(o_ref.shape[1:], NEG, jnp.float32)

    def compute(blk, slot):
        k = k_buf[slot].reshape(cpb * ps, d)
        if q.dtype != k.dtype:
            k = k.astype(jnp.float32)
        s = jnp.maximum(_dot(q, k, ((1,), (1,))), 0.0)     # [H, T]
        o_ref[0, pl.ds(blk, 1), :] = jnp.sum(w * s, axis=0, keepdims=True)

    run(compute)


def _latent_decode_kernel(pt_ref, len_ref, q_ref, k_hbm, o_ref, k_buf, sem,
                          state, m_sc, l_sc, acc_sc, *, scale):
    """One batch row a grid step: the absorbed query's heads [H, W] against
    every position's row [W] (``c | k_rope | zeros``; the query's zeros
    meet the row's), an online softmax over the compute blocks, the
    probabilities times the rows' first ``C`` lanes (``c``) into the
    context [H, C]."""
    _, cpb, ps, w = k_buf.shape
    latent = acc_sc.shape[1]
    run = _page_walk(pt_ref, len_ref, k_hbm, k_buf, sem, state)
    n = len_ref[pl.program_id(0)]
    q = q_ref[0]                                   # [H, W]
    m_sc[...] = jnp.full(m_sc.shape, NEG, jnp.float32)
    l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
    acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def compute(blk, slot):
        k = k_buf[slot].reshape(cpb * ps, w)
        s = _dot(q, k, ((1,), (1,))) * scale               # [H, T]
        pos = blk * (cpb * ps) + jax.lax.broadcasted_iota(
            jnp.int32, (1, cpb * ps), 1)
        s = jnp.where(pos < n, s, NEG)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + _dot(
            p.astype(k.dtype), k[:, :latent], ((1,), (0,)))
        m_sc[...] = m_new

    run(compute)
    o_ref[0] = acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dsa_index_scores(q, w, k_pool, page_table, seq_lens, interpret=None):
    """Indexer scores of one query a row over the row's paged keys.

    q: [B, H, D] in the pool's dtype, w: [B, H] float32,
    k_pool: [num_pages, page_size, D], page_table: [B, max_pages] int32,
    seq_lens: [B] int32 (0 = a dead row). Returns [B, max_pages *
    page_size] float32: ``sum_h w[b, h] * relu(q[b, h] . k[u])`` at the
    positions ``u`` under row ``b``'s length. What lies past the length is
    finite and means nothing (-1e30 in the blocks the row never reached,
    a stale page's score inside its last block): mask by the length."""
    b, h, d = q.shape
    ps = k_pool.shape[1]
    cpb = max(1, BLOCK_POSITIONS // ps)
    maxp = page_table.shape[1]
    nblk = -(-maxp // cpb)
    it = _interpret() if interpret is None else interpret
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, d), lambda bi, pt, ln: (bi, 0, 0)),
                  pl.BlockSpec((1, h, 1), lambda bi, pt, ln: (bi, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, nblk, cpb * ps),
                               lambda bi, pt, ln: (bi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, cpb, ps, d), k_pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    out = pl.pallas_call(
        _index_scores_kernel,
        out_shape=jax.ShapeDtypeStruct((b, nblk, cpb * ps), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=it,
        name="dsa_index_scores",
    )(page_table, seq_lens, q, w.astype(jnp.float32)[:, :, None], k_pool)
    return out.reshape(b, nblk * cpb * ps)[:, :maxp * ps]


def top_k_mask(scores, k):
    """The mask of each row's ``k`` largest values, exact: scores [..., N]
    float32 (-inf allowed), k [...] int32 >= 1. A value's bit pattern is
    mapped to an unsigned integer of the same order and the k-th largest is
    built bit by bit from the top: 32 passes that count, no sort. Ties at
    the k-th value are all kept; where a row has fewer than ``k`` values
    above -inf the mask reaches into the -inf entries (mask those apart)."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.int32)
    ordered = jnp.where(bits < 0, bits ^ jnp.int32(0x7fffffff), bits)
    u = jax.lax.bitcast_convert_type(ordered, jnp.uint32) \
        ^ jnp.uint32(0x80000000)
    k = jnp.asarray(k, jnp.int32)

    def bit(i, kth):
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        count = jnp.sum((u >= cand[..., None]).astype(jnp.int32), axis=-1)
        return jnp.where(count >= k, cand, kth)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    return u >= kth[..., None]


def sparse_latent_decode(q_lat, q_rope, lat_pool, chosen, ok, scale):
    """Attention of one query a row over its chosen cache rows, in latent
    space. q_lat [B, H, C] (the query's nope part through the absorbed
    up-projection), q_rope [B, H, R], lat_pool [num_pages, page_size,
    W >= C + R] (``c | k_rope`` a token, then zeros), chosen [B, K] int32:
    the chosen positions' rows of the pool (``page x page_size + offset``),
    ok [B, K] bool (False: the entry is no position of the row). Returns
    the context in latent space, [B, H, C] float32: the caller applies the
    value half of the up-projection."""
    c = q_lat.shape[-1]
    kv = jnp.take(lat_pool.reshape(-1, lat_pool.shape[-1]), chosen, axis=0)
    pad = jnp.zeros(q_lat.shape[:2] + (kv.shape[-1] - c - q_rope.shape[-1],),
                    q_lat.dtype)
    q = jnp.concatenate([q_lat, q_rope, pad], axis=-1).astype(kv.dtype)
    s = jnp.einsum("bhd,bkd->bhk", q, kv,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(ok[:, None, :], s, NEG), axis=-1)
    return jnp.einsum("bhk,bkc->bhc", p.astype(kv.dtype), kv[..., :c],
                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_latent_decode(q_lat, q_rope, lat_pool, page_table, seq_lens, scale,
                        interpret=None):
    """Attention of one query a row over EVERY position of its paged cache
    rows, in latent space (a Pallas kernel: see the module's docstring).

    q_lat [B, H, C] (the query's nope part through the absorbed
    up-projection), q_rope [B, H, R], lat_pool [num_pages, page_size,
    W >= C + R] (``c | k_rope`` a token, then zeros), page_table
    [B, max_pages] int32, seq_lens [B] int32 (0 = a dead row: no page is
    copied and its context is zeros); ``scale`` the softmax's (static). A
    compute block is ``BLOCK_POSITIONS`` positions. The query is rounded to
    the pool's dtype, both
    products accumulate in float32 and the softmax is float32, ``p``
    rounded to the pool's dtype before ``p x c``. Returns the context in
    latent space, [B, H, C] float32: the caller applies the value half of
    the up-projection."""
    b, h, c = q_lat.shape
    ps, w = lat_pool.shape[1], lat_pool.shape[2]
    cpb = max(1, BLOCK_POSITIONS // ps)
    pad = jnp.zeros((b, h, w - c - q_rope.shape[-1]), lat_pool.dtype)
    q = jnp.concatenate([q_lat.astype(lat_pool.dtype),
                         q_rope.astype(lat_pool.dtype), pad], axis=-1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, w), lambda bi, pt, ln: (bi, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, h, c), lambda bi, pt, ln: (bi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, cpb, ps, w), lat_pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, c), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_latent_decode_kernel, scale=float(scale)),
        out_shape=jax.ShapeDtypeStruct((b, h, c), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret() if interpret is None else interpret,
        name="paged_latent_decode",
    )(page_table, seq_lens, q, lat_pool)


def _tiling(s, h):
    """(positions a block, queries a sub-tile, heads a grid step) of
    :func:`selected_attention` for ``h`` heads of ``s`` positions. The
    pipeline fetches square blocks of queries by keys; the body walks a
    block's queries in sub-tiles, so that a sub-tile of a diagonal block
    stops at its own last key."""
    blk = next(b for b in (1024, 512, s) if s % b == 0)
    sub = 256 if blk % 256 == 0 else blk
    hb = 4 if h % 4 == 0 else 1
    return blk, sub, hb


def _selected_attention_kernel(last_ref, qb_ref, kb_ref, q_ref, k_ref, v_ref,
                               *rest, masked, sub):
    """One (head block, block pair) grid step of the online softmax. The
    grid's second axis walks the (query block, key block) pairs at or under
    the diagonal, key blocks innermost; ``qb_ref`` / ``kb_ref`` say which
    pair a step is. ``m``/``l``/``acc`` live in scratch across the key
    blocks of a query block.

    The step's mask becomes an additive float32 ``bias`` (0 / NEG) once,
    for all its heads: the selection's int8 entries, and the causal compare
    in the diagonal block only. A head's queries are then walked in
    sub-tiles of ``sub`` rows, each one chain: product, bias, max, exp,
    sum, cast, product; a sub-tile of the diagonal block takes the keys up
    to its own last row and no more. ``m`` and ``l`` are ``w`` lanes wide:
    a row's maximum repeated over the lanes, and its sum as ``w`` partial
    sums (keys ``c, c + w, ...`` in lane ``c``) that meet in ``_finalize``:
    a sub-tile costs one reduction across lanes, the maximum's."""
    if masked:
        mask_ref, o_ref, m_sc, l_sc, acc_sc, bias_sc = rest
    else:
        o_ref, m_sc, l_sc, acc_sc, bias_sc = rest
    hb, blk, _ = q_ref.shape
    dv, w = v_ref.shape[2], m_sc.shape[2]
    t = pl.program_id(1)
    i, j = qb_ref[t], kb_ref[t]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def causal():
        return (jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
                <= jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0))

    def over(x, n):                 # [rows, w] -> [rows, n]
        return x if w == 1 or n == w else pltpu.repeat(x, n // w, axis=1)

    def attend(biased, diagonal):
        def head(h, _):
            for r0 in range(0, blk, sub):
                rows = pl.ds(r0, sub)
                n = r0 + sub if diagonal else blk       # keys it can see
                sc = _dot(q_ref[h, rows, :], k_ref[h, :n, :], ((1,), (1,)))
                if biased:
                    sc = sc + bias_sc[rows, :n]
                m_prev = m_sc[h, rows, :]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(sc, axis=1, keepdims=True))
                # a row that has attended nothing yet: exp(NEG - 0) = 0
                m_use = jnp.where(m_new > 0.5 * NEG, m_new, 0.0)
                p = jnp.exp(sc - over(m_use, n))
                alpha = jnp.exp(m_prev - m_use)
                if w == 1:
                    folded = jnp.sum(p, axis=1, keepdims=True)
                else:
                    folded = p[:, :w]
                    for c in range(w, n, w):
                        folded = folded + p[:, c:c + w]
                l_sc[h, rows, :] = l_sc[h, rows, :] * alpha + folded
                acc_sc[h, rows, :] = (
                    acc_sc[h, rows, :] * (over(alpha, dv) if dv % w == 0
                                          else alpha[:, :1])
                    + _dot(p.astype(v_ref.dtype), v_ref[h, :n, :],
                           ((1,), (0,))))
                m_sc[h, rows, :] = m_new
            return None

        jax.lax.fori_loop(0, hb, head, None)

    live = i * blk <= last_ref[0]

    @pl.when(live & (i == j))
    def _diagonal():
        keep = causal()
        if masked:
            keep = keep & (mask_ref[...].astype(jnp.int32) != 0)
        bias_sc[...] = jnp.where(keep, 0.0, NEG)
        attend(True, True)

    @pl.when(live & (i != j))
    def _under():
        if masked:
            bias_sc[...] = jnp.where(mask_ref[...].astype(jnp.int32) != 0,
                                     0.0, NEG)
        attend(masked, False)

    @pl.when(i == j)
    def _finalize():
        def head(h, _):
            l = jnp.sum(l_sc[h], axis=1, keepdims=True)
            o_ref[h] = (acc_sc[h] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
            return None

        jax.lax.fori_loop(0, hb, head, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selected_attention(q, k, v, mask, last_idx, interpret=None):
    """Causal attention under a selection's mask, keys wider than values.

    q/k: [H, S, Dk], the softmax's scale already in ``q``; v: [H, S, Dv];
    mask: [S, S] int8 (0 = not attended; the causal mask is applied
    besides) or None (causal alone); last_idx: int32 scalar, the last query
    whose output matters: the blocks of queries after its own give zeros,
    and nothing is computed or fetched for them. Returns [H, S, Dv] in v's
    dtype. A query that attends nothing gives zeros. The products take the
    operands as they are (``Dk`` need not fill whole lanes: nothing is
    padded or copied on the way in) and accumulate in float32; the softmax
    is float32, ``p`` rounded to v's dtype before ``p x v``."""
    h, s, dk = q.shape
    dv = v.shape[-1]
    blk, sub, hb = _tiling(s, h)
    w = 128 if sub % 128 == 0 else 1    # lanes of the softmax's statistics
    masked = mask is not None
    it = _interpret() if interpret is None else interpret
    # the block pairs at or under the diagonal, key blocks innermost
    qb, kb = np.tril_indices(s // blk)

    # a block of queries past ``last`` repeats the blocks fetched last, the
    # diagonal pair of the last live block, so the pipeline copies nothing
    def qi(t, last, qb):
        return jnp.minimum(qb[t], last[0] // blk)

    def ki(t, last, qb, kb):
        return jnp.where(qb[t] * blk <= last[0], kb[t], last[0] // blk)

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda g, t, last, qb, kb: index(
            g, qi(t, last, qb), ki(t, last, qb, kb)))

    qq = spec((hb, blk, dk), lambda g, i, j: (g, i, 0))
    kk = spec((hb, blk, dk), lambda g, i, j: (g, j, 0))
    vv = spec((hb, blk, dv), lambda g, i, j: (g, j, 0))
    in_specs, operands = [qq, kk, vv], [q, k, v]
    if masked:
        in_specs.append(spec((blk, blk), lambda g, i, j: (i, j)))
        operands.append(mask)
    return pl.pallas_call(
        functools.partial(_selected_attention_kernel, masked=masked, sub=sub),
        out_shape=jax.ShapeDtypeStruct((h, s, dv), v.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(h // hb, len(qb)),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((hb, blk, dv),
                                   lambda g, t, last, qb, kb: (g, qb[t], 0)),
            scratch_shapes=[pltpu.VMEM((hb, blk, w), jnp.float32),
                            pltpu.VMEM((hb, blk, w), jnp.float32),
                            pltpu.VMEM((hb, blk, dv), jnp.float32),
                            pltpu.VMEM((blk, blk), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=it,
        name="mla_selected_prefill",
    )(jnp.asarray(last_idx, jnp.int32).reshape(1), qb.astype(np.int32),
      kb.astype(np.int32), *operands)
