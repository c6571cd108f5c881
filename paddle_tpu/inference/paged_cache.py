"""PagedKVCache — page-pool KV cache manager for continuous batching.

Reference analog: fused_multi_transformer's per-batch cache slabs
(fused_multi_transformer_op.cu.h) sized ``[max_batch, max_len, ...]``.
Here the cache is a SHARED pool of fixed-size pages plus a per-slot page
table (ops/paged_attention.py consumes both), so:

- HBM holds the tokens in flight (rounded up to pages), not
  ``max_batch * max_len`` — with skewed lengths the pool can be a
  fraction of the dense slabs;
- any free page serves any slot: no fragmentation, admission between
  decode segments allocates pages for at most one segment of growth;
- with ``prefix_cache=True`` full pages of prompt KV become
  CONTENT-ADDRESSABLE and shareable (vLLM-style automatic prefix
  caching, Kwon et al. SOSP'23): every page carries a REFCOUNT, full
  prompt blocks are indexed by a chain hash (hash of the block's
  tokens + the previous block's hash, token-verified on match so a
  hash collision can never alias KV), a new request maps already
  resident blocks read-only instead of re-prefilling them, and the
  first write into a shared page goes through host-side COPY-ON-WRITE
  (:meth:`PageAllocator.cow`). Fully released cached pages PARK in an
  LRU free-but-indexed state — still a cache hit, but reclaimed on
  demand when the pool needs pages — so cache capacity is whatever the
  pool is not actively using.

Split of responsibilities (mirrors the engine's host/device split):
page ALLOCATION is host-side Python between jitted segments (the free
list is plain state, like the engine's slot free list); page READS and
token WRITES are pure jittable functions of (pools, page_table) so they
ride inside compiled segment programs.

TENSOR PARALLELISM (engine ``tp_degree=k``, see ``inference/tp.py``)
is invisible here BY CONSTRUCTION: pools shard on the kv-HEAD axis
(axis 2; int8 scales on axis 1), never on the page axis, so a page id
means "the same row of every shard's local pool slice" — the page
table replicates, and every function in this module (write/scatter/
copy/gather and all PageAllocator bookkeeping: refcounts, chain
hashes, CoW, LRU parking, ``check()``) runs UNMODIFIED under GSPMD
with head-sharded operands. Do not add per-shard branches to this
file; anything that would need one belongs in the attention ops'
shard_map wrap instead.
"""
from __future__ import annotations

import functools
import hashlib
import heapq
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["PageAllocator", "PagedKVCache", "write_tokens", "write_rows",
           "gather_dense", "scatter_rows", "copy_page", "gather_pages",
           "install_page", "write_tokens_q", "scatter_rows_q",
           "copy_page_q", "gather_pages_q", "gather_dense_q",
           "install_page_q", "write_prompt", "install_prompt",
           "WindowedPageAllocator", "write_ring_tokens"]

# chain-hash root: the "parent" of a prompt's first block
_ROOT = b"\x00" * 16


def _chain_root(salt: bytes) -> bytes:
    """Chain root for a (possibly salted) prefix namespace. The LoRA
    serving path salts with the adapter id (``name@generation``) so
    one adapter's cached blocks can never parent-match — and therefore
    never alias — another's (or the base model's)."""
    if not salt:
        return _ROOT
    return hashlib.blake2b(salt, digest_size=16).digest()


def _block_hash(parent: bytes, tokens: np.ndarray) -> bytes:
    """Chain hash of one page_size-token prompt block: a function of
    the block's tokens AND the whole prefix before it (via ``parent``),
    so equal blocks at different prefixes never alias. 128-bit blake2b
    — and matches are token-verified anyway, so a collision can
    degrade a hit, never corrupt KV."""
    return hashlib.blake2b(
        parent + np.ascontiguousarray(tokens, np.int32).tobytes(),
        digest_size=16).digest()


@functools.partial(jax.jit, donate_argnums=(0, 1))
def write_tokens(k_pool, v_pool, page_table, slots, positions, k_new,
                 v_new):
    """Scatter one new token per row into the pools (pure, jittable; the
    pools are DONATED — per-step writes must not copy the dominant HBM
    allocation, so callers follow the
    ``cache.k, cache.v = write_tokens(cache.k, cache.v, ...)`` pattern
    and never reuse the old arrays).

    slots: [N] int32 page-table rows; positions: [N] int32 token index
    within each sequence; k_new/v_new: [N, H, D]. Returns updated pools.
    Writes whose position has NO mapped page (table entry -1 — caller
    forgot ``ensure``) are DROPPED, never wrapped onto another
    sequence's page (JAX scatter would wrap the -1 to the last pool
    row otherwise). That drop is SILENT by design (one compiled
    program), which is why the paged engine's per-gap ``debug_pages``
    check also asserts no slot's live length extends past its mapped
    pages — a forgotten ensure() or copy-on-write surfaces there
    loudly instead of as wrong tokens far downstream
    (:meth:`PageAllocator.check_coverage`).
    """
    return write_rows((k_pool, v_pool), page_table, slots, positions,
                      (k_new, v_new))


def write_rows(pools, page_table, slots, positions, news):
    """:func:`write_tokens` for any number of pools that share the table
    (a latent layer's one pool of rows, a K and a V pool): ``news[i]``
    [N, ...] goes into ``pools[i]`` [pages, page_size, ...] at the same
    (page, offset) of each. Returns the pools, a tuple."""
    ps = pools[0].shape[1]
    pages = page_table[slots, positions // ps]        # [N]
    # unmapped -> out-of-range sentinel; mode="drop" discards those rows
    pages = jnp.where(pages >= 0, pages, pools[0].shape[0])
    offs = positions % ps
    return tuple(p.at[pages, offs].set(n.astype(p.dtype), mode="drop")
                 for p, n in zip(pools, news))


@functools.partial(jax.jit, donate_argnums=(0, 1),
                   static_argnames=("width",))
def scatter_rows(k_pool, v_pool, page_table, slot, start, limit,
                 mini_k, mini_v, *, width):
    """Masked variant of :func:`write_tokens` for ONE slot: scatter
    ``width`` consecutive mini-cache rows starting at TRACED position
    ``start``, dropping rows outside ``[start, limit)``. The
    prefix-cache install path uses this to write exactly the UNCACHED
    suffix of a warm prompt — positions below the cached coverage must
    never be re-written (their pages are shared read-only), and the
    fixed-width garbage tail past the prompt must never land in a
    shared page either. Programs are keyed on the STATIC ``width``
    (one per prefill bucket) and the pool/mini shapes — never on the
    offsets, so admissions with different cached coverage share one
    compiled program."""
    L = mini_k.shape[1]
    ps = k_pool.shape[1]
    # clamp the slice base so [base, base+width) stays inside the mini
    # (rows pulled in below `start` by the clamp are masked back out)
    base = jnp.clip(start, 0, L - width)
    pos = base + jnp.arange(width, dtype=jnp.int32)
    valid = (pos >= start) & (pos < limit)
    pages = page_table[slot, pos // ps]                      # [width]
    pages = jnp.where(valid & (pages >= 0), pages, k_pool.shape[0])
    offs = pos % ps
    k_new = jax.lax.dynamic_slice_in_dim(mini_k[0], base, width, axis=0)
    v_new = jax.lax.dynamic_slice_in_dim(mini_v[0], base, width, axis=0)
    k_pool = k_pool.at[pages, offs].set(k_new.astype(k_pool.dtype),
                                        mode="drop")
    v_pool = v_pool.at[pages, offs].set(v_new.astype(v_pool.dtype),
                                        mode="drop")
    return k_pool, v_pool


@functools.partial(jax.jit, donate_argnums=(0, 1))
def copy_page(k_pool, v_pool, src, dst):
    """Copy one page's rows src -> dst inside the pools (the device
    half of copy-on-write; src/dst are traced scalars, so every CoW in
    the process shares ONE compiled program per pool shape)."""
    k_pool = k_pool.at[dst].set(k_pool[src])
    v_pool = v_pool.at[dst].set(v_pool[src])
    return k_pool, v_pool


@functools.partial(jax.jit, donate_argnums=(0, 1))
def install_page(k_pool, v_pool, dst, k_rows, v_rows):
    """Write one page's worth of host rows into the pools at traced
    ``dst`` (the device half of a KV-page IMPORT: the wire carried the
    page's raw rows, this lands them — a pure copy in the pool dtype,
    the import-side mirror of :func:`copy_page`). ``dst`` is a traced
    scalar so every imported page in the process shares ONE compiled
    program per pool shape."""
    k_pool = k_pool.at[dst].set(k_rows.astype(k_pool.dtype))
    v_pool = v_pool.at[dst].set(v_rows.astype(v_pool.dtype))
    return k_pool, v_pool


@functools.partial(jax.jit, donate_argnums=(3, 4))
def gather_pages(k_pool, v_pool, pages, mini_k, mini_v):
    """Gather whole pages from the pools into the head of a dense mini
    cache (``mini[:, :len(pages)*page_size] = pool[pages]``): the warm
    admission path materializes the CACHED prefix KV this way — a pure
    copy, bitwise-identical to what the original prefill wrote — so the
    uncached tail can prefill against it at a traced offset. Callers
    pass a FIXED-width page vector (a full page-table row, ``-1``
    padded — clamped to page 0 here) so every warm admission shares
    ONE compiled program per pool shape; the junk rows gathered for
    unmapped entries sit past the cached coverage, where the tail
    prefill overwrites them or the causal/length mask hides them."""
    idx = jnp.maximum(pages, 0)
    uk = k_pool[idx].reshape(1, -1, *k_pool.shape[2:])
    uv = v_pool[idx].reshape(1, -1, *v_pool.shape[2:])
    mini_k = jax.lax.dynamic_update_slice_in_dim(
        mini_k, uk.astype(mini_k.dtype), 0, axis=1)
    mini_v = jax.lax.dynamic_update_slice_in_dim(
        mini_v, uv.astype(mini_v.dtype), 0, axis=1)
    return mini_k, mini_v


@jax.jit
def gather_dense(pool, page_table, row):
    """Row's cache as a dense [max_pages*page_size, H, D] (testing/debug;
    the attention kernel never materializes this)."""
    return pool[jnp.maximum(page_table[row], 0)].reshape(
        -1, *pool.shape[2:])


# -- int8 pools (kv_dtype="int8"): quantize-on-store twins ------------------
#
# Same shapes, same page-table convention, same drop-sentinel semantics
# as the functions above, but the pools are int8 and every page carries
# a per-(page, kv_head) f32 running-absmax scale that rides the page
# table exactly like the pages do: writes quantize on store and update
# the scales (quantization.kv.quant_store_rows — growth re-quantizes
# the page's existing rows, which is the bounded-not-bitwise part of
# the int8 contract), copies/gathers carry scales so CoW and warm
# prefix-cache admission stay pure page copies, and the paged
# attention read dequantizes INSIDE the kernel so the HBM read is
# int8 (ops/paged_attention.py).

@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def write_tokens_q(k_pool, v_pool, k_scale, v_scale, page_table, slots,
                   positions, k_new, v_new, limit=None):
    """Quantizing :func:`write_tokens`: one new token per row into int8
    pools, scales updated by running absmax. Unmapped positions drop —
    rows, absmax contributions and all (a dropped write must not
    inflate another page's scale).

    ``limit`` (traced scalar, optional): rows at ``positions >= limit``
    drop too. The unquantized install scatters its bucket-width pad
    tail as ignorable garbage; quantized, those rows would RATCHET the
    headroom pages' running absmax and cost real precision — and
    freshly claimed pages' floor-reset scales already dequantize their
    stale rows to ~0, so dropping the tail is strictly better."""
    from ..quantization.kv import quant_store_rows

    ps = k_pool.shape[1]
    pages = page_table[slots, positions // ps]
    ok = pages >= 0
    if limit is not None:
        ok = ok & (positions < limit)
    pages = jnp.where(ok, pages, k_pool.shape[0])
    offs = positions % ps
    k_pool, k_scale = quant_store_rows(k_pool, k_scale, pages, offs,
                                       k_new)
    v_pool, v_scale = quant_store_rows(v_pool, v_scale, pages, offs,
                                       v_new)
    return k_pool, v_pool, k_scale, v_scale


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3),
                   static_argnames=("width",))
def scatter_rows_q(k_pool, v_pool, k_scale, v_scale, page_table, slot,
                   start, limit, mini_k, mini_v, *, width):
    """Quantizing :func:`scatter_rows`: the masked one-slot install of
    a warm admission's uncached suffix. Masked-out rows (below the
    cached coverage or past the prompt) drop entirely, so shared
    read-only pages keep both their rows AND their scales untouched."""
    from ..quantization.kv import quant_store_rows

    L = mini_k.shape[1]
    ps = k_pool.shape[1]
    base = jnp.clip(start, 0, L - width)
    pos = base + jnp.arange(width, dtype=jnp.int32)
    valid = (pos >= start) & (pos < limit)
    pages = page_table[slot, pos // ps]
    pages = jnp.where(valid & (pages >= 0), pages, k_pool.shape[0])
    offs = pos % ps
    k_new = jax.lax.dynamic_slice_in_dim(mini_k[0], base, width, axis=0)
    v_new = jax.lax.dynamic_slice_in_dim(mini_v[0], base, width, axis=0)
    k_pool, k_scale = quant_store_rows(k_pool, k_scale, pages, offs,
                                       k_new)
    v_pool, v_scale = quant_store_rows(v_pool, v_scale, pages, offs,
                                       v_new)
    return k_pool, v_pool, k_scale, v_scale


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def copy_page_q(k_pool, v_pool, k_scale, v_scale, src, dst):
    """Quantizing :func:`copy_page`: copy-on-write must carry the
    page's SCALES with its rows — int8 rows are meaningless under
    another page's scale, so a CoW that copied only rows would corrupt
    the copy (the allocator's ``check()`` fails loudly on exactly that
    under ``debug_pages=True``)."""
    k_pool = k_pool.at[dst].set(k_pool[src])
    v_pool = v_pool.at[dst].set(v_pool[src])
    k_scale = k_scale.at[dst].set(k_scale[src])
    v_scale = v_scale.at[dst].set(v_scale[src])
    return k_pool, v_pool, k_scale, v_scale


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def install_page_q(k_pool, v_pool, k_scale, v_scale, dst, k_rows,
                   v_rows, k_s, v_s):
    """Quantizing :func:`install_page`: an imported int8 page carries
    its per-(page, kv_head) scale rows on the wire exactly like
    :func:`copy_page_q` carries them across a CoW — int8 rows are
    meaningless under another page's scale, so the import must never
    re-quantize (that would be a format conversion, not a page copy)."""
    k_pool = k_pool.at[dst].set(k_rows.astype(k_pool.dtype))
    v_pool = v_pool.at[dst].set(v_rows.astype(v_pool.dtype))
    k_scale = k_scale.at[dst].set(k_s.astype(k_scale.dtype))
    v_scale = v_scale.at[dst].set(v_s.astype(v_scale.dtype))
    return k_pool, v_pool, k_scale, v_scale


@functools.partial(jax.jit, donate_argnums=(5, 6))
def gather_pages_q(k_pool, v_pool, k_scale, v_scale, pages, mini_k,
                   mini_v):
    """Quantizing :func:`gather_pages`: dequantize whole resident pages
    into the head of a float mini cache (warm prefix admission — the
    tail prefill attends over the DEQUANTIZED prefix KV, which is what
    the fused-dequant decode reads see too, so warm and cold
    admissions agree to quantization error, not to a format skew)."""
    from ..quantization.kv import dequantize_page

    idx = jnp.maximum(pages, 0)
    uk = dequantize_page(k_pool[idx], k_scale[idx][:, None, :])
    uv = dequantize_page(v_pool[idx], v_scale[idx][:, None, :])
    uk = uk.reshape(1, -1, *k_pool.shape[2:])
    uv = uv.reshape(1, -1, *v_pool.shape[2:])
    mini_k = jax.lax.dynamic_update_slice_in_dim(
        mini_k, uk.astype(mini_k.dtype), 0, axis=1)
    mini_v = jax.lax.dynamic_update_slice_in_dim(
        mini_v, uv.astype(mini_v.dtype), 0, axis=1)
    return mini_k, mini_v


@jax.jit
def gather_dense_q(pool, scales, page_table, row):
    """Dequantized :func:`gather_dense` (testing/debug)."""
    from ..quantization.kv import dequantize_page

    idx = jnp.maximum(page_table[row], 0)
    return dequantize_page(pool[idx], scales[idx][:, None, :]).reshape(
        -1, *pool.shape[2:])


def write_ring_tokens(k_pool, v_pool, ring_table, slots, positions, limit,
                      k_new, v_new):
    """:func:`write_tokens` for a WINDOW layer, whose table row is a ring
    of ``ring_table.shape[1]`` slots: position p lives at slot
    ``(p // page_size) % ring``. Of a prompt of ``limit`` tokens only the
    pages the ring can hold are written, the last ``ring`` of them: an
    earlier position would land on a later one's slot (and is out of
    every window to come). Positions at or past ``limit`` (a bucket's
    padding) DROP as well: in a ring they would overwrite live rows,
    where a full layer's land on headroom that decode rewrites."""
    ps, ring = k_pool.shape[1], ring_table.shape[1]
    pidx = positions // ps
    keep = (positions < limit) & (pidx > (limit - 1) // ps - ring)
    pages = ring_table[slots, pidx % ring]
    pages = jnp.where(keep & (pages >= 0), pages, k_pool.shape[0])
    offs = positions % ps
    k_pool = k_pool.at[pages, offs].set(k_new.astype(k_pool.dtype),
                                        mode="drop")
    v_pool = v_pool.at[pages, offs].set(v_new.astype(v_pool.dtype),
                                        mode="drop")
    return k_pool, v_pool


def write_row_state(pool, slot, mini):
    """A layer whose cache is a fixed-size state a ROW (a recurrent state,
    the last inputs of a convolution), not pages: ``pool``'s arrays are
    ``[rows, ...]`` and row ``slot`` takes the B=1 ``mini``'s, whole. The
    state a prefill hands out is the state after the prompt's last
    position already, so there is no ``limit`` to apply."""
    return tuple(p.at[slot].set(m[0].astype(p.dtype))
                 for p, m in zip(pool, mini))


def write_prompt(pools, page_table, slot, limit, mini, window_layers=None,
                 state_layers=None):
    """Scatter every row of a B=1 dense mini cache into ``slot``'s
    pages, all layers (pure: the body the paged engine's fused prefill
    program ends with, and of :func:`install_prompt`). Row ``i`` of the
    mini is position ``i``; the index arithmetic is
    :func:`write_tokens`'s own, so rows on unmapped pages drop. Int8
    pools (4-tuples) take :func:`write_tokens_q` with ``limit`` (the
    prompt length): the pad tail drops instead of ratcheting the
    headroom pages' scales. Float pools write it: it lands past the
    prompt in the slot's own pages, where the decode mask hides it and
    decode's writes overwrite it.

    ``window_layers`` (one bool a layer; static): ``page_table`` is then
    the pair ``(full, ring)`` of a :class:`WindowedPageAllocator`, and a
    layer marked True goes into its ring (:func:`write_ring_tokens`).
    ``state_layers`` (one bool a layer; static): a layer marked True keeps
    a state a row and no pages (:func:`write_row_state`); the others'
    entries are any number of pools (:func:`write_rows`)."""
    out = []
    # the bucket's width: of the first layer whose mini holds positions
    paged = [m for m, state in zip(mini, state_layers or ()) if not state]
    width = (paged or mini)[0][0].shape[1]
    slots = jnp.full((width,), slot, jnp.int32)
    pos = jnp.arange(width, dtype=jnp.int32)
    if state_layers is not None:
        for pool, entry, state in zip(pools, mini, state_layers):
            out.append(write_row_state(pool, slot, entry) if state
                       else write_rows(pool, page_table, slots, pos,
                                       tuple(m[0] for m in entry)))
        return out
    if window_layers is not None:
        full, ring = page_table
        for pool, (mk, mv), win in zip(pools, mini, window_layers):
            out.append(write_ring_tokens(*pool, ring, slots, pos, limit,
                                         mk[0], mv[0]) if win
                       else write_tokens(*pool, full, slots, pos,
                                         mk[0], mv[0]))
        return out
    for pool, (mk, mv) in zip(pools, mini):
        if len(pool) == 4:
            out.append(write_tokens_q(*pool, page_table, slots, pos,
                                      mk[0], mv[0], limit=limit))
        else:
            out.append(write_tokens(*pool, page_table, slots, pos,
                                    mk[0], mv[0]))
    return out


# one program for the whole layer list (chunked admissions, whose mini
# outlives a program); the pools are DONATED as in write_tokens
install_prompt = jax.jit(write_prompt, donate_argnums=(0,))


class PageAllocator:
    """Page-table + free-list bookkeeping, pool-agnostic: ONE allocator
    (one table) serves every layer's pools — the table maps logical
    positions to page ids, and all layers use the same ids.

    ``num_pages * page_size`` bounds the TOTAL tokens in flight across
    all slots; ``max_pages`` bounds one sequence's length. Allocation
    (``ensure``) and free (``free_slot``) are host-side between
    segments; reads/writes are the pure functions above.

    Every page carries a REFCOUNT (the number of slot-row appearances
    referencing it). Without ``prefix_cache`` every page's refcount is
    0 or 1 and the allocator behaves exactly like the pre-sharing one.
    With ``prefix_cache=True`` pages also move through a content index
    (see the module docstring): a page is in exactly ONE of three
    states — FREE (``_free`` heap), PARKED (refcount 0 but still
    indexed; an LRU of reclaimable cache hits), or REFERENCED
    (refcount >= 1, appearing in that many slot rows).
    """

    def __init__(self, num_pages: int, page_size: int, max_batch: int,
                 max_pages: int, debug: bool = False,
                 prefix_cache: bool = False, kv_dtype: str = "bf16"):
        from ..quantization.kv import KV_DTYPES

        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, got "
                f"{kv_dtype!r}")
        self.page_size = page_size
        self.num_pages = num_pages
        # int8 pools: host-side SCALE bookkeeping (the scale arrays
        # themselves live on device next to the pools). _scaled holds
        # the pages whose per-page scale rows are ESTABLISHED by
        # protocol — reset-fresh by the engine's claim flush, or copied
        # by a CoW — and check() enforces that every owned/parked page
        # is in it (a CoW that forgot to copy its scale fails loudly).
        # _fresh_scales queues newly claimed pages whose stale scale
        # rows the engine must reset to the floor before any write
        # (a previous owner's absmax must not ratchet a fresh page's
        # precision down); the engine drains it via take_fresh_scales.
        self.kv_dtype = kv_dtype
        self._scaled: set = set()
        self._fresh_scales: List[int] = []
        # HBM bytes the int8 pools avoided for pages claimed so far
        # (host-side total; the engine sets bytes_saved_per_page from
        # the real pool array sizes, scale overhead subtracted)
        self.bytes_saved_per_page = 0
        self.quant_bytes_saved = 0
        # debug=True runs the full check() invariant validator after
        # every mutating call (and the paged engine runs it once per
        # inter-segment gap): a reclaim bug fails LOUDLY at the faulty
        # op instead of silently scattering one request's KV into a
        # neighbour's pages. O(num_pages) per call — test/chaos tool,
        # not a production default.
        self.debug = bool(debug)
        self.prefix_cache = bool(prefix_cache)
        self.preemptions = 0          # lifetime count, host-side
        # HOST-side numpy, mutated in place: ensure() runs for active
        # slots in the latency-critical gap between jitted segments, and
        # per-page jnp .at[].set updates would each be a device dispatch.
        # Consumers convert once per segment (jnp.asarray). -1 =
        # unmapped; the kernel clamps skipped entries to page 0.
        # The mutable pool state below is OWNED by the engine-driving
        # (scheduler) thread — no lock by design: every mutation runs
        # between jitted segments, and the cross-thread readers
        # (Server.load()/healthz pressure) only take atomic int/len
        # snapshots. The guarded-by annotations document that ownership
        # for PT004 (documented, not lock-enforced — see MIGRATING.md).
        # guarded-by: scheduler-thread
        self.page_table = np.full((max_batch, max_pages), -1, np.int32)
        # guarded-by: scheduler-thread
        self._free: List[int] = list(range(num_pages))
        # guarded-by: scheduler-thread
        self._owned: Dict[int, List[int]] = {}
        self._ref: Dict[int, int] = {}         # pid -> refcount (>=1)
        self._shared = 0                       # pages with refcount > 1
        # prefix index (prefix_cache): chain hash <-> resident page
        self._index: Dict[bytes, int] = {}     # guarded-by: scheduler-thread
        self._hash_of: Dict[int, bytes] = {}   # pid -> hash
        self._tok_of: Dict[int, np.ndarray] = {}   # pid -> block tokens
        self._parent_of: Dict[int, bytes] = {}     # pid -> parent hash
        self._next: Dict[bytes, set] = {}      # parent hash -> {pid}
        # refcount-0 indexed pages, LRU order (oldest evicted first)
        # guarded-by: scheduler-thread
        self._parked: "OrderedDict[int, bytes]" = OrderedDict()
        # host-side prefix-cache accounting (monitor-independent)
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_tokens_saved = 0
        self.cow_copies = 0
        # pool label so several allocators (multi-model serving) publish
        # side by side instead of clobbering one process-global gauge
        from .. import monitor

        self.monitor_pool = monitor.instance_label("pool")
        self._publish_occupancy()

    # -- capacity accounting --------------------------------------------------
    @property
    def free_pages(self) -> int:
        """Strictly free pages (unindexed). Parked cache pages are NOT
        counted here — see :attr:`available_pages` for what an
        admission can actually claim."""
        return len(self._free)

    @property
    def cached_pages(self) -> int:
        """Refcount-0 pages parked in the prefix LRU: resident cache
        hits the pool reclaims on demand."""
        return len(self._parked)

    @property
    def available_pages(self) -> int:
        """Pages an allocation can claim right now: strictly free plus
        LRU-parked (a parked page is evicted from the index and reused
        the moment the pool needs it)."""
        return len(self._free) + len(self._parked)

    @property
    def shared_pages(self) -> int:
        """Pages referenced by MORE than one slot row right now — the
        sharing multiplier the prefix cache buys. Maintained
        incrementally on the 1<->2 refcount crossings (publish runs in
        the latency-critical gap; an O(pool) scan there would not);
        ``check()`` recomputes and cross-validates it."""
        return self._shared

    @staticmethod
    def _pages_gauge():
        from .. import monitor

        # kv_dtype label: at fixed HBM an int8 pool holds ~2x the
        # pages, so a pages number is only comparable WITH its storage
        # dtype attached
        return monitor.gauge("paddle_tpu_kv_pages",
                             "KV-cache page pool occupancy by state "
                             "and storage dtype",
                             ("pool", "state", "kv_dtype"))

    @property
    def used_pages(self) -> int:
        """Pages REFERENCED by at least one slot (parked cache pages
        are reclaimable, so they count as capacity, not use)."""
        return self.num_pages - len(self._free) - len(self._parked)

    @property
    def occupancy(self) -> float:
        """Fraction of the pool actually referenced right now (0.0 on
        an empty pool) — the number admission watermarks and the
        serving ``pressure`` surface read. LRU-parked cache pages are
        reclaimable and do not count."""
        if not self.num_pages:
            return 0.0
        return self.used_pages / self.num_pages

    @staticmethod
    def _occupancy_gauge():
        from .. import monitor

        return monitor.gauge("paddle_tpu_kv_page_occupancy_ratio",
                             "fraction of the KV page pool in use",
                             ("pool",))

    @staticmethod
    def _shared_gauge():
        from .. import monitor

        return monitor.gauge(
            "paddle_tpu_kv_shared_pages",
            "pages referenced by more than one slot (prefix-cache "
            "sharing)", ("pool",))

    def _publish_occupancy(self) -> None:
        """Push pool occupancy into the monitor (host-side mutations only
        happen in ensure/free_slot/map_shared/cow, so pushing there
        keeps the gauges exact with zero per-token cost)."""
        from .. import monitor

        if not monitor.enabled():
            return
        free = len(self._free)
        pages = self._pages_gauge()
        pages.labels(pool=self.monitor_pool, state="free",
                     kv_dtype=self.kv_dtype).set(free)
        pages.labels(pool=self.monitor_pool, state="used",
                     kv_dtype=self.kv_dtype).set(self.used_pages)
        if self.prefix_cache:
            pages.labels(pool=self.monitor_pool, state="cached",
                         kv_dtype=self.kv_dtype).set(len(self._parked))
            self._shared_gauge().labels(pool=self.monitor_pool).set(
                self.shared_pages)
        self._occupancy_gauge().labels(pool=self.monitor_pool).set(
            self.occupancy)

    @staticmethod
    def _preempt_counter():
        from .. import monitor

        return monitor.counter(
            "paddle_tpu_kv_preemptions_total",
            "requests preempted to relieve KV page-pool memory "
            "pressure, by reason (pressure = growth needed the pages; "
            "unsatisfiable = could not fit even alone)",
            ("pool", "reason"))

    @staticmethod
    def _prefix_hits_counter():
        from .. import monitor

        return monitor.counter(
            "paddle_tpu_kv_prefix_hits_total",
            "admissions that mapped at least one cached prompt-prefix "
            "page instead of re-prefilling it", ("pool",))

    @staticmethod
    def _prefix_saved_counter():
        from .. import monitor

        return monitor.counter(
            "paddle_tpu_kv_prefix_tokens_saved_total",
            "prompt tokens whose prefill compute was skipped because "
            "their KV was already resident (prefix-cache hits)",
            ("pool",))

    def count_preemption(self, reason: str = "pressure") -> None:
        """Record one preemption against this pool (the engine's
        ``preempt_request`` and the scheduler's admission-abort
        preemption path both land here, so ``preemptions`` is the
        pool-wide total whatever the victim's shape)."""
        self.preemptions += 1
        from .. import monitor

        if monitor.enabled():
            self._preempt_counter().labels(
                pool=self.monitor_pool, reason=reason).inc()

    @staticmethod
    def _quant_saved_counter():
        from .. import monitor

        return monitor.counter(
            "paddle_tpu_kv_quant_bytes_saved_total",
            "HBM bytes avoided by storing claimed KV pages int8 "
            "instead of the model cache dtype (per-page scale "
            "overhead already subtracted)", ("pool",))

    def _count_quant_claim(self) -> None:
        """One page claimed under int8 storage: account the HBM bytes
        the quantized layout avoided for it (host total + monitor
        counter; ``bytes_saved_per_page`` is 0 until the engine
        measures it from the real pools)."""
        if self.kv_dtype != "int8" or not self.bytes_saved_per_page:
            return
        self.quant_bytes_saved += self.bytes_saved_per_page
        from .. import monitor

        if monitor.enabled():
            self._quant_saved_counter().labels(
                pool=self.monitor_pool).inc(self.bytes_saved_per_page)

    def count_prefix_hit(self, tokens_saved: int) -> None:
        """Record one prefix-cache hit and the prompt tokens whose
        prefill compute it skipped (the engine calls this once per warm
        admission, AFTER the shared mapping succeeded)."""
        self.prefix_hits += 1
        self.prefix_tokens_saved += int(tokens_saved)
        from .. import tracing as _trace

        if _trace.enabled():
            _trace.event("prefix.hit", pool=self.monitor_pool,
                         tokens_saved=int(tokens_saved))
        from .. import monitor

        if monitor.enabled():
            self._prefix_hits_counter().labels(
                pool=self.monitor_pool).inc()
            if tokens_saved:
                self._prefix_saved_counter().labels(
                    pool=self.monitor_pool).inc(int(tokens_saved))

    # -- invariant validator --------------------------------------------------
    def check(self) -> None:
        """Invariant validator for the sharing era: every page must be
        in exactly ONE of free / parked / referenced, and the
        partition is by REFCOUNT ACCOUNTING — a page may appear in
        multiple slots' rows iff its refcount equals the appearance
        count; LRU-parked pages are indexed-but-reclaimable and appear
        in no row; every ``page_table`` row must mirror its slot's
        owned list exactly (owned prefix in order, ``-1`` tail); and
        the prefix index must be internally consistent. Raises
        RuntimeError on the first violation — called per-op under
        ``debug=True`` and once per gap by the paged engine, so a
        refcount leak, double free, or stale table entry fails loudly
        instead of corrupting a neighbour's KV."""
        owner = {}
        for pid in self._free:
            if pid in owner:
                raise RuntimeError(
                    f"page {pid} appears twice in the free list")
            owner[pid] = "free"
        for pid in self._parked:
            if pid in owner:
                raise RuntimeError(
                    f"page {pid} parked in the prefix LRU is also "
                    f"{owner[pid]}")
            if pid not in self._hash_of:
                raise RuntimeError(
                    f"page {pid} parked in the prefix LRU but not "
                    f"indexed")
            if self._ref.get(pid, 0):
                raise RuntimeError(
                    f"page {pid} parked with refcount "
                    f"{self._ref[pid]} (must be 0)")
            owner[pid] = "parked"
        appear: Dict[int, int] = {}
        for slot, pages in self._owned.items():
            for pid in pages:
                appear[pid] = appear.get(pid, 0) + 1
        for pid, n in appear.items():
            if pid in owner:
                raise RuntimeError(
                    f"page {pid} referenced by a slot is also "
                    f"{owner[pid]}")
            r = self._ref.get(pid, 0)
            if r != n:
                raise RuntimeError(
                    f"page {pid} appears in {n} slot row(s) but its "
                    f"refcount is {r} — sharing is legal only with a "
                    f"matching refcount (double-own / refcount leak)")
            owner[pid] = f"referenced(x{n})"
        for pid, r in self._ref.items():
            if appear.get(pid, 0) != r:
                raise RuntimeError(
                    f"page {pid} has refcount {r} but appears in "
                    f"{appear.get(pid, 0)} slot row(s) (refcount leak)")
        shared = sum(1 for r in self._ref.values() if r > 1)
        if shared != self._shared:
            raise RuntimeError(
                f"incremental shared-page counter {self._shared} "
                f"disagrees with the pool ({shared} pages with "
                f"refcount > 1)")
        if set(owner) != set(range(self.num_pages)):
            missing = sorted(set(range(self.num_pages)) - set(owner))
            foreign = sorted(set(owner) - set(range(self.num_pages)))
            raise RuntimeError(
                f"free ∪ parked ∪ referenced does not partition the "
                f"pool: missing {missing}, foreign {foreign}")
        for h, pid in self._index.items():
            if self._hash_of.get(pid) != h:
                raise RuntimeError(
                    f"prefix index maps {h.hex()} -> page {pid} but "
                    f"the page's hash is "
                    f"{self._hash_of.get(pid) and self._hash_of[pid].hex()}")
        for pid, h in self._hash_of.items():
            if self._index.get(h) != pid:
                raise RuntimeError(
                    f"page {pid} hashed but not (or differently) "
                    f"indexed")
            if pid not in self._tok_of or pid not in self._parent_of:
                raise RuntimeError(
                    f"indexed page {pid} missing token/parent records")
            if (self._ref.get(pid, 0) == 0
                    and pid not in self._parked):
                raise RuntimeError(
                    f"page {pid} indexed with refcount 0 but not "
                    f"parked (index leak)")
        for slot in range(self.page_table.shape[0]):
            owned = self._owned.get(slot, [])
            row = self.page_table[slot]
            if (list(row[:len(owned)]) != list(owned)
                    or not (row[len(owned):] == -1).all()):
                raise RuntimeError(
                    f"page_table row {slot} inconsistent with owned "
                    f"pages {owned}: {row.tolist()}")
        if self.kv_dtype == "int8":
            # scale accounting (int8 pools): every page whose KV is
            # readable — referenced by a slot or parked in the prefix
            # LRU — must have ESTABLISHED scale rows (reset-fresh at
            # claim, or copied by CoW); a page on the free heap must
            # not (freed pages reset their scale bookkeeping). The
            # canonical failure this catches: a copy-on-write that
            # copied the page's rows but forgot its scales.
            for pid, state in owner.items():
                if state == "free":
                    if pid in self._scaled:
                        raise RuntimeError(
                            f"free page {pid} still marked "
                            f"scale-established (freed pages must "
                            f"reset scale bookkeeping)")
                elif pid not in self._scaled:
                    raise RuntimeError(
                        f"{state} page {pid} has no established "
                        f"scales — a copy-on-write or install forgot "
                        f"to carry the per-page scale rows")
            for pid in self._fresh_scales:
                if owner.get(pid) == "free" or pid >= self.num_pages:
                    raise RuntimeError(
                        f"fresh-scale queue holds page {pid} which is "
                        f"{owner.get(pid, 'foreign')} — reset queue "
                        f"out of sync with claims")

    def check_coverage(self, slot: int, live_len: int,
                       write_ahead: int = 1) -> None:
        """Per-gap hardening against :func:`write_tokens`' silent drop
        (and a forgotten copy-on-write): ``slot``'s live length must
        not extend past its mapped pages, and the page the next decode
        write lands in must be PRIVATE (refcount 1, unindexed) —
        otherwise the write would either be dropped silently or mutate
        a shared/indexed page other requests read. The paged engine
        calls this for every live slot per gap under ``debug_pages``."""
        owned = self._owned.get(slot, [])
        if self.pages_for(live_len) > len(owned):
            raise RuntimeError(
                f"slot {slot}: live length {live_len} extends past its "
                f"{len(owned)} mapped page(s) — a KV write was (or "
                f"would be) silently dropped (forgot ensure()/CoW?)")
        max_len = self.page_size * self.page_table.shape[1]
        for pos in range(live_len, min(live_len + write_ahead, max_len)):
            # unmapped growth is the optimistic-mode grow/exhaustion
            # path's job, not a CoW bug — needs_cow returns False there
            if self.needs_cow(slot, pos):
                raise RuntimeError(
                    f"slot {slot}: next decode write at position {pos} "
                    f"lands in shared/indexed page "
                    f"{owned[pos // self.page_size]} — missing "
                    f"copy-on-write")
            if (self.kv_dtype == "int8"
                    and pos // self.page_size < len(owned)
                    and owned[pos // self.page_size] not in self._scaled):
                raise RuntimeError(
                    f"slot {slot}: imminent int8 write at position "
                    f"{pos} lands in page "
                    f"{owned[pos // self.page_size]} whose scales were "
                    f"never established (missing CoW scale copy or "
                    f"claim reset)")

    def check_scales(self, k_scale, v_scale) -> None:
        """Device-side half of the int8 scale invariants (the paged
        engine pulls one layer's scale arrays per gap under
        ``debug_pages=True``): every owned/parked/shared page's scales
        must be FINITE and positive — NaN/inf here means a quantized
        store was fed garbage and every future dequant of the page is
        poisoned."""
        ks = np.asarray(k_scale)
        vs = np.asarray(v_scale)
        live = sorted(set().union(
            *(set(p) for p in self._owned.values())) | set(self._parked))
        for pid in live:
            for name, arr in (("k", ks), ("v", vs)):
                row = arr[pid]
                if not np.all(np.isfinite(row)) or np.any(row <= 0):
                    raise RuntimeError(
                        f"page {pid}: non-finite/non-positive {name} "
                        f"scale row {row.tolist()} — quantized store "
                        f"fed garbage, dequant poisoned")

    def needs_cow(self, slot: int, pos: int) -> bool:
        """True when the page mapped at token position ``pos`` of
        ``slot`` is shared (refcount > 1) or indexed — a write there
        must go through :meth:`cow` first. False for private pages and
        unmapped positions (growth is ``ensure``'s job, not CoW's)."""
        owned = self._owned.get(slot, [])
        idx = pos // self.page_size
        if idx >= len(owned):
            return False
        pid = owned[idx]
        return self._ref.get(pid, 0) > 1 or pid in self._hash_of

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def covered_tokens(self, slot: int) -> int:
        """Token positions ``slot``'s mapped pages cover (writes past
        this are silently dropped by :func:`write_tokens` — the
        speculative verify step caps per-row acceptance here)."""
        return len(self._owned.get(slot, [])) * self.page_size

    def can_fit(self, slot: int, n_tokens: int) -> bool:
        have = len(self._owned.get(slot, []))
        return (self.pages_for(n_tokens) - have
                <= len(self._free) + len(self._parked))

    def _claim_page(self) -> int:
        """One fresh private page: from the free heap, else by evicting
        the LRU-oldest parked cache page (its index entries drop — a
        future lookup simply misses)."""
        if self._free:
            # heap pop (lowest page id first): ensure/free run in the
            # latency-critical inter-segment gap — a list pop(0) is O(n)
            # per page and the free() re-sort O(n log n) per retirement
            return self._note_claim(heapq.heappop(self._free))
        if self._parked:
            pid, _h = self._parked.popitem(last=False)
            self._unindex(pid)
            from .. import tracing as _trace

            if _trace.enabled():
                # LRU eviction of a parked cache page: future lookups
                # for its block will MISS — the event that explains a
                # hit-rate drop under pool pressure
                _trace.event("prefix.evict", pool=self.monitor_pool,
                             page=pid)
            return self._note_claim(pid)
        raise RuntimeError("page pool exhausted")

    def _note_claim(self, pid: int) -> int:
        """Scale bookkeeping for a freshly claimed page (int8): its
        device scale rows are a previous owner's leftovers, so it is
        UN-established (``_scaled`` drop) and queued for the engine's
        reset flush. ``ensure`` re-establishes it (the claim flush
        covers it); ``cow`` instead pulls it off the fresh queue and
        waits for :meth:`note_scale_copied`."""
        if self.kv_dtype == "int8":
            self._scaled.discard(pid)
            self._fresh_scales.append(pid)
            self._count_quant_claim()
        return pid

    def note_scale_copied(self, pid: int) -> None:
        """The engine copied scale rows onto ``pid`` on device
        (copy-on-write's second half): mark its scales established.
        Under ``debug=True`` this is also where the post-CoW invariant
        check runs — :meth:`cow` cannot check itself because its own
        return value IS the copy instruction."""
        if self.kv_dtype != "int8":
            return
        self._scaled.add(pid)
        if self.debug:
            self.check()

    def take_fresh_scales(self) -> List[int]:
        """Drain the queue of claimed-but-unreset pages (int8). The
        engine calls this at its write choke points and resets the
        listed pages' scale rows to the floor IN ONE fixed-shape masked
        program before any quantized write — never per page, never a
        shape-keyed recompile."""
        out, self._fresh_scales = self._fresh_scales, []
        return out

    def _unindex(self, pid: int) -> None:
        h = self._hash_of.pop(pid, None)
        if h is not None and self._index.get(h) == pid:
            del self._index[h]
        self._tok_of.pop(pid, None)
        parent = self._parent_of.pop(pid, None)
        if parent is not None:
            kids = self._next.get(parent)
            if kids is not None:
                kids.discard(pid)
                if not kids:
                    del self._next[parent]

    def _release_ref(self, pid: int) -> None:
        """Drop one reference; at zero the page parks (still indexed)
        or returns to the free heap."""
        n = self._ref.get(pid, 0) - 1
        if n == 1:
            self._shared -= 1
        if n > 0:
            self._ref[pid] = n
            return
        self._ref.pop(pid, None)
        if pid in self._hash_of:
            self._parked[pid] = self._hash_of[pid]
            self._parked.move_to_end(pid)
            from .. import tracing as _trace

            if _trace.enabled():
                _trace.event("prefix.park", pool=self.monitor_pool,
                             page=pid)
        else:
            # freed pages reset their scale bookkeeping: whatever
            # scale rows they carry belong to a dead owner (parked
            # pages keep theirs — their KV stays readable). A claim
            # freed before the engine's reset flush ran (aborted
            # admission) also leaves the fresh queue — it re-queues on
            # its next claim.
            self._scaled.discard(pid)
            if pid in self._fresh_scales:
                self._fresh_scales.remove(pid)
            heapq.heappush(self._free, pid)

    def ensure(self, slot: int, n_tokens: int) -> None:
        """Grow ``slot``'s mapping to cover ``n_tokens`` positions with
        PRIVATE pages (already-mapped pages — shared prefix ones
        included — count toward coverage). Raises RuntimeError when the
        pool is exhausted — the engine's admission control treats that
        like 'no free slot' and drains."""
        owned = self._owned.setdefault(slot, [])
        target = self.pages_for(n_tokens)
        if target > self.page_table.shape[1]:
            # an out-of-bounds table write would be silently dropped by
            # JAX while the page was still consumed — leak + wrong pages
            raise ValueError(
                f"slot {slot}: {n_tokens} tokens needs {target} pages > "
                f"max_pages={self.page_table.shape[1]} — grow max_pages "
                "(per-sequence length bound)")
        need = target - len(owned)
        if need <= 0:
            return
        if need > len(self._free) + len(self._parked):
            raise RuntimeError(
                f"page pool exhausted: slot {slot} needs {need} pages, "
                f"{len(self._free) + len(self._parked)} reclaimable — "
                "drain finished requests or grow num_pages")
        for _ in range(need):
            pid = self._claim_page()
            self._ref[pid] = 1
            if self.kv_dtype == "int8":
                # established by protocol: the claim sits on the fresh
                # queue and the engine's flush resets its scale rows
                # before any write lands in it
                self._scaled.add(pid)
            self.page_table[slot, len(owned)] = pid
            owned.append(pid)
        self._publish_occupancy()
        if self.debug:
            self.check()

    def free_slot(self, slot: int) -> None:
        """Release the slot's references (request retired): private
        pages return to the pool; shared pages survive for their other
        referents; indexed pages with no referent left park in the
        prefix LRU (still a cache hit, reclaimable on demand)."""
        for pid in self._owned.pop(slot, []):
            self._release_ref(pid)
        self.page_table[slot, :] = -1
        self._publish_occupancy()
        if self.debug:
            self.check()

    # -- prefix cache (content-addressable shared pages) ----------------------
    def lookup_prefix(self, tokens,
                      salt: bytes = b"") -> Tuple[List[int], int,
                                                  List[bytes]]:
        """Longest resident cached prefix of ``tokens`` (1-D int ids).

        Walks the full-block chain hash (token-verified per block),
        then tries ONE partial block: an indexed child of the last
        matched chain point whose leading tokens extend the match
        (divergent-suffix / mid-tail sharing — the page the caller must
        copy-on-write before its first write). Returns
        ``(pids, coverage, hashes)``: the resident pages to map
        read-only in order, the token coverage they provide
        (``<= len(tokens)``), and the full-block chain hashes (for
        registering the blocks the caller will prefill). Touches the
        LRU order of parked hits; claims no references —
        :meth:`map_shared` does.

        ``salt`` namespaces the whole chain: a non-empty salt replaces
        the chain ROOT, so hashes under different salts can never match
        each other's blocks. The LoRA serving path salts with the
        adapter's ``name@generation`` — cached KV is a function of the
        WEIGHTS that produced it, so a base-model block must never
        warm-hit an adapter's admission (or vice versa), and a reload
        of the same adapter name gets a fresh namespace. ``b""`` (the
        default) keeps the pre-LoRA root: base-model traffic on a
        LoRA-enabled engine shares KV with pre-LoRA admissions."""
        self.prefix_lookups += 1
        toks = np.ascontiguousarray(
            np.asarray(tokens).reshape(-1), np.int32)
        ps = self.page_size
        nfull = len(toks) // ps
        root = _chain_root(salt)
        hashes: List[bytes] = []
        h = root
        for b in range(nfull):
            h = _block_hash(h, toks[b * ps:(b + 1) * ps])
            hashes.append(h)
        pids: List[int] = []
        matched = 0
        while matched < nfull:
            pid = self._index.get(hashes[matched])
            if pid is None or not np.array_equal(
                    self._tok_of[pid], toks[matched * ps:
                                            (matched + 1) * ps]):
                break
            pids.append(pid)
            matched += 1
        cov = matched * ps
        rem = toks[cov:]
        if len(rem):
            parent = hashes[matched - 1] if matched else root
            best, best_m = None, 0
            for pid in self._next.get(parent, ()):
                bt = self._tok_of.get(pid)
                if bt is None:
                    continue
                lim = min(len(rem), ps)
                m = 0
                while m < lim and int(bt[m]) == int(rem[m]):
                    m += 1
                if m > best_m:
                    best, best_m = pid, m
            if best is not None and best_m > 0:
                pids.append(best)
                cov += best_m
        for pid in pids:
            if pid in self._parked:
                self._parked.move_to_end(pid)
        return pids, cov, hashes

    def map_shared(self, slot: int, pids: List[int]) -> None:
        """Map resident cached pages read-only into an EMPTY slot's
        table (refcount++ each; parked pages leave the LRU but stay
        indexed). Prefill and page claiming skip the coverage these
        provide; the first write into any of them must go through
        :meth:`cow`."""
        if self._owned.get(slot):
            raise RuntimeError(
                f"map_shared needs an empty slot, slot {slot} already "
                f"owns {len(self._owned[slot])} page(s)")
        if not pids:
            return
        owned = self._owned.setdefault(slot, [])
        for pid in pids:
            self._parked.pop(pid, None)
            n = self._ref.get(pid, 0) + 1
            if n == 2:
                self._shared += 1
            self._ref[pid] = n
            self.page_table[slot, len(owned)] = pid
            owned.append(pid)
        self._publish_occupancy()
        if self.debug:
            self.check()

    def cow(self, slot: int, page_idx: int) -> Tuple[int, int]:
        """Copy-on-write bookkeeping for ``slot``'s page at
        ``page_idx``: claim a fresh private page, swap the table entry,
        release the old reference (the shared original survives for its
        other referents / stays parked-indexed). Returns
        ``(old_pid, new_pid)`` — the caller owns the device-side row
        copy (:func:`copy_page`) BEFORE any write to the new page."""
        owned = self._owned[slot]
        old = owned[page_idx]
        new = self._claim_page()
        if self.kv_dtype == "int8":
            # NOT a fresh-reset page: the caller's device copy brings
            # the SOURCE page's scales over (copy_page_q), and
            # note_scale_copied marks it established. Until then the
            # page is deliberately un-established so a forgotten scale
            # copy fails the next check() loudly.
            self._fresh_scales.remove(new)
        self._ref[new] = 1
        owned[page_idx] = new
        self.page_table[slot, page_idx] = new
        self._release_ref(old)
        self.cow_copies += 1
        from .. import tracing as _trace

        if _trace.enabled():
            _trace.event("prefix.cow", pool=self.monitor_pool,
                         slot=slot, old=old, new=new)
        self._publish_occupancy()
        if self.debug and self.kv_dtype != "int8":
            # int8 defers to note_scale_copied: between this return and
            # the device copy the new page is legitimately in the
            # not-yet-scaled state check() exists to reject
            self.check()
        return old, new

    def register_blocks(self, slot: int, hashes: List[bytes], tokens,
                        start_block: int, end_block: int,
                        salt: bytes = b"") -> None:
        """Index ``slot``'s fully-written prompt blocks
        ``[start_block, end_block)`` under their chain hashes so future
        admissions can map them read-only. Only PRIVATE pages
        (refcount 1, unindexed) register; an already-taken hash keeps
        its first page (first writer wins — both hold identical KV).
        ``salt`` must match the ``lookup_prefix`` call that produced
        ``hashes`` — it only affects block 0's recorded parent (the
        salted chain root), which is what keeps partial-block child
        lookups inside one adapter's namespace."""
        if not self.prefix_cache:
            return
        owned = self._owned.get(slot, [])
        toks = np.ascontiguousarray(
            np.asarray(tokens).reshape(-1), np.int32)
        ps = self.page_size
        for b in range(start_block, end_block):
            if b >= len(owned) or b >= len(hashes):
                break
            pid = owned[b]
            h = hashes[b]
            if (h in self._index or pid in self._hash_of
                    or self._ref.get(pid, 0) != 1):
                continue
            self._index[h] = pid
            self._hash_of[pid] = h
            self._tok_of[pid] = toks[b * ps:(b + 1) * ps].copy()
            parent = hashes[b - 1] if b else _chain_root(salt)
            self._parent_of[pid] = parent
            self._next.setdefault(parent, set()).add(pid)
        if self.debug:
            self.check()

    def adopt_block(self, h: bytes, parent: bytes,
                    tokens) -> Optional[int]:
        """Adopt one IMPORTED full block into the prefix index as a
        PARKED page (refcount 0, LRU-reclaimable): the bookkeeping half
        of a cross-process KV-page import. The caller owns the device
        copy (:func:`install_page` / :func:`install_page_q` onto the
        returned pid, then — int8 — :meth:`note_scale_copied`, same
        deferred-check contract as CoW).

        Idempotent by content address: a hash already resident (token-
        verified or not — first writer wins, both hold identical KV)
        returns ``None`` and claims nothing, which is what makes a
        replayed/duplicated handoff a dedup no-op fleet-wide. ``parent``
        is the previous block's chain hash (or the salted chain root
        for block 0) — recording it keeps imported blocks reachable by
        the partial-block child walk exactly like locally written ones.
        Raises RuntimeError when the pool has no reclaimable page."""
        if not self.prefix_cache:
            raise RuntimeError(
                "adopt_block needs the prefix cache (an unindexed "
                "import could never be found again — enable "
                "cache_prefixes on the importing engine)")
        toks = np.ascontiguousarray(
            np.asarray(tokens).reshape(-1), np.int32)
        if len(toks) != self.page_size:
            raise ValueError(
                f"adopt_block takes exactly one FULL block "
                f"({self.page_size} tokens), got {len(toks)}")
        if h in self._index:
            return None
        pid = self._claim_page()
        if self.kv_dtype == "int8":
            # not a fresh-reset page: the wire carried the source
            # page's scale rows and install_page_q lands them; until
            # note_scale_copied the page is deliberately un-established
            # so a forgotten scale install fails check() loudly
            self._fresh_scales.remove(pid)
        self._index[h] = pid
        self._hash_of[pid] = h
        self._tok_of[pid] = toks.copy()
        self._parent_of[pid] = parent
        self._next.setdefault(parent, set()).add(pid)
        self._parked[pid] = h
        self._parked.move_to_end(pid)
        self._publish_occupancy()
        if self.debug and self.kv_dtype != "int8":
            self.check()
        return pid

    def clear_prefix_index(self) -> None:
        """Drop the whole content index and return parked pages to the
        free heap (engine ``reset_state``: the pools are rebuilt from
        zeros, so every cached block's KV is gone)."""
        for pid in list(self._parked):
            self._scaled.discard(pid)
            heapq.heappush(self._free, pid)
        self._parked.clear()
        self._index.clear()
        self._hash_of.clear()
        self._tok_of.clear()
        self._parent_of.clear()
        self._next.clear()
        self._publish_occupancy()
        if self.debug:
            self.check()

    def set_kv_dtype(self, kv_dtype: str) -> None:
        """Swap this pool's storage-dtype bookkeeping (the ENGINE owns
        rebuilding the device pools — only call through its idle-only
        ``set_kv_dtype``). Retires the old ``kv_dtype``-labeled gauge
        points so the pages gauge never exports two dtypes for one
        pool, and resets the scale bookkeeping (fresh pools start with
        floor scales, nothing established or pending)."""
        from ..quantization.kv import KV_DTYPES

        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, got "
                f"{kv_dtype!r}")
        if kv_dtype == self.kv_dtype:
            return
        self._retire_pages_gauge()
        self.kv_dtype = kv_dtype
        self._scaled.clear()
        self._fresh_scales.clear()
        self._publish_occupancy()

    def _retire_pages_gauge(self) -> None:
        try:
            from .. import monitor

            monitor.remove_series("paddle_tpu_kv_pages",
                                  pool=self.monitor_pool)
        except Exception:  # teardown-ordering safe
            pass

    def close(self) -> None:
        """Retire this allocator's monitor series (idempotent). Without
        this, a dropped engine's pool gauges would export their last
        values forever and label cardinality would grow per engine."""
        self._retire_pages_gauge()
        try:
            self._occupancy_gauge().remove(pool=self.monitor_pool)
        except Exception:  # teardown-ordering safe
            pass
        # open-ended label dimensions — retire by pool label
        try:
            from .. import monitor

            for name in ("paddle_tpu_kv_preemptions_total",
                         "paddle_tpu_kv_prefix_hits_total",
                         "paddle_tpu_kv_prefix_tokens_saved_total",
                         "paddle_tpu_kv_shared_pages",
                         "paddle_tpu_kv_quant_bytes_saved_total"):
                monitor.remove_series(name, pool=self.monitor_pool)
        except Exception:
            pass

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class WindowedPageAllocator(PageAllocator):
    """Two cache geometries in one manager, for a model whose layers are
    part full attention and part sliding window.

    This allocator IS the full layers' (every position of a row holds a
    page, as in :class:`PageAllocator`; the capacity, occupancy and
    pressure that admission and the serving surface read are the full
    layers', the pool that runs out first). ``self.window`` is a second
    allocator, over the window layers' own (smaller) pools: a row's table
    there is a RING of ``ring_pages`` slots, position p at slot
    ``(p // page_size) % ring_pages``, so a row never holds more than
    ``ring_pages`` pages in a window layer and holds none for positions
    that have left the window: their slot is the one being rewritten.
    The window pool is sized for every slot's whole ring
    (``max_batch * ring_pages``: a window layer's worst case is small),
    so it never refuses what the full pool grants. Claims and releases
    go to both; no prefix sharing, no int8 scales (the engine refuses
    both with such a model)."""

    def __init__(self, num_pages: int, page_size: int, max_batch: int,
                 max_pages: int, ring_pages: int, debug: bool = False):
        super().__init__(num_pages, page_size, max_batch, max_pages,
                         debug=debug)
        self.ring_pages = min(ring_pages, max_pages)
        self.window = PageAllocator(max_batch * self.ring_pages, page_size,
                                    max_batch, self.ring_pages, debug=debug)

    def _ring_tokens(self, n_tokens: int) -> int:
        return min(n_tokens, self.ring_pages * self.page_size)

    def tables(self):
        """The host tables ``(full, ring)``, as the device programs take
        them."""
        return self.page_table, self.window.page_table

    def held_pages(self, slot: int):
        """Pages ``slot`` holds in (a full layer, a window layer)."""
        return (len(self._owned.get(slot, ())),
                len(self.window._owned.get(slot, ())))

    def can_fit(self, slot: int, n_tokens: int) -> bool:
        return (super().can_fit(slot, n_tokens)
                and self.window.can_fit(slot, self._ring_tokens(n_tokens)))

    def ensure(self, slot: int, n_tokens: int) -> None:
        super().ensure(slot, n_tokens)
        self.window.ensure(slot, self._ring_tokens(n_tokens))

    def free_slot(self, slot: int) -> None:
        super().free_slot(slot)
        self.window.free_slot(slot)

    def check(self) -> None:
        super().check()
        self.window.check()

    def close(self) -> None:
        super().close()
        self.window.close()


class PagedKVCache(PageAllocator):
    """One layer's paged K/V pool + its allocator (single-layer
    convenience; multi-layer engines hold per-layer pools and ONE
    PageAllocator)."""

    def __init__(self, num_pages: int, page_size: int, num_heads: int,
                 head_dim: int, max_batch: int, max_pages: int,
                 dtype=jnp.bfloat16):
        super().__init__(num_pages, page_size, max_batch, max_pages)
        self.k = jnp.zeros((num_pages, page_size, num_heads, head_dim),
                           dtype)
        self.v = jnp.zeros_like(self.k)
