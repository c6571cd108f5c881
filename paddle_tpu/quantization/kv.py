"""Shared int8 KV-cache quantization math (serving path).

ONE home for the absmax quantize/dequantize arithmetic the quantized
KV serving path uses everywhere — the page-pool store helpers
(inference/paged_cache.py), the fused-dequant read kernel
(ops/paged_attention.py), and the A/B divergence harness
(tools/serve_bench.py --kv-ab) all import from here, and a future
weight-side int8 path is expected to as well. Keeping the rounding and
scale conventions in one module is what makes "bounded divergence"
a checkable contract instead of N slightly-different quantizers.

Conventions (symmetric absmax, per-page-per-KV-head):

- a scale ``s`` is the running ABSMAX of everything quantized against
  it (never below :data:`KV_SCALE_FLOOR` — dequant of a never-written
  page must be finite and ~0, not NaN);
- quantize: ``q = clip(round(x / s * KV_QMAX), -KV_QMAX, KV_QMAX)``
  (symmetric [-127, 127]: 0.0 round-trips exactly and the error bound
  is the same both sides);
- dequantize: ``x̂ = q * s / KV_QMAX`` — i.e. ``q *``
  :func:`dequant_scale` ``(s)``. With ``s >= absmax(x)`` the
  round-trip error is at most ``s / (2 * KV_QMAX)`` per element;
- page granularity: one f32 scale per (page, kv_head) — heads have
  very different dynamic ranges, and a page is the grain the pool
  copies/shares at, so scales ride the page table exactly like pages
  do (CoW copies them, warm prefix admissions gather through them).

RUNNING absmax (:func:`quant_store_rows`): decode appends tokens into
a page one step at a time, so a page's absmax can GROW after earlier
rows were already quantized. A growth event re-quantizes the page's
existing int8 rows by the old/new scale ratio (one extra rounding —
this is the "bounded, not bitwise" part of the int8 contract; the
per-page bound above still holds for the final scale).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["KV_DTYPES", "KV_QMAX", "KV_SCALE_FLOOR", "dequant_scale",
           "quantize_page", "dequantize_page", "quant_store_rows",
           "max_logit_divergence"]

# pool storage dtypes the paged engine accepts: "bf16" is the
# NON-quantized path (pools in the model's configured cache dtype —
# bf16 on production configs, f32 on the CPU-tiny test model) and
# stays bitwise-identical to pre-quantization behavior; "int8" stores
# pages int8 with per-page-per-head scales
KV_DTYPES = ("bf16", "int8")

KV_QMAX = 127.0          # symmetric int8 range [-127, 127]
KV_SCALE_FLOOR = 1e-8    # scales never 0: dequant stays finite


def dequant_scale(scale):
    """Per-element dequant multiplier for absmax scale(s) ``scale``:
    ``x̂ = q * dequant_scale(s)``. The fused read kernel applies this
    inside the attention program so the HBM read stays int8."""
    return scale / KV_QMAX


def quantize_page(page, scale):
    """Quantize one page's rows ``[..., H, D]`` (float) against
    per-head absmax ``scale [H]`` (or any shape broadcastable over the
    head axis at -2). Callers own ``scale >= absmax(page)`` — values
    above the scale saturate at ±KV_QMAX."""
    s = jnp.maximum(jnp.asarray(scale, jnp.float32), KV_SCALE_FLOOR)
    q = jnp.round(page.astype(jnp.float32)
                  / jnp.expand_dims(s, -1) * KV_QMAX)
    return jnp.clip(q, -KV_QMAX, KV_QMAX).astype(jnp.int8)


def dequantize_page(qpage, scale):
    """Inverse of :func:`quantize_page` (f32 result)."""
    s = jnp.asarray(scale, jnp.float32)
    return qpage.astype(jnp.float32) * jnp.expand_dims(
        dequant_scale(s), -1)


def quant_store_rows(pool, scales, pages, offs, rows):
    """Running-absmax int8 store of token rows into a paged pool —
    the ONE write primitive every quantized KV write path reduces to
    (single-token decode scatter, bucket-width prefill install, the
    masked warm-suffix scatter, and the W-wide speculative writes).

    pool: [P, ps, H, D] int8; scales: [P, H] f32 (running absmax per
    page per head); pages: [N] int32 target page per row, with the
    OUT-OF-RANGE sentinel ``P`` for rows to drop (the ``write_tokens``
    convention — dead slots, unmapped positions); offs: [N] int32 row
    offset within each page; rows: [N, H, D] float.

    Per call (pure, jittable — rides inside compiled programs):

    1. per-row per-head absmax joins the target pages' running scales
       via a scatter-max (rows landing in the same page compose
       correctly in one shot);
    2. pages whose scale GREW re-quantize their existing int8 rows by
       ``old/new`` (ratio 1 for untouched pages — exact no-op);
    3. the new rows store quantized against the updated scales.

    Writes never touch pages other than ``pages`` (dropped rows touch
    nothing), so shared/read-only pages are exactly as safe as with
    the unquantized scatter. Returns ``(pool, scales)``.
    """
    P = pool.shape[0]
    safe = jnp.minimum(pages, P - 1)        # gather-safe page index
    a = jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=-1)   # [N, H]
    old = jnp.maximum(scales, KV_SCALE_FLOOR)
    new_scales = jnp.maximum(old.at[pages].max(a, mode="drop"),
                             KV_SCALE_FLOOR)
    # re-quantize grown pages' existing rows (identical duplicate
    # writes when several rows hit one page — deterministic content).
    # Gated on ACTUAL growth: steady-state decode (absmax long
    # established, ratio 1 everywhere) must not pay the full-page
    # gather + rewrite per step — that write amplification sits on
    # the exact HBM-bound path int8 exists to relieve.
    r = (old / new_scales)[safe]                              # [N, H]

    def _requant(p):
        repaged = jnp.clip(
            jnp.round(p[safe].astype(jnp.float32)
                      * r[:, None, :, None]),
            -KV_QMAX, KV_QMAX).astype(jnp.int8)
        return p.at[pages].set(repaged, mode="drop")

    pool = jax.lax.cond(jnp.any(r < 1.0), _requant, lambda p: p,
                        pool)
    q = quantize_page(rows, new_scales[safe])
    pool = pool.at[pages, offs].set(q, mode="drop")
    return pool, new_scales


def max_logit_divergence(eng_a, eng_b, prompts, cfg=None,
                         steps: int = 16):
    """Plain-vs-quantized logit-divergence probe: admit the same
    prompts (greedy) into two IDLE continuous-batching engines, step
    them one decode token at a time, and before each step compare the
    next-token logits both engines would sample from. Returns
    ``{"max_logit_div", "mean_logit_div", "token_flips", "tokens"}``.

    This is the serving correctness bar for ``kv_dtype="int8"``:
    bounded logit divergence and (on the reference tiny model) ZERO
    token flips — the harness ``tools/serve_bench.py --kv-ab`` runs
    and records (``serve_kv_quant_max_logit_div``). Both engines are
    driven through their public admission/segment path, so the probe
    exercises the real store/read pipeline (quantize-on-store, fused
    dequant) — the extra logit read per step is an eager forward whose
    cache result is discarded.

    Greedy-intended. Once a slot's argmax FLIPS the two trajectories
    feed themselves different tokens, so later logit gaps there
    measure history divergence, not quantization error — a flipped
    slot is counted once and excluded from further comparison (the
    recorded divergence is always apples-to-apples on identical
    prefixes).
    """
    import numpy as np

    from ..inference.generation import GenerationConfig

    cfg = cfg or GenerationConfig(max_new_tokens=steps)
    for eng in (eng_a, eng_b):
        if eng._slot_req:
            raise RuntimeError(
                "max_logit_divergence needs idle engines")
    for p in prompts:
        eng_a.add_request(p, cfg)
        eng_b.add_request(p, cfg)
    max_div = 0.0
    sum_div = 0.0
    flips = 0
    tokens = 0
    n = 0
    dead = set()                      # slots whose trajectories split
    for _ in range(steps):
        if not (eng_a._slot_req and eng_b._slot_req):
            break
        la = eng_a._fwd_ragged(eng_a.params, eng_a.last[:, None],
                               eng_a.caches, eng_a.lens,
                               eng_a._active_mask())[0]
        lb = eng_b._fwd_ragged(eng_b.params, eng_b.last[:, None],
                               eng_b.caches, eng_b.lens,
                               eng_b._active_mask())[0]
        live = eng_a._active_mask() & eng_b._active_mask()
        la = np.asarray(la[:, 0], np.float32)
        lb = np.asarray(lb[:, 0], np.float32)
        for s in np.nonzero(live)[0]:
            if int(s) in dead:
                continue
            d = float(np.max(np.abs(la[s] - lb[s])))
            max_div = max(max_div, d)
            sum_div += d
            n += 1
            tokens += 1
            if int(la[s].argmax()) != int(lb[s].argmax()):
                flips += 1
                dead.add(int(s))
        eng_a.decode_segment(1, cfg)
        eng_b.decode_segment(1, cfg)
    # drain so the engines come back idle/leak-free for the caller
    while eng_a.decode_segment(4, cfg):
        pass
    while eng_b.decode_segment(4, cfg):
        pass
    eng_a.collect_finished()
    eng_b.collect_finished()
    return {"max_logit_div": max_div,
            "mean_logit_div": (sum_div / n if n else 0.0),
            "token_flips": flips, "tokens": tokens}
