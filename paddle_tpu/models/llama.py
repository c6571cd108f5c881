"""Llama-family causal LM, TPU-native.

Architecture: RMSNorm / RoPE / GQA attention / SwiGLU — the Llama-2 recipe,
built from the tensor-parallel layer stack
(distributed/fleet/layers/mpu/mp_layers.py) so the SAME module runs
single-chip, TP-sharded under GSPMD (weights carry PartitionSpecs), or
inside shard_map. Reference analogs: the reference's fused transformer
blocks (fluid/operators/fused/fused_multi_transformer_op.cu) define the
fusion targets; attention runs through nn.functional.flash_attention which
routes to the Pallas kernel on TPU.

Sharding plan (scaling-book "2D finalized" layout):
- embed/lm_head:  vocab on mp                       P('mp', None)
- q/k/v/gate/up:  out-dim on mp (column parallel)   P(None, 'mp')
- o/down:         in-dim on mp (row parallel)       P('mp', None)
- activations:    batch on dp(+sharding), heads/ffn on mp via constraints
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.autograd import apply_op
from ..core.tensor import Tensor
from ..distributed._spmd import P, constraint, set_pspec
from ..distributed.fleet.layers.mpu import (ColumnParallelLinear,
                                            ParallelCrossEntropy,
                                            RowParallelLinear,
                                            VocabParallelEmbedding)
from ..nn import functional as F
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "llama_config"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None  # GQA; None → MHA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: str = "float32"
    # remat policy for the decoder stack ("none" | "full")
    recompute: str = "none"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads


_PRESETS = {
    # name: (hidden, inter, layers, heads, kv_heads, vocab)
    "tiny":  (64, 176, 2, 4, 4, 256),        # CI / dryrun
    "350m":  (1024, 2816, 24, 16, 16, 32000),
    "1b3":   (2048, 5504, 24, 16, 16, 32000),
    "7b":    (4096, 11008, 32, 32, 32, 32000),
    "13b":   (5120, 13824, 40, 40, 40, 32000),
    "65b":   (8192, 22016, 80, 64, 64, 32000),  # Llama-2-65B: MHA (kv=64)
}


def llama_config(preset: str = "tiny", **overrides) -> LlamaConfig:
    h, i, l, a, kv, v = _PRESETS[preset]
    cfg = LlamaConfig(hidden_size=h, intermediate_size=i, num_hidden_layers=l,
                      num_attention_heads=a, num_key_value_heads=kv,
                      vocab_size=v)
    for k, val in overrides.items():
        setattr(cfg, k, val)
    return cfg


def _rope_cos_sin(seq_len: int, head_dim: int, theta: float, dtype):
    """Precompute RoPE cos/sin tables [seq, head_dim//2]."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def _lora_add(x, y, lora, name):
    """Add the per-row LoRA delta for target projection ``name``:
    ``y + (x @ A[idx]^T) @ B[idx]^T`` with each ROW's factors gathered
    by its ``adapter_idx`` — the S-LoRA batched-adapter shape, per-slot
    weights as a device-vector gather inside the one compiled program
    (the PR 2 invariant extended from sampling params to weights).

    ``lora`` is ``(bank, idx)``: ``bank`` maps target names to THIS
    layer's stacked factors ``A [K+1, r, d_in]`` / ``B [K+1, d_out, r]``
    (index 0 = base model, rows pinned to zeros — the gathered delta is
    exactly 0.0, so base rows stay bitwise what a LoRA-free forward
    produces); ``idx`` is the per-row ``[B]`` int32 adapter index.
    Works for any sequence width (prefill S, decode 1, spec-verify W).
    The LoRA scaling (alpha/r) is folded into B at install time."""
    if lora is None:
        return y
    bank, idx = lora
    ab = bank.get(name)
    if ab is None:
        return y
    A, B = ab

    def add(xv, yv, Av, Bv, iv):
        a_sel = jnp.take(Av, iv, axis=0)      # [B, r, d_in]
        b_sel = jnp.take(Bv, iv, axis=0)      # [B, d_out, r]
        t = jnp.einsum("bsd,brd->bsr", xv, a_sel)
        return yv + jnp.einsum("bsr,bor->bso", t,
                               b_sel).astype(yv.dtype)

    return apply_op(add, x, y, A, B, idx, op_name=f"lora_{name}")


def _lora_layer(lora, i):
    """Layer ``i``'s slice of the engine-level LoRA inputs: the bank
    holds per-layer factor stacks ``[L, K+1, r, d]``; each decoder
    layer gathers from its own ``[K+1, r, d]`` slice (``i`` is a trace
    constant, so the slice costs nothing)."""
    if lora is None:
        return None
    bank, idx = lora
    return {t: (A[i], B[i]) for t, (A, B) in bank.items()}, idx


def apply_rotary_emb(x, cos, sin):
    """x: [B, S, H, D]; rotate-half RoPE (reference analog:
    fused_rope_kernel.cu:87 fused_rotary_position_embedding).

    ``cos``/``sin`` are either the shared position tables ``[S, d2]`` or
    already broadcast to x's rank (the ragged-decode path passes per-ROW
    angles ``[B, 1, 1, d2]``).

    On TPU the shared-table form routes to the Pallas fused_rope kernel
    (unless x is laid out over a GSPMD mesh, which cannot partition it):
    the half-split of the 128-lane head_dim is VMEM-local there, where the
    jnp slice+concat forms cost two HBM relayouts. The per-row form stays
    in jnp (one token per row)."""
    from ..ops.pallas import _kernel_routable

    shared = cos.ndim == 2
    if shared and x.shape[-1] % 2 == 0 and _kernel_routable(x):
        from ..ops.pallas_kernels import fused_rope

        return fused_rope(x, cos, sin)
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[None, :, None, :] if shared else cos
    s = sin[None, :, None, :] if shared else sin
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        h = config.hidden_size
        hd = config.head_dim
        self.num_heads = config.num_attention_heads
        self.kv_heads = config.kv_heads
        self.q_proj = ColumnParallelLinear(h, self.num_heads * hd,
                                           has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(h, self.kv_heads * hd,
                                           has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(h, self.kv_heads * hd,
                                           has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(self.num_heads * hd, h,
                                        has_bias=False, input_is_parallel=True)

    def _qkv_lora(self, x, lora):
        """Shared q/k/v projection + per-row LoRA delta (every cached/
        decode path's head; ``lora=None`` is exactly the pre-LoRA
        projection)."""
        q = _lora_add(x, self.q_proj(x), lora, "q")
        k = _lora_add(x, self.k_proj(x), lora, "k")
        v = _lora_add(x, self.v_proj(x), lora, "v")
        return q, k, v

    def _o_lora(self, ctx, lora):
        return _lora_add(ctx, self.o_proj(ctx), lora, "o")

    def forward_with_cache(self, x, cos_full, sin_full, cache, pos,
                           lora=None, tp=None):
        """Serving path: attend over a preallocated KV cache.

        x: [B, S, h] (S>1 = prefill, S==1 = decode); cache: (k, v) jnp
        arrays [B, S_max, Hkv, hd]; pos: int32 scalar — tokens already in
        the cache. Returns (out, new_cache). The decode step is the
        masked_multihead_attention analog (reference
        fused_multi_transformer_op.cu.h:745); prefill uses the flash path.
        ``lora`` (here and on the paged decode forwards below) is the
        per-row batched-adapter input — see :func:`_lora_add`;
        ``tp`` is the serving engine's tensor-parallel handle
        ``(mesh, axis)`` (see ``inference/tp.py``) — threaded into the
        attention ops' shard_map wrap so each mesh shard runs the
        kernel on its local head slice (None = single-device trace,
        byte-identical to pre-TP).
        """
        b, s = x.shape[0], x.shape[1]
        hd = self.config.head_dim
        q, k, v = self._qkv_lora(x, lora)
        k_cache, v_cache = cache

        def attend(qv, kv, vv, kc, vc):
            # rope at absolute positions [pos, pos+s)
            cs = jax.lax.dynamic_slice_in_dim(cos_full, pos, s, axis=0)
            sn = jax.lax.dynamic_slice_in_dim(sin_full, pos, s, axis=0)
            qh = apply_rotary_emb(qv.reshape(b, s, self.num_heads, hd), cs, sn)
            kh = apply_rotary_emb(kv.reshape(b, s, self.kv_heads, hd), cs, sn)
            vh = vv.reshape(b, s, self.kv_heads, hd)
            kc = jax.lax.dynamic_update_slice_in_dim(
                kc, kh.astype(kc.dtype), pos, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(
                vc, vh.astype(vc.dtype), pos, axis=1)
            lens = jnp.full((b,), pos + s, jnp.int32)
            if s == 1:
                from ..ops._decode import gqa_decode_attention

                ctx = gqa_decode_attention(
                    qh[:, 0], kc, vc, lens,
                    tp=tp)[:, None]                       # [B, 1, Hq, hd]
            elif isinstance(pos, int) and pos == 0:
                # fresh prefill (the generation engine's case): plain causal
                # flash over just the prompt — attending the full
                # preallocated cache width would cost max_len/s extra work
                from ..ops.pallas import flash_attention as _flash

                ctx = _flash(qh, kh, vh, causal=True, tp=tp)
            else:
                # chunked prefill / spec-verify at a traced offset: the
                # online-softmax prefix attention shares its reduction
                # structure with the one-shot flash fallback, so chunked
                # and padded-bucket prefill reproduce single-shot prefill
                # bitwise (ops/pallas.prefix_chunk_attention)
                from ..ops.pallas import prefix_chunk_attention

                ctx = prefix_chunk_attention(qh, kc, vc, pos, tp=tp)
            return ctx.reshape(b, s, self.num_heads * hd), kc, vc

        ctx, kc, vc = apply_op(attend, q, k, v, k_cache, v_cache,
                               op_name="cached_attention")
        val = lambda t: t.value if isinstance(t, Tensor) else t  # noqa: E731
        return self._o_lora(ctx, lora), (val(kc), val(vc))

    def forward_decode_spec_paged(self, x, cos_full, sin_full, cache,
                                  page_table, lens, live, lora=None,
                                  tp=None):
        """Speculative VERIFY step over the page pool: W query
        positions per row at per-row offsets (x: [B, W, h]; position i
        of row b sits at absolute position ``lens[b] + i``).

        All W tokens' K/V are written at their per-row positions first;
        writes to dead rows, unmapped pages, or positions past the
        table width are DROPPED (the ``write_tokens`` sentinel
        convention — never clamped: a clamp would overwrite the last
        valid cell with draft garbage), so the step stays one compiled
        program and a draft window reaching past a slot's grown
        coverage degrades to fewer accepted tokens instead of
        corrupting a neighbour's page. Then each query position runs
        the SAME ``paged_decode_mha`` call the one-token step uses,
        with its own length ``lens + i + 1`` — position i attends
        exactly the history a sequential decode would have, so when
        the input tokens match the greedy continuation the logits are
        BITWISE what :meth:`forward_decode_paged` would have produced
        one token at a time. Rejected drafts leave stale KV past the
        accepted length; every read is length-masked and later writes
        overwrite it. The third result is the int8 path's window-write
        rows (None on float pools)."""
        b, w = x.shape[0], x.shape[1]
        hd = self.config.head_dim
        q, k, v = self._qkv_lora(x, lora)
        quant = len(cache) == 4   # (k, v, k_scale, v_scale) int8 pools

        def _prep(qv, kv, vv, kp):
            ps = kp.shape[1]
            max_len = page_table.shape[1] * ps
            pos = lens[:, None] + jnp.arange(w, dtype=jnp.int32)[None]
            idx = jnp.minimum(pos, max_len - 1)
            c = cos_full[idx][:, :, None, :]
            sn = sin_full[idx][:, :, None, :]
            qh = apply_rotary_emb(qv.reshape(b, w, self.num_heads, hd),
                                  c, sn)
            kh = apply_rotary_emb(kv.reshape(b, w, self.kv_heads, hd),
                                  c, sn)
            vh = vv.reshape(b, w, self.kv_heads, hd)
            ar = jnp.arange(b)
            page = page_table[ar[:, None], idx // ps]       # [B, W]
            ok = live[:, None] & (page >= 0) & (pos < max_len)
            page = jnp.where(ok, page, kp.shape[0])
            return qh, kh, vh, page, idx % ps

        def attend(qv, kv, vv, kp, vp):
            qh, kh, vh, page, offs = _prep(qv, kv, vv, kp)
            kp = kp.at[page, offs].set(kh.astype(kp.dtype),
                                       mode="drop")
            vp = vp.at[page, offs].set(vh.astype(vp.dtype),
                                       mode="drop")
            from ..ops.paged_attention import paged_decode_mha

            # a dead row attends nothing: length 0 costs the kernel no
            # page
            ctx = jnp.stack(
                [paged_decode_mha(qh[:, i], kp, vp, page_table,
                                  jnp.where(live, lens + i + 1, 0), tp=tp)
                 for i in range(w)], axis=1)
            return ctx.reshape(b, w, self.num_heads * hd), kp, vp

        def attend_q(qv, kv, vv, kp, vp, ks, vs):
            # int8 pools: the verify window must store-then-attend one
            # position at a time through the SAME running-absmax
            # primitive as single-token decode — a scale-growth event
            # at window row i requantizes the page before position
            # i+1's read, exactly as the sequential plain path would,
            # so acceptance-matched positions reduce bitwise to it.
            # The window rows are still PROVISIONAL (acceptance may
            # reject all but a prefix, and the plain path never writes
            # rejected rows — their absmax joining a page's MONOTONIC
            # running scale would be unrecoverable), so the touched
            # pages + scale tables snapshot BEFORE any store and ride
            # out as aux with the float rows: the engine restores the
            # snapshot post-acceptance and replays only the accepted
            # prefix (PagedContinuousBatchingEngine._commit_spec_rows).
            from ..ops.paged_attention import paged_decode_mha
            from ..quantization.kv import quant_store_rows

            qh, kh, vh, page, offs = _prep(qv, kv, vv, kp)
            safe = jnp.minimum(page.reshape(-1), kp.shape[0] - 1)
            snap_k, snap_v = kp[safe], vp[safe]
            snap_ks, snap_vs = ks, vs
            ctxs = []
            for i in range(w):
                kp, ks = quant_store_rows(kp, ks, page[:, i],
                                          offs[:, i], kh[:, i])
                vp, vs = quant_store_rows(vp, vs, page[:, i],
                                          offs[:, i], vh[:, i])
                ctxs.append(paged_decode_mha(
                    qh[:, i], kp, vp, page_table,
                    jnp.where(live, lens + i + 1, 0), ks, vs, tp=tp))
            ctx = jnp.stack(ctxs, axis=1)
            return (ctx.reshape(b, w, self.num_heads * hd), kp, vp,
                    ks, vs, snap_k, snap_v, snap_ks, snap_vs,
                    kh, vh, page, offs)

        val = lambda t: t.value if isinstance(t, Tensor) else t  # noqa: E731
        if quant:
            (ctx, kp, vp, ks, vs, snap_k, snap_v, snap_ks, snap_vs,
             kh, vh, page, offs) = apply_op(
                attend_q, q, k, v, *cache,
                op_name="spec_paged_attention")
            return (self._o_lora(ctx, lora),
                    (val(kp), val(vp), val(ks), val(vs)),
                    tuple(val(t) for t in
                          (snap_k, snap_v, snap_ks, snap_vs,
                           kh, vh, page, offs)))
        ctx, kp, vp = apply_op(attend, q, k, v, *cache,
                               op_name="spec_paged_attention")
        return self._o_lora(ctx, lora), (val(kp), val(vp)), None

    def forward_decode_paged(self, x, cos_full, sin_full, cache,
                             page_table, lens, live, lora=None,
                             tp=None):
        """Paged decode step: mixed-length rows, padding-free semantics,
        the KV cache this layer's slice of a shared page pool
        (ops/paged_attention + inference/paged_cache — the vLLM-style
        serving layout the reference's contiguous CacheKV slabs cannot
        express).

        x: [B, 1, h]; lens: [B] int32 tokens already in each ROW's cache
        (per-row positions — rows need not agree); live: [B] bool — only
        live rows write their k/v and advance. The reference's decode
        kernel serves mixed-length batches after remove_padding
        (fused_multi_transformer_op.cu.h:1641) with per-sequence lengths
        (:1680); here the per-row state is the lengths vector
        ``paged_decode_mha`` takes, which walks the pages each row's
        length spans. Writes to dead rows and unmapped pages are DROPPED
        via an out-of-range sentinel, so the step stays one compiled
        program."""
        b = x.shape[0]
        hd = self.config.head_dim
        q, k, v = self._qkv_lora(x, lora)
        quant = len(cache) == 4   # (k, v, k_scale, v_scale) int8 pools

        def _prep(qv, kv, vv, kp):
            ps = kp.shape[1]
            idx = jnp.minimum(lens, page_table.shape[1] * ps - 1)
            c = cos_full[idx][:, None, None, :]
            sn = sin_full[idx][:, None, None, :]
            qh = apply_rotary_emb(
                qv.reshape(b, 1, self.num_heads, hd), c, sn)[:, 0]
            kh = apply_rotary_emb(
                kv.reshape(b, 1, self.kv_heads, hd), c, sn)[:, 0]
            vh = vv.reshape(b, self.kv_heads, hd)
            page = page_table[jnp.arange(b), idx // ps]
            # dead rows / unmapped pages -> sentinel, dropped by scatter
            page = jnp.where(live & (page >= 0), page, kp.shape[0])
            return qh, kh, vh, page, idx % ps

        def attend(qv, kv, vv, kp, vp):
            qh, kh, vh, page, offs = _prep(qv, kv, vv, kp)
            kp = kp.at[page, offs].set(kh.astype(kp.dtype),
                                       mode="drop")
            vp = vp.at[page, offs].set(vh.astype(vp.dtype),
                                       mode="drop")
            from ..ops.paged_attention import paged_decode_mha

            # a dead row (retired slots keep their ``lens``) attends
            # nothing: length 0 costs the kernel no page
            ctx = paged_decode_mha(qh, kp, vp, page_table,
                                   jnp.where(live, lens + 1, 0), tp=tp)
            return ctx.reshape(b, 1, self.num_heads * hd), kp, vp

        def attend_q(qv, kv, vv, kp, vp, ks, vs):
            # int8 pools: quantize-on-store (running absmax rides the
            # scale arrays), fused dequant in the read kernel — the
            # decode-step HBM read is int8, the whole point on
            # bandwidth-bound decode
            from ..ops.paged_attention import paged_decode_mha
            from ..quantization.kv import quant_store_rows

            qh, kh, vh, page, offs = _prep(qv, kv, vv, kp)
            kp, ks = quant_store_rows(kp, ks, page, offs, kh)
            vp, vs = quant_store_rows(vp, vs, page, offs, vh)
            ctx = paged_decode_mha(qh, kp, vp, page_table,
                                   jnp.where(live, lens + 1, 0),
                                   ks, vs, tp=tp)
            return (ctx.reshape(b, 1, self.num_heads * hd), kp, vp,
                    ks, vs)

        val = lambda t: t.value if isinstance(t, Tensor) else t  # noqa: E731
        if quant:
            ctx, kp, vp, ks, vs = apply_op(
                attend_q, q, k, v, *cache, op_name="paged_attention")
            return self._o_lora(ctx, lora), (val(kp), val(vp), val(ks),
                                             val(vs))
        ctx, kp, vp = apply_op(attend, q, k, v, *cache,
                               op_name="paged_attention")
        return self._o_lora(ctx, lora), (val(kp), val(vp))

    def forward(self, x, cos, sin, attn_mask=None):
        b = x.shape[0]
        s = x.shape[1]
        hd = self.config.head_dim
        q = self.q_proj(x)
        k = self.k_proj(x)
        v = self.v_proj(x)

        def prep(qv, kv, vv, cv, sv):
            # GQA stays grouped: the flash kernel selects shared KV heads in
            # its index maps (no jnp.repeat — a 65B config with 64 q-heads /
            # 8 kv-heads would otherwise pay 8x KV activation memory)
            qh = apply_rotary_emb(qv.reshape(b, s, self.num_heads, hd), cv, sv)
            kh = apply_rotary_emb(kv.reshape(b, s, self.kv_heads, hd), cv, sv)
            vh = vv.reshape(b, s, self.kv_heads, hd)
            return qh, kh, vh

        qh, kh, vh = apply_op(prep, q, k, v, cos, sin, op_name="qkv_rope")
        qh = constraint(qh, P("dp", None, "mp", None))
        kh = constraint(kh, P("dp", None, "mp", None))
        vh = constraint(vh, P("dp", None, "mp", None))
        if attn_mask is None:
            ctx, _ = F.flash_attention(qh, kh, vh, causal=True)
        else:
            ctx = F.scaled_dot_product_attention(
                qh, kh, vh, attn_mask=attn_mask, is_causal=True)
        ctx = apply_op(lambda c: c.reshape(b, s, self.num_heads * hd), ctx,
                       op_name="merge_heads")
        ctx = constraint(ctx, P("dp", None, "mp"))
        return self.o_proj(ctx)


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        h, i = config.hidden_size, config.intermediate_size
        self.gate_proj = ColumnParallelLinear(h, i, has_bias=False,
                                              gather_output=False)
        self.up_proj = ColumnParallelLinear(h, i, has_bias=False,
                                            gather_output=False)
        self.down_proj = RowParallelLinear(i, h, has_bias=False,
                                           input_is_parallel=True)

    def forward(self, x, lora=None):
        if lora is None:
            return self.down_proj(F.silu(self.gate_proj(x))
                                  * self.up_proj(x))
        g = _lora_add(x, self.gate_proj(x), lora, "gate")
        u = _lora_add(x, self.up_proj(x), lora, "up")
        h = F.silu(g) * u
        return _lora_add(h, self.down_proj(h), lora, "down")


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)

    def forward(self, x, cos, sin, attn_mask=None):
        x = x + self.self_attn(self.input_layernorm(x), cos, sin, attn_mask)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return constraint(x, P("dp", None, None))

    def forward_with_cache(self, x, cos_full, sin_full, cache, pos,
                           lora=None, tp=None):
        attn, cache = self.self_attn.forward_with_cache(
            self.input_layernorm(x), cos_full, sin_full, cache, pos,
            lora=lora, tp=tp)
        x = x + attn
        x = x + self.mlp(self.post_attention_layernorm(x), lora=lora)
        return x, cache

    def forward_decode_paged(self, x, cos_full, sin_full, cache,
                             page_table, lens, live, lora=None,
                             tp=None):
        attn, cache = self.self_attn.forward_decode_paged(
            self.input_layernorm(x), cos_full, sin_full, cache,
            page_table, lens, live, lora=lora, tp=tp)
        x = x + attn
        x = x + self.mlp(self.post_attention_layernorm(x), lora=lora)
        return x, cache

    def forward_decode_spec_paged(self, x, cos_full, sin_full, cache,
                                  page_table, lens, live, lora=None,
                                  tp=None):
        attn, cache, aux = self.self_attn.forward_decode_spec_paged(
            self.input_layernorm(x), cos_full, sin_full, cache,
            page_table, lens, live, lora=lora, tp=tp)
        x = x + attn
        x = x + self.mlp(self.post_attention_layernorm(x), lora=lora)
        return x, cache, aux


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        from ..nn.layer.container import LayerList

        self.layers = LayerList([LlamaDecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None):
        cfg = self.config
        x = self.embed_tokens(input_ids)
        x = constraint(x, P("dp", None, None))
        s = x.shape[1]
        cos, sin = _rope_cos_sin(s, cfg.head_dim, cfg.rope_theta,
                                 x.value.dtype if isinstance(x, Tensor) else x.dtype)
        for layer in self.layers:
            if cfg.recompute == "full" and self.training:
                from ..distributed.fleet.recompute import recompute

                x = recompute(layer, x, cos, sin, attn_mask)
            else:
                x = layer(x, cos, sin, attn_mask)
        return self.norm(x)

    def init_cache(self, batch_size: int, max_len: int):
        """Preallocated per-layer KV caches (≙ the reference's
        CacheKV tensors fed to fused_multi_transformer)."""
        import numpy as _np

        cfg = self.config
        dt = jnp.dtype(cfg.dtype) if cfg.dtype != "float32" else jnp.float32
        shape = (batch_size, max_len, cfg.kv_heads, cfg.head_dim)
        return [(jnp.zeros(shape, dt), jnp.zeros(shape, dt))
                for _ in range(cfg.num_hidden_layers)]

    def forward_with_cache(self, input_ids, caches, pos, lora=None,
                           tp=None):
        cfg = self.config
        x = self.embed_tokens(input_ids)
        max_len = caches[0][0].shape[1]
        cos_full, sin_full = _rope_cos_sin(
            max_len, cfg.head_dim, cfg.rope_theta,
            x.value.dtype if isinstance(x, Tensor) else x.dtype)
        new_caches = []
        for i, (layer, cache) in enumerate(zip(self.layers, caches)):
            x, cache = layer.forward_with_cache(
                x, cos_full, sin_full, cache, pos,
                lora=_lora_layer(lora, i), tp=tp)
            new_caches.append(cache)
        return self.norm(x), new_caches

    def init_paged_cache(self, num_pages: int, page_size: int,
                         kv_dtype: str = "bf16"):
        """Per-layer page POOLS (shared-table layout: one page_table,
        inference/paged_cache.PageAllocator, serves every layer).

        ``kv_dtype="bf16"`` (default) stores pages in the model's
        configured cache dtype — the bitwise pre-quantization layout.
        ``"int8"`` returns 4-tuples ``(k, v, k_scale, v_scale)`` per
        layer: int8 pools plus per-(page, kv_head) f32 running-absmax
        scales (quantization.kv conventions) that every paged
        decode/spec forward quantizes against on store and dequantizes
        with inside the attention kernel."""
        cfg = self.config
        shape = (num_pages, page_size, cfg.kv_heads, cfg.head_dim)
        if kv_dtype == "int8":
            from ..quantization.kv import KV_SCALE_FLOOR

            sshape = (num_pages, cfg.kv_heads)
            return [(jnp.zeros(shape, jnp.int8),
                     jnp.zeros(shape, jnp.int8),
                     jnp.full(sshape, KV_SCALE_FLOOR, jnp.float32),
                     jnp.full(sshape, KV_SCALE_FLOOR, jnp.float32))
                    for _ in range(cfg.num_hidden_layers)]
        dt = jnp.dtype(cfg.dtype) if cfg.dtype != "float32" else jnp.float32
        return [(jnp.zeros(shape, dt), jnp.zeros(shape, dt))
                for _ in range(cfg.num_hidden_layers)]

    def forward_decode_paged(self, input_ids, caches, page_table, lens,
                             live, lora=None, tp=None):
        cfg = self.config
        x = self.embed_tokens(input_ids)
        max_len = page_table.shape[1] * caches[0][0].shape[1]
        cos_full, sin_full = _rope_cos_sin(
            max_len, cfg.head_dim, cfg.rope_theta,
            x.value.dtype if isinstance(x, Tensor) else x.dtype)
        new_caches = []
        for i, (layer, cache) in enumerate(zip(self.layers, caches)):
            x, cache = layer.forward_decode_paged(
                x, cos_full, sin_full, cache, page_table, lens, live,
                lora=_lora_layer(lora, i), tp=tp)
            new_caches.append(cache)
        return self.norm(x), new_caches

    def forward_decode_spec_paged(self, input_ids, caches, page_table,
                                  lens, live, lora=None, tp=None):
        """Speculative verify step over the page pool — see
        LlamaAttention.forward_decode_spec_paged. The third result is
        the per-layer window-write aux (int8 pools: the float K/V rows
        + their page/offset targets, for the engine's post-acceptance
        running-absmax commit; ``None`` entries on bf16 pools)."""
        cfg = self.config
        x = self.embed_tokens(input_ids)
        max_len = page_table.shape[1] * caches[0][0].shape[1]
        cos_full, sin_full = _rope_cos_sin(
            max_len, cfg.head_dim, cfg.rope_theta,
            x.value.dtype if isinstance(x, Tensor) else x.dtype)
        new_caches = []
        aux_rows = []
        for i, (layer, cache) in enumerate(zip(self.layers, caches)):
            x, cache, aux = layer.forward_decode_spec_paged(
                x, cos_full, sin_full, cache, page_table, lens, live,
                lora=_lora_layer(lora, i), tp=tp)
            new_caches.append(cache)
            aux_rows.append(aux)
        return self.norm(x), new_caches, aux_rows


class LlamaForCausalLM(Layer):
    IGNORE_INDEX = -100

    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        from ..core.dtype import get_default_dtype, set_default_dtype

        prev = get_default_dtype()
        set_default_dtype(config.dtype)  # params honor the config dtype
        try:
            self.model = LlamaModel(config)
            if config.tie_word_embeddings:
                self.lm_head = None
            else:
                self.lm_head = ColumnParallelLinear(
                    config.hidden_size, config.vocab_size, has_bias=False,
                    gather_output=False)
        finally:
            set_default_dtype(prev)
        self.loss_fn = ParallelCrossEntropy(ignore_index=self.IGNORE_INDEX)

    def logits(self, hidden):
        if self.lm_head is None:
            w = self.model.embed_tokens.weight
            return apply_op(lambda hv, wv: hv @ wv.T, hidden, w,
                            op_name="tied_lm_head")
        return self.lm_head(hidden)

    def forward(self, input_ids, labels=None, attn_mask=None):
        hidden = self.model(input_ids, attn_mask)
        logits = self.logits(hidden)
        if labels is None:
            return logits
        loss = self.loss_fn(logits, labels)
        from ._utils import masked_lm_loss

        return masked_lm_loss(loss, labels, self.IGNORE_INDEX)

    def init_cache(self, batch_size: int, max_len: int):
        return self.model.init_cache(batch_size, max_len)

    def lora_shapes(self, targets):
        """LoRA bank geometry hook for the serving engines: returns
        ``(num_layers, {target: (d_in, d_out)})`` for the requested
        target projections (subset of q/k/v/o, gate/up/down). The
        engine stacks every resident adapter's factors into
        ``[L, K+1, r, d_in]`` / ``[L, K+1, d_out, r]`` device arrays
        per target and gathers each slot's delta inside the compiled
        decode programs (see :func:`_lora_add`)."""
        cfg = self.config
        hd = cfg.head_dim
        dims = {
            "q": (cfg.hidden_size, cfg.num_attention_heads * hd),
            "k": (cfg.hidden_size, cfg.kv_heads * hd),
            "v": (cfg.hidden_size, cfg.kv_heads * hd),
            "o": (cfg.num_attention_heads * hd, cfg.hidden_size),
            "gate": (cfg.hidden_size, cfg.intermediate_size),
            "up": (cfg.hidden_size, cfg.intermediate_size),
            "down": (cfg.intermediate_size, cfg.hidden_size),
        }
        unknown = [t for t in targets if t not in dims]
        if unknown:
            raise ValueError(
                f"unknown lora target(s) {unknown}; supported: "
                f"{sorted(dims)}")
        return cfg.num_hidden_layers, {t: dims[t] for t in targets}

    def forward_with_cache(self, input_ids, caches, pos, lora=None,
                           tp=None):
        """(logits_of_last_positions, new_caches) — the serving forward.
        ``lora`` (every serving forward below too) is the optional
        batched-adapter input ``(bank, adapter_idx)`` —
        see :func:`_lora_add`."""
        hidden, caches = self.model.forward_with_cache(
            input_ids, caches, pos, lora=lora, tp=tp)
        return self.logits(hidden), caches

    def init_paged_cache(self, num_pages: int, page_size: int,
                         kv_dtype: str = "bf16"):
        return self.model.init_paged_cache(num_pages, page_size,
                                           kv_dtype=kv_dtype)

    def forward_decode_paged(self, input_ids, caches, page_table, lens,
                             live, lora=None, tp=None):
        """(logits [B, 1, V], new_caches) — the mixed-length decode step
        over the page pool (per-row positions; see
        LlamaAttention.forward_decode_paged)."""
        hidden, caches = self.model.forward_decode_paged(
            input_ids, caches, page_table, lens, live, lora=lora,
            tp=tp)
        return self.logits(hidden), caches

    def forward_decode_spec_paged(self, input_ids, caches, page_table,
                                  lens, live, lora=None, tp=None):
        """(logits [B, W, V], new_caches, aux) — batched speculative
        verify step over the page pool; ``aux`` is the per-layer
        window-write rows for the engine's post-acceptance int8 commit
        (``None`` entries on bf16 pools)."""
        hidden, caches, aux = self.model.forward_decode_spec_paged(
            input_ids, caches, page_table, lens, live, lora=lora,
            tp=tp)
        return self.logits(hidden), caches, aux
