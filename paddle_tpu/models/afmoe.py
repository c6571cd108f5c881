"""A sparse-expert decoder with window and full attention layers side by
side (``model_type: afmoe``), for the paged serving engine.

The block, every norm an RMSNorm with a weight (``layer_types[l]`` names
layer ``l``'s attention; the ``num_dense_layers`` leading layers have a
dense SwiGLU, the rest a router, routed experts and one shared expert):

    x   = E[ids] * sqrt(hidden_size)                    # mup_enabled
    a   = N1(x)
    q,k,v = a Wq, a Wk, a Wv ;  g = a Wg
    q,k = RMSNorm_head(q), RMSNorm_head(k)
    sliding_attention: q,k = rope(q,k)                  # full_attention: none
    o   = softmax(q k^T / sqrt(head_dim) + mask) v      # sliding: i-W < j <= i
    x   = x + N2((o * sigmoid(g)) Wo)
    m   = N3(x)
    f   = dense(m)   or   shared(m) + routed(m)         # nn/layer/routed_experts
    x   = x + N4(f)

It implements the paged engine's model contract and nothing of the dense
engines': ``init_cache`` / ``forward_with_cache`` (one-shot prefill from
position 0 into a bucket-wide cache), ``init_paged_cache`` /
``forward_decode_paged``, and ``paged_layout``, which tells the engine
that its layers keep their KV in TWO geometries: a full layer holds every
position of a row, a window layer the last ``sliding_window`` in a ring of
pages (``inference/paged_cache.WindowedPageAllocator``). What the engine
cannot do with such a model (tensor parallelism, int8 pools, speculation,
the prefix cache, chunked prefill, LoRA) it refuses at construction.
``tests/reference_moe_window_decoder.py`` is the plain reference.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.autograd import apply_op
from ..core.tensor import Tensor
from ..distributed.fleet.layers.mpu import (ColumnParallelLinear,
                                            RowParallelLinear,
                                            VocabParallelEmbedding)
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..nn.layer.routed_experts import RoutedExperts
from .llama import LlamaMLP, _rope_cos_sin, apply_rotary_emb

__all__ = ["AfmoeConfig", "AfmoeModel", "AfmoeForCausalLM"]

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass
class AfmoeConfig:
    """The published ``config.json`` keys, every one a field (a key the
    file has and the class lacks would be dropped in silence by a caller
    that filters on fields). Values this implementation does not compute
    are refused in ``__post_init__``, not ignored."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    # the first num_hidden_layers entries are used (a longer list is a
    # depth cut laid over the published pattern)
    layer_types: Optional[list] = None
    sliding_window: int = 2048
    global_attn_every_n_layers: int = 4
    num_dense_layers: int = 2
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    num_expert_groups: int = 1
    num_limited_groups: int = 1
    n_group: int = 1
    topk_group: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True
    load_balance_coeff: float = 0.001
    use_grouped_mm: bool = True
    model_type: str = "afmoe"
    dtype: str = "float32"

    def __post_init__(self):
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            self.layer_types = [FULL if (i + 1) % n == 0 else SLIDING
                                for i in range(self.num_hidden_layers)]
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown or len(self.layer_types) < self.num_hidden_layers:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers as "
                f"{SLIDING!r} or {FULL!r}; got {self.layer_types!r}")
        for key, want in (("score_func", "sigmoid"), ("hidden_act", "silu"),
                          ("num_shared_experts", 1), ("n_group", 1),
                          ("topk_group", 1), ("num_expert_groups", 1),
                          ("num_limited_groups", 1), ("rope_scaling", None),
                          ("tie_word_embeddings", False)):
            if getattr(self, key) != want:
                raise ValueError(
                    f"{key}={getattr(self, key)!r} is not implemented "
                    f"(only {want!r})")

    def is_sliding(self, layer: int) -> bool:
        return self.layer_types[layer] == SLIDING


def _val(t):
    return t.value if isinstance(t, Tensor) else t


def _head_norm(x, w, eps):
    """RMSNorm over the head size, in float32 (x [..., D] float32)."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def ring_pages(window: int, page_size: int) -> int:
    """Pages a window layer holds for one row at the most: the window's
    own, and one more so that the page being written never shares a ring
    slot with a page the window still reaches."""
    return -(-window // page_size) + 1


class AfmoeAttention(Layer):
    """Gated attention with QK-norm; ``window`` None = a full layer (no
    position encoding), else a sliding layer (rope, last ``window`` keys)."""

    def __init__(self, config: AfmoeConfig, window: Optional[int]):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.window = window
        h, hd = config.hidden_size, config.head_dim
        self.num_heads = config.num_attention_heads
        self.kv_heads = config.num_key_value_heads
        lin = dict(has_bias=False, gather_output=False)
        self.q_proj = ColumnParallelLinear(h, self.num_heads * hd, **lin)
        self.k_proj = ColumnParallelLinear(h, self.kv_heads * hd, **lin)
        self.v_proj = ColumnParallelLinear(h, self.kv_heads * hd, **lin)
        self.gate_proj = ColumnParallelLinear(h, self.num_heads * hd, **lin)
        self.o_proj = RowParallelLinear(self.num_heads * hd, h,
                                        has_bias=False,
                                        input_is_parallel=True)
        self.q_norm = RMSNorm(hd, epsilon=config.rms_norm_eps)
        self.k_norm = RMSNorm(hd, epsilon=config.rms_norm_eps)

    def _heads(self, qv, kv, vv, qw, kw, cos, sin):
        """Projections [B, S, H*D] -> normed (and, sliding, rotated) heads
        in the cache dtype. ``cos``/``sin``: float32, broadcastable to
        [B, S, 1, D/2] (per-position or per-row angles)."""
        b, s = qv.shape[0], qv.shape[1]
        hd, eps = self.config.head_dim, self.config.rms_norm_eps
        qh = _head_norm(qv.reshape(b, s, self.num_heads, hd)
                        .astype(jnp.float32), qw, eps)
        kh = _head_norm(kv.reshape(b, s, self.kv_heads, hd)
                        .astype(jnp.float32), kw, eps)
        if self.window is not None:
            qh = apply_rotary_emb(qh, cos, sin)
            kh = apply_rotary_emb(kh, cos, sin)
        return (qh.astype(qv.dtype), kh.astype(kv.dtype),
                vv.reshape(b, s, self.kv_heads, hd))

    def _out(self, ctx, x):
        """(ctx * sigmoid(gate(x))) Wo."""
        gated = apply_op(
            lambda c, g: (c.astype(jnp.float32)
                          * jax.nn.sigmoid(g.astype(jnp.float32))
                          ).astype(c.dtype),
            ctx, self.gate_proj(x), op_name="attention_gate")
        return self.o_proj(gated)

    def forward_with_cache(self, x, cos, sin, cache):
        """Prefill from position 0: x [B, S, h]; ``cache`` (k, v)
        [B, S_max, Hkv, D] takes the prompt's keys and values at [0, S).
        Returns (out, new_cache)."""
        from ..ops.pallas import flash_attention

        b, s = x.shape[0], x.shape[1]

        def attend(qv, kv, vv, qw, kw, kc, vc):
            qh, kh, vh = self._heads(qv, kv, vv, qw, kw,
                                     cos[None, :s, None, :],
                                     sin[None, :s, None, :])
            ctx = flash_attention(qh, kh, vh, causal=True,
                                  window=self.window)
            kc = jax.lax.dynamic_update_slice_in_dim(
                kc, kh.astype(kc.dtype), 0, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(
                vc, vh.astype(vc.dtype), 0, axis=1)
            return ctx.reshape(b, s, -1), kc, vc

        ctx, kc, vc = apply_op(
            attend, self.q_proj(x), self.k_proj(x), self.v_proj(x),
            self.q_norm.weight, self.k_norm.weight, *cache,
            op_name="cached_attention")
        return self._out(ctx, x), (_val(kc), _val(vc))

    def forward_decode_paged(self, x, cos, sin, cache, page_table, lens,
                             live):
        """One token per row at per-row position ``lens``. A full layer's
        ``page_table`` row lists the row's pages in order; a sliding
        layer's is a RING of ``ring_pages`` slots in which position p
        lives at slot (p // page_size) % ring: the kernel is handed the
        ring turned so that the window's first page comes first, and the
        lengths counted from that page."""
        from ..ops.paged_attention import paged_decode_mha

        b = x.shape[0]

        def attend(qv, kv, vv, qw, kw, kp, vp):
            ps, cols = kp.shape[1], page_table.shape[1]
            c = cos[lens][:, None, None, :]
            sn = sin[lens][:, None, None, :]
            qh, kh, vh = self._heads(qv, kv, vv, qw, kw, c, sn)
            # a dead row attends nothing: length 0 costs the kernel no page
            new_len = jnp.where(live, lens + 1, 0)
            col = lens // ps
            table = page_table
            if self.window is not None:
                col = col % cols
                first = jnp.maximum(new_len - self.window, 0) // ps
                turn = (first[:, None] + jnp.arange(cols)[None, :]) % cols
                table = jnp.take_along_axis(page_table, turn, axis=1)
                new_len = new_len - first * ps
            page = page_table[jnp.arange(b), jnp.minimum(col, cols - 1)]
            # dead rows / unmapped pages -> sentinel, dropped by scatter
            page = jnp.where(live & (page >= 0), page, kp.shape[0])
            kp = kp.at[page, lens % ps].set(kh[:, 0].astype(kp.dtype),
                                            mode="drop")
            vp = vp.at[page, lens % ps].set(vh[:, 0].astype(vp.dtype),
                                            mode="drop")
            ctx = paged_decode_mha(qh[:, 0], kp, vp, table, new_len,
                                   window=self.window)
            return ctx.reshape(b, 1, -1), kp, vp

        ctx, kp, vp = apply_op(
            attend, self.q_proj(x), self.k_proj(x), self.v_proj(x),
            self.q_norm.weight, self.k_norm.weight, *cache,
            op_name="paged_attention")
        return self._out(ctx, x), (_val(kp), _val(vp))


class AfmoeSparseMLP(Layer):
    """shared(m) + routed(m); returns (f, routing stats)."""

    def __init__(self, config: AfmoeConfig):
        super().__init__(dtype=config.dtype)
        self.experts = RoutedExperts(
            config.hidden_size, config.moe_intermediate_size,
            config.num_experts, config.num_experts_per_tok,
            route_scale=config.route_scale, route_norm=config.route_norm)
        self.shared_experts = LlamaMLP(SimpleNamespace(
            hidden_size=config.hidden_size,
            intermediate_size=(config.moe_intermediate_size
                               * config.num_shared_experts),
            dtype=config.dtype))

    def forward(self, m, valid=None):
        routed, stats = self.experts(m, valid=valid)
        return self.shared_experts(m) + routed, stats


class AfmoeDecoderLayer(Layer):
    def __init__(self, config: AfmoeConfig, index: int):
        super().__init__(dtype=config.dtype)
        self.sparse = index >= config.num_dense_layers
        self.self_attn = AfmoeAttention(
            config, config.sliding_window if config.is_sliding(index)
            else None)
        self.mlp = (AfmoeSparseMLP(config) if self.sparse
                    else LlamaMLP(config))
        h, eps = config.hidden_size, config.rms_norm_eps
        self.input_layernorm = RMSNorm(h, epsilon=eps)
        self.post_attention_layernorm = RMSNorm(h, epsilon=eps)
        self.pre_mlp_layernorm = RMSNorm(h, epsilon=eps)
        self.post_mlp_layernorm = RMSNorm(h, epsilon=eps)

    def _ffn(self, x, valid):
        """x + N4(f(N3(x))); returns (x, routing stats or None)."""
        m = self.pre_mlp_layernorm(x)
        f, stats = (self.mlp(m, valid=valid) if self.sparse
                    else (self.mlp(m), None))
        return x + self.post_mlp_layernorm(f), stats

    def forward_with_cache(self, x, cos, sin, cache, valid=None):
        attn, cache = self.self_attn.forward_with_cache(
            self.input_layernorm(x), cos, sin, cache)
        x, stats = self._ffn(x + self.post_attention_layernorm(attn), valid)
        return x, cache, stats

    def forward_decode_paged(self, x, cos, sin, cache, page_table, lens,
                             live):
        attn, cache = self.self_attn.forward_decode_paged(
            self.input_layernorm(x), cos, sin, cache, page_table, lens,
            live)
        x, stats = self._ffn(x + self.post_attention_layernorm(attn),
                             live[:, None])
        return x, cache, stats


class AfmoeModel(Layer):
    def __init__(self, config: AfmoeConfig):
        super().__init__(dtype=config.dtype)
        from ..nn.layer.container import LayerList

        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = LayerList([AfmoeDecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def _embed(self, input_ids):
        x = self.embed_tokens(input_ids)
        if self.config.mup_enabled:
            x = x * math.sqrt(self.config.hidden_size)
        return x

    def _rope(self, positions: int):
        cfg = self.config
        return _rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                             jnp.float32)

    def forward_with_cache(self, input_ids, caches, pos=0, last_idx=None):
        if not (isinstance(pos, int) and pos == 0):
            raise NotImplementedError(
                "prefill at an offset (chunked prefill, a warm prefix hit) "
                "is not implemented for window layers")
        x = self._embed(input_ids)
        s = x.shape[1]
        cos, sin = self._rope(s)
        # bucket padding past the prompt's last token takes no expert
        valid = (None if last_idx is None
                 else (jnp.arange(s) <= last_idx)[None, :])
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            x, cache, _ = layer.forward_with_cache(x, cos, sin, cache,
                                                   valid=valid)
            new_caches.append(cache)
        if last_idx is not None:
            # the head is 200k wide: only the position that is sampled
            x = apply_op(lambda v: jax.lax.dynamic_slice_in_dim(
                v, last_idx, 1, axis=1), x, op_name="last_position")
        return self.norm(x), new_caches

    def forward_decode_paged(self, input_ids, caches, page_table, lens,
                             live):
        cfg = self.config
        full_table, ring_table = page_table
        x = self._embed(input_ids)
        ps = caches[0][0].shape[1]
        cos, sin = self._rope(full_table.shape[1] * ps)
        lens = jnp.minimum(lens, full_table.shape[1] * ps - 1)
        new_caches = []
        hit = rows_max = jnp.int32(0)
        for i, (layer, cache) in enumerate(zip(self.layers, caches)):
            x, cache, stats = layer.forward_decode_paged(
                x, cos, sin, cache,
                ring_table if cfg.is_sliding(i) else full_table, lens, live)
            new_caches.append(cache)
            if stats is not None:
                hit = hit + _val(stats["experts_hit"])
                rows_max = rows_max + _val(stats["expert_rows_max"])
        return (self.norm(x), new_caches,
                {"experts_hit": hit, "expert_rows_max": rows_max})


class AfmoeForCausalLM(Layer):
    def __init__(self, config: AfmoeConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        from ..core.dtype import get_default_dtype, set_default_dtype

        prev = get_default_dtype()
        set_default_dtype(config.dtype)  # params honor the config dtype
        try:
            self.model = AfmoeModel(config)
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=False)
        finally:
            set_default_dtype(prev)

    def _logits(self, hidden):
        """The head's product with a float32 result, whatever the weights'
        dtype: the top of 200k bf16 logits would be rounded to steps as
        large as the differences between them."""
        return apply_op(
            lambda h, w: jnp.matmul(h, w,
                                    preferred_element_type=jnp.float32),
            hidden, self.lm_head.weight, op_name="lm_head")

    def forward(self, input_ids):
        """Logits [B, S, V] of a whole sequence, no cache kept."""
        ids = _val(input_ids)
        logits, _ = self.forward_with_cache(
            input_ids, self.init_cache(ids.shape[0], ids.shape[1]), 0)
        return logits

    def init_cache(self, batch_size: int, max_len: int):
        cfg = self.config
        shape = (batch_size, max_len, cfg.num_key_value_heads, cfg.head_dim)
        dt = jnp.dtype(cfg.dtype)
        return [(jnp.zeros(shape, dt), jnp.zeros(shape, dt))
                for _ in range(cfg.num_hidden_layers)]

    def forward_with_cache(self, input_ids, caches, pos=0, last_idx=None):
        """(logits, new_caches) of a one-shot prefill from position 0.
        ``last_idx`` (a traced position): logits [B, 1, V] of that
        position only, and the padding after it is routed nowhere."""
        hidden, caches = self.model.forward_with_cache(
            input_ids, caches, pos, last_idx=last_idx)
        return self._logits(hidden), caches

    def paged_layout(self, page_size: int) -> dict:
        """What the paged engine has to know of this model's cache: which
        layers keep a ring of the last ``window`` positions, and the
        ring's pages; that prefill takes ``last_idx``; that a decode step
        hands out counters."""
        cfg = self.config
        return {"ring": {"window": cfg.sliding_window,
                         "ring_pages": ring_pages(cfg.sliding_window,
                                                  page_size),
                         "window_layers": tuple(
                             cfg.is_sliding(i)
                             for i in range(cfg.num_hidden_layers))},
                "last_idx": True, "counters": True,
                "rows": "per-head K and V in two geometries (its window "
                        "layers keep a ring of pages)"}

    def init_paged_cache(self, num_pages: int, page_size: int,
                         window_pages: int = 0):
        """Per-layer page pools: ``num_pages`` for a full layer,
        ``window_pages`` for a sliding layer."""
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        out = []
        for i in range(cfg.num_hidden_layers):
            shape = (window_pages if cfg.is_sliding(i) else num_pages,
                     page_size, cfg.num_key_value_heads, cfg.head_dim)
            out.append((jnp.zeros(shape, dt), jnp.zeros(shape, dt)))
        return out

    def forward_decode_paged(self, input_ids, caches, page_table, lens,
                             live):
        """(logits [B, 1, V], new_caches, routing counts) — one decode
        step over the two page tables ``(full, ring)``."""
        hidden, caches, stats = self.model.forward_decode_paged(
            input_ids, caches, page_table, lens, live)
        return self._logits(hidden), caches, stats
