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
engines', through ``models/_windowed.py`` (shared with
``models/smallthinker.py``): its layers keep their KV in TWO geometries, a
full layer every position of a row, a window layer the last
``sliding_window`` in a ring of pages. What the engine cannot do with such
a model (tensor parallelism, int8 pools, speculation, the prefix cache,
chunked prefill, LoRA) it refuses at construction.
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
from ..distributed.fleet.layers.mpu import (ColumnParallelLinear,
                                            RowParallelLinear,
                                            VocabParallelEmbedding)
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..nn.layer.routed_experts import RoutedExperts
from ._windowed import (WindowedAttention, WindowedForCausalLM,
                        WindowedModel, ring_pages)
from .llama import LlamaMLP, apply_rotary_emb

__all__ = ["AfmoeConfig", "AfmoeModel", "AfmoeForCausalLM", "ring_pages"]

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

    @property
    def window(self) -> int:
        return self.sliding_window

    def is_sliding(self, layer: int) -> bool:
        return self.layer_types[layer] == SLIDING


def _head_norm(x, w, eps):
    """RMSNorm over the head size, in float32 (x [..., D] float32)."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


class AfmoeAttention(WindowedAttention):
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

    def _head_weights(self):
        return self.q_norm.weight, self.k_norm.weight

    def _heads(self, qv, kv, vv, weights, cos, sin):
        """Projections [B, S, H*D] -> normed (and, sliding, rotated) heads
        in the cache dtype. ``cos``/``sin``: float32, broadcastable to
        [B, S, 1, D/2] (per-position or per-row angles)."""
        qw, kw = weights
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


class AfmoeModel(WindowedModel):
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


class AfmoeForCausalLM(WindowedForCausalLM):
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
