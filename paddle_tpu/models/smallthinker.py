"""A sparse-expert decoder whose router reads the attention's input, with
window and full attention layers side by side (``model_type:
smallthinker``), for the paged serving engine.

Every layer is the same block but for its attention's kind
(``sliding_window_layout[l]`` = ``rope_layout[l]`` = 1: a window layer
with rope; 0: a full layer with no position encoding); every norm an
RMSNorm with a weight, no product has a bias, the head is untied:

    x   = E[ids]                                        # no embedding scale
    a   = N1(x)
    z   = a Wr                                          # float32, highest
    sel = top_k(z);  w = softmax(z[sel])                # 6 of 64
    q,k,v = a Wq, a Wk, a Wv
    window layer: q,k = rope(q,k)                       # full layer: none
    o   = softmax(q k^T / sqrt(head_dim) + mask) v      # window: i-W < j <= i
    x   = x + o Wo
    m   = N2(x)
    x   = x + sum_{e in sel} w_e (relu(m Wg_e) * m Wu_e) Wd_e
    logits = N(x) Whead                                 # float32

The router is computed from ``a`` while the experts compute on ``m``
(``nn/layer/routed_experts.RoutedExperts(router_input=)``). The engine's
model contract, the two cache geometries and the six features the
engine refuses with such a model are ``models/_windowed.py``'s, shared
with ``models/afmoe.py``. ``tests/reference_smallthinker_decoder.py`` is
the plain reference.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from ..distributed.fleet.layers.mpu import (ColumnParallelLinear,
                                            RowParallelLinear,
                                            VocabParallelEmbedding)
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..nn.layer.routed_experts import RoutedExperts
from ._windowed import (WindowedAttention, WindowedForCausalLM,
                        WindowedModel)

__all__ = ["SmallThinkerConfig", "SmallThinkerModel",
           "SmallThinkerForCausalLM"]


@dataclass
class SmallThinkerConfig:
    """The published ``config.json`` keys, every one a field (a key the
    file has and the class lacks would be dropped in silence by a caller
    that filters on fields). Values this implementation does not compute
    are refused in ``__post_init__``, not ignored."""
    vocab_size: int = 151936
    hidden_size: int = 2560
    moe_ffn_hidden_size: int = 768
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 16384
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    rope_scaling: Optional[dict] = None
    tie_word_embeddings: bool = False
    # one entry a layer, 1 = a window layer with rope, 0 = a full layer
    # with no position encoding; the first num_hidden_layers are used (a
    # longer list is a depth cut laid over the published pattern)
    rope_layout: Optional[list] = None
    sliding_window_layout: Optional[list] = None
    sliding_window_size: int = 4096
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    # the family's secondary experts: none are built
    moe_num_secondary_experts: int = 0
    model_name: str = "smallthinker_21b_instruct"
    model_type: str = "smallthinker"
    dtype: str = "float32"

    def __post_init__(self):
        n = self.num_hidden_layers
        default = [0 if i % 4 == 0 else 1 for i in range(n)]
        if self.rope_layout is None:
            self.rope_layout = list(default)
        if self.sliding_window_layout is None:
            self.sliding_window_layout = list(default)
        for key in ("rope_layout", "sliding_window_layout"):
            lay = getattr(self, key)
            if len(lay) < n or set(lay) - {0, 1}:
                raise ValueError(f"{key} must give {n} layers as 0 or 1; "
                                 f"got {lay!r}")
        if self.rope_layout[:n] != self.sliding_window_layout[:n]:
            raise ValueError(
                "rope_layout and sliding_window_layout disagree: a window "
                "layer without rope or a full layer with it is not "
                "implemented")
        for key, want in (("moe_primary_router_apply_softmax", True),
                          ("norm_topk_prob", True),
                          ("moe_num_secondary_experts", 0),
                          ("rope_scaling", None),
                          ("tie_word_embeddings", False)):
            if getattr(self, key) != want:
                raise ValueError(
                    f"{key}={getattr(self, key)!r} is not implemented "
                    f"(only {want!r})")

    @property
    def window(self) -> int:
        return self.sliding_window_size

    def is_sliding(self, layer: int) -> bool:
        return self.sliding_window_layout[layer] == 1


class SmallThinkerAttention(WindowedAttention):
    """Grouped-query attention with no QK-norm and no output gate;
    ``window`` None = a full layer (no position encoding), else a window
    layer (rope, the last ``window`` keys)."""

    def __init__(self, config: SmallThinkerConfig, window: Optional[int]):
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
        self.o_proj = RowParallelLinear(self.num_heads * hd, h,
                                        has_bias=False,
                                        input_is_parallel=True)


class SmallThinkerDecoderLayer(Layer):
    def __init__(self, config: SmallThinkerConfig, index: int):
        super().__init__(dtype=config.dtype)
        self.self_attn = SmallThinkerAttention(
            config, config.window if config.is_sliding(index) else None)
        self.mlp = RoutedExperts(
            config.hidden_size, config.moe_ffn_hidden_size,
            config.moe_num_primary_experts,
            config.moe_num_active_primary_experts, score="softmax",
            act="relu")
        h, eps = config.hidden_size, config.rms_norm_eps
        self.input_layernorm = RMSNorm(h, epsilon=eps)
        self.post_attention_layernorm = RMSNorm(h, epsilon=eps)

    def _block(self, x, attend, valid):
        """x + attention, then + the experts; the router reads the
        attention's normed input. Returns (x, cache, routing stats)."""
        a = self.input_layernorm(x)
        attn, cache = attend(a)
        x = x + attn
        f, stats = self.mlp(self.post_attention_layernorm(x), valid=valid,
                            router_input=a)
        return x + f, cache, stats

    def forward_with_cache(self, x, cos, sin, cache, valid=None):
        return self._block(x, lambda a: self.self_attn.forward_with_cache(
            a, cos, sin, cache), valid)

    def forward_decode_paged(self, x, cos, sin, cache, page_table, lens,
                             live):
        return self._block(x, lambda a: self.self_attn.forward_decode_paged(
            a, cos, sin, cache, page_table, lens, live), live[:, None])


class SmallThinkerModel(WindowedModel):
    def __init__(self, config: SmallThinkerConfig):
        super().__init__(dtype=config.dtype)
        from ..nn.layer.container import LayerList

        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = LayerList([SmallThinkerDecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)


class SmallThinkerForCausalLM(WindowedForCausalLM):
    def __init__(self, config: SmallThinkerConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        from ..core.dtype import get_default_dtype, set_default_dtype

        prev = get_default_dtype()
        set_default_dtype(config.dtype)  # params honor the config dtype
        try:
            self.model = SmallThinkerModel(config)
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=False)
        finally:
            set_default_dtype(prev)
