"""A hybrid of latent attention (MLA) and gated-delta-rule linear attention
with sparse experts (``model_type: gigachat3_5``), as ONE chip's share of an
expert-parallel deployment, for the paged serving engine.

``full_attention_layers`` lists the layers whose mixer is latent attention;
every other layer's is the gated delta rule. Every norm ``N`` is a
zero-centred gated RMS norm, ``x rsqrt(mean(x^2) + eps) * 2 sigmoid(w)``
with ``w`` zero at initialisation (``nn.layer.norm.ZeroCenteredGatedNorm``),
and each sublayer has one before it and one after it (``pre_post``):

    x = x + N2(M(N1(x)))
    x = x + N4(F(N3(x)))

    full layer M (MLA, 64 heads, no indexer: every earlier position):
      c_q = Nqa(a Wqa) ;  q_nope | q_rope = c_q Wqb ;  q_rope = rope(q_rope)
      c | k_r = a Wkva ;  c = Nkva(c) ;  k_rope = rope(k_r)    # one a token
      k_nope | v = c Wkvb
      o = causal_softmax(s (q_nope.k_nope + q_rope.k_rope)) v
      M = (o * sigmoid(a Wg)) Wo                       # gated_attention
    linear layer M (GDN, Hk key heads, Hv value heads, dk = dv = 128):
      the mixer of ``models/olmo_hybrid.GatedDeltaNet`` with value head j
      reading key head j // (Hv / Hk), beta = sigmoid(a Wb), and the output
      (N_o(o) * 2 sigmoid(z)) Wo, N_o a zero-centred gated norm
    F: layers < first_k_dense_replace a SwiGLU of ``intermediate_size``;
      the others shared(a) + this chip's part of routed(a) over sigmoid
      scores, top ``num_experts_per_tok`` of score + bias, weights
      ``routed_scaling_factor`` x the chosen scores renormalised; every
      SwiGLU (dense, shared, routed) is
      ``(silu(min(a Wg, L)) * clip(a Wu, -L, L)) Wd`` with L
      ``swiglu_limit``.

``rope`` rotates neighbouring pairs (``rope_interleave``) with YaRN
frequencies; the softmax scale is ``(nope + rope)^-0.5 * mscale^2``
(``use_mla_scaling_factor``). The MLA projections, YaRN and the rope are
``models/deepseek_v32.py``'s (:class:`LatentAttention`), the GDN mixer and
its kernels ``models/olmo_hybrid.py``'s and ``ops/gated_delta_rule``'s.

What a full layer leaves in the cache is one 640-wide row a token (``c |
k_rope``, zeros to whole lanes), in pages; decode attends every cached row
of the context with the query absorbed into the latent space
(``ops/sparse_latent_attention.paged_latent_decode``), prefill expands the
heads and attends causally (``selected_attention`` with no mask). A linear
layer keeps a fixed-size state a ROW (an engine slot): ``paged_layout``
says which layers are which, and the engine keeps those as ``[max_batch,
...]`` arrays beside the page pools.

The share: ``ep_size`` chips hold one layer's ``n_routed_experts``; this
chip (rank ``ep_rank``) holds ``n_routed_experts // ep_size`` of them from
``ep_rank`` times that on and adds their part of the routed sum. The
multi-token-prediction modules are not built (``num_nextn_predict_layers``
is a field only). ``benchmark/reference/gigachat35_decoder.py`` is the
plain reference.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.autograd import apply_op
from ..distributed.fleet.layers.mpu import (ColumnParallelLinear,
                                            VocabParallelEmbedding)
from ..nn.layer.layers import Layer
from ..nn.layer.norm import ZeroCenteredGatedNorm, zero_centered_scale
from ..nn.layer.routed_experts import RoutedExperts, glu
from ..ops.sparse_latent_attention import paged_latent_decode
from ._live_rows import live_rows, row_block
from .deepseek_v32 import (PREFILL_ROW_BLOCK, LatentAttention, _angles, _rms,
                           _rows, _val, row_page, yarn_inv_freq, yarn_mscale)
from .llama import LlamaMLP
from .olmo_hybrid import GatedDeltaNet, gdn_state_entry

__all__ = ["GigaChat35Config", "GigaChat35Model", "GigaChat35ForCausalLM"]

F32 = jnp.float32


def _published_full_layers():
    return [3 + 4 * i for i in range(10)]


def _published_yarn():
    return {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 32768,
            "type": "yarn"}


@dataclass
class GigaChat35Config:
    """The published ``config.json`` keys, every one a field, plus
    ``ep_size`` / ``ep_rank`` (which share of the experts this chip holds)
    and ``dtype``. Values this implementation does not compute are refused
    in ``__post_init__``, by name, not ignored."""
    vocab_size: int = 128256
    max_position_embeddings: int = 262144
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 40
    nextn_is_sparse: bool = False
    num_attention_heads: int = 64
    n_shared_experts: int = 1
    n_routed_experts: int = 256     # of the layer; ep_size chips share them
    routed_scaling_factor: float = 2.5
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    qk_nope_head_dim: int = 128
    qk_head_dim: int = 192
    n_group: int = 1
    topk_group: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 3
    norm_topk_prob: bool = True
    rope_interleave: bool = True
    num_key_value_heads: int = 64
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 100000.0
    rope_scaling: Optional[dict] = field(default_factory=_published_yarn)
    attention_bias: bool = False
    norm_type: str = "ZeroCenteredGatedNorm"
    layernorm_type: str = "pre_post"
    layernorm_gating_weight: float = 2.0
    gated_attention: bool = True
    use_shared_expert_sigmoid: bool = False
    use_mla_scaling_factor: bool = True
    linear_attention_type: str = "GigaChat35GatedDeltaNet"
    full_attention_layers: list = field(
        default_factory=_published_full_layers)
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    linear_num_key_heads: int = 32
    linear_num_value_heads: int = 64
    linear_gating_type: str = "gated_rmsnorm_sigmoid_zero_centered"
    linear_sigmoid_gate_scale: float = 2.0
    linear_attn_o_norm_eps: float = 1e-6
    swiglu_limit: Optional[float] = 10.0
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 2
    model_type: str = "gigachat3_5"
    tf_legacy_loss: bool = False
    ep_size: int = 1
    ep_rank: int = 0
    dtype: str = "float32"

    # the GDN's write strength is sigmoid(a Wb) (no published key: the
    # mixer of models/olmo_hybrid.py reads this)
    linear_allow_neg_eigval = False

    def __post_init__(self):
        for key, want in (
                ("nextn_is_sparse", False), ("n_group", 1),
                ("use_shared_expert_sigmoid", False), ("hidden_act", "silu"),
                ("attention_bias", False), ("tie_word_embeddings", False),
                ("norm_type", "ZeroCenteredGatedNorm"),
                ("layernorm_type", "pre_post"), ("gated_attention", True),
                ("rope_interleave", True),
                ("linear_attention_type", "GigaChat35GatedDeltaNet"),
                ("linear_gating_type", "gated_rmsnorm_sigmoid_zero_centered"),
                ("linear_sigmoid_gate_scale", 2),
                ("num_key_value_heads", self.num_attention_heads),
                ("qk_head_dim",
                 self.qk_nope_head_dim + self.qk_rope_head_dim)):
            if getattr(self, key) != want:
                raise ValueError(
                    f"{key}={getattr(self, key)!r} is not implemented "
                    f"(only {want!r})")
        rs = self.rope_scaling
        if rs is not None and rs.get("type", rs.get("rope_type")) != "yarn":
            raise ValueError(f"rope_scaling type {rs!r} is not implemented "
                             f"(only 'yarn')")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank {self.ep_rank} outside the "
                             f"{self.ep_size} shares")
        if self.n_routed_experts % self.ep_size:
            raise ValueError(f"{self.n_routed_experts} experts do not divide "
                             f"by ep_size={self.ep_size}")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"linear_num_value_heads={self.linear_num_value_heads} is "
                f"not a multiple of linear_num_key_heads="
                f"{self.linear_num_key_heads}")
        if any(not 0 <= i < self.num_hidden_layers
               for i in self.full_attention_layers):
            raise ValueError(f"full_attention_layers "
                             f"{self.full_attention_layers!r} name layers "
                             f"outside the {self.num_hidden_layers}")

    def is_linear(self, layer: int) -> bool:
        return layer not in self.full_attention_layers

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts // self.ep_size

    @property
    def cache_row(self) -> int:
        """Width of a token's latent row in the cache, ``c | k_rope`` and
        zeros up to whole lanes of 128 (576 -> 640), as
        ``DeepseekV32Config.cache_row`` and for its reason."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        rs = self.rope_scaling
        if rs is None or not self.use_mla_scaling_factor:
            return scale
        return scale * yarn_mscale(rs["factor"],
                                   rs.get("mscale_all_dim", 0)) ** 2


def _norm(config, width):
    return ZeroCenteredGatedNorm(width, epsilon=config.rms_norm_eps,
                                 gating=config.layernorm_gating_weight)


class GigaChat35Attention(LatentAttention):
    """Latent attention with no selection and a sigmoid output gate. Its
    cache is ``(rows,)``: one ``cache_row``-wide row a token."""

    def __init__(self, config: GigaChat35Config):
        super().__init__(config, norm=functools.partial(
            ZeroCenteredGatedNorm, gating=config.layernorm_gating_weight))
        cfg = config
        self.gate_proj = ColumnParallelLinear(
            cfg.hidden_size, cfg.num_attention_heads * cfg.v_head_dim,
            has_bias=False, gather_output=False)
        self.inv_freq = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                                      cfg.rope_scaling)

    def _weights(self):
        return (self.q_a_proj.weight, self.q_a_layernorm.weight,
                self.q_b_proj.weight, self.kv_a_proj_with_mqa.weight,
                self.kv_a_layernorm.weight, self.kv_b_proj.weight,
                self.o_proj.weight, self.gate_proj.weight)

    def _front(self, a, cos, sin, wqa, nq, wkva, nkv):
        """a [..., h] -> (c_q [..., q_lora_rank], the cache's row [...,
        cache_row]), both in a's dtype; ``nq`` / ``nkv`` the latent norms'
        weights as stored."""
        cfg = self.config
        gating = cfg.layernorm_gating_weight
        cq = _rms(jnp.matmul(a, wqa, preferred_element_type=F32),
                  zero_centered_scale(nq, gating),
                  cfg.rms_norm_eps).astype(a.dtype)
        c, kr = self._latent(a, cos, sin, wkva,
                             zero_centered_scale(nkv, gating))
        return cq, self._as_row(c, kr, a.dtype)

    @staticmethod
    def _gated_out(o, a, wg, wo):
        """(o * sigmoid(a Wg)) Wo: o [..., H * v] (any dtype), the gate
        float32, rounded to a's dtype before Wo."""
        gate = jax.nn.sigmoid(jnp.matmul(a, wg, preferred_element_type=F32))
        return jnp.matmul((o.astype(F32) * gate).astype(a.dtype), wo)

    def forward_with_cache(self, x, cache, last_idx=None):
        """Prefill from position 0: x [B, S, h]; ``cache`` (rows [B, S_max,
        cache_row],) takes the prompt's rows at [0, S). Returns (out,
        new_cache)."""
        live = None if last_idx is None else last_idx + 1

        def one(a, wqa, nq, wqb, wkva, nkv, wkvb, wo, wg):
            s = a.shape[0]
            block = row_block(s, PREFILL_ROW_BLOCK)
            pos = jnp.arange(s)

            def before(a, pos):
                cos, sin = _angles(pos, self.inv_freq)
                return self._front(a, cos, sin, wqa, nq, wkva, nkv)

            def after(ctx, a):
                return self._gated_out(
                    jnp.swapaxes(ctx, 0, 1).reshape(ctx.shape[1], -1), a,
                    wg, wo)

            cq, row = live_rows(before, (a, pos), live, block)
            ctx = self._attend_expanded(cq, row, None, pos, wqb, wkvb,
                                        last_idx)
            return live_rows(after, (ctx, a), live, block,
                             in_axes=(1, 0)), row

        def attend(xv, rows, *w):
            out, row = (jnp.stack(v) for v in zip(
                *(one(a, *w) for a in xv)))
            return out, jax.lax.dynamic_update_slice_in_dim(
                rows, row.astype(rows.dtype), 0, axis=1)

        out, rows = apply_op(attend, x, *cache, *self._weights(),
                             op_name="latent_attention_prefill")
        return out, (_val(rows),)

    def forward_decode_paged(self, x, cache, page_table, lens, live):
        """One token a row at per-row position ``lens``: x [B, 1, h];
        ``cache`` the layer's pool (rows [pages, page, cache_row],).
        Returns (out [B, 1, h], new pool)."""
        cfg = self.config

        def attend(xv, lat_pool, wqa, nq, wqb, wkva, nkv, wkvb, wo, wg):
            a = xv[:, 0]
            b, ps = a.shape[0], lat_pool.shape[1]
            cos, sin = _angles(lens, self.inv_freq)
            cq, row = self._front(a, cos, sin, wqa, nq, wkva, nkv)
            page = row_page(page_table, lens, live, lat_pool.shape[0], ps)
            lat_pool = lat_pool.at[page, lens % ps].set(
                row.astype(lat_pool.dtype), mode="drop")
            q_lat, qr, up = self._absorbed_query(cq, cos, sin, wqb, wkvb)
            # a dead row attends nothing: length 0 costs the kernel no page
            ctx = paged_latent_decode(q_lat, qr, lat_pool, page_table,
                                      jnp.where(live, lens + 1, 0),
                                      cfg.softmax_scale)
            o = self._latent_out(ctx, up, a.dtype).reshape(b, -1)
            return self._gated_out(o, a, wg, wo)[:, None], lat_pool

        out, lat_pool = apply_op(attend, x, *cache, *self._weights(),
                                 op_name="latent_attention_decode")
        return out, (_val(lat_pool),)


class LimitedSwiGLU(LlamaMLP):
    """``(silu(min(x Wg, limit)) * clip(x Wu, -limit, limit)) Wd``
    (``nn.layer.routed_experts.glu``), float32 between the products."""

    def __init__(self, hidden_size: int, width: int, dtype, limit):
        super().__init__(SimpleNamespace(hidden_size=hidden_size,
                                         intermediate_size=width,
                                         dtype=dtype))
        self.limit = limit

    def forward(self, x):
        mid = apply_op(lambda g, u: glu(g, u, "silu", self.limit).astype(
            g.dtype), self.gate_proj(x), self.up_proj(x),
            op_name="swiglu_limit")
        return self.down_proj(mid)


class GigaChat35SparseMLP(Layer):
    """shared(m) + this chip's part of routed(m); returns (f, stats)."""

    def __init__(self, config: GigaChat35Config):
        super().__init__(dtype=config.dtype)
        held = (None if config.ep_size == 1 else
                (config.ep_rank * config.experts_held, config.experts_held))
        self.experts = RoutedExperts(
            config.hidden_size, config.moe_intermediate_size,
            config.n_routed_experts, config.num_experts_per_tok,
            route_scale=config.routed_scaling_factor,
            route_norm=config.norm_topk_prob, n_group=config.n_group,
            topk_group=config.topk_group, held=held,
            limit=config.swiglu_limit)
        self.shared_experts = LimitedSwiGLU(
            config.hidden_size,
            config.moe_intermediate_size * config.n_shared_experts,
            config.dtype, config.swiglu_limit)

    def forward(self, m, valid=None):
        routed, stats = self.experts(m, valid=valid)
        return self.shared_experts(m) + routed, stats


class GigaChat35DecoderLayer(Layer):
    def __init__(self, config: GigaChat35Config, index: int):
        super().__init__(dtype=config.dtype)
        self.linear = config.is_linear(index)
        self.sparse = index >= config.first_k_dense_replace
        if self.linear:
            self.linear_attn = GatedDeltaNet(
                config, gating="sigmoid_zero_centered",
                norm_eps=config.linear_attn_o_norm_eps)
        else:
            self.self_attn = GigaChat35Attention(config)
        self.mlp = (GigaChat35SparseMLP(config) if self.sparse else
                    LimitedSwiGLU(config.hidden_size,
                                  config.intermediate_size, config.dtype,
                                  config.swiglu_limit))
        h = config.hidden_size
        self.input_layernorm = _norm(config, h)
        self.post_attention_layernorm = _norm(config, h)
        self.pre_feedforward_layernorm = _norm(config, h)
        self.post_feedforward_layernorm = _norm(config, h)

    def _rest(self, x, mixed, valid=None):
        """x + N2(mixed), then the FFN's residual; returns (x, stats)."""
        x = x + self.post_attention_layernorm(mixed)
        m = self.pre_feedforward_layernorm(x)
        f, stats = (self.mlp(m, valid=valid) if self.sparse
                    else (self.mlp(m), None))
        return x + self.post_feedforward_layernorm(f), stats

    def forward_with_cache(self, x, cache, valid=None, last_idx=None):
        """(x, cache) of a prefill; what is row-wise here (the norms, the
        residuals, the FFN) runs over the prompt's row blocks alone."""
        a = _rows(self.input_layernorm, last_idx, x)
        mixed, cache = (
            self.linear_attn.forward_with_cache(a, cache, last_idx)
            if self.linear else
            self.self_attn.forward_with_cache(a, cache, last_idx=last_idx))

        def after(x, mixed, valid=None):
            return self._rest(x, mixed, valid)[0]

        # the routed experts read every held expert's weights once a call:
        # their layers' blocks are as wide as the experts take whole
        block = self.mlp.experts.token_block if self.sparse else None
        rest = () if valid is None else (valid,)
        return _rows(after, last_idx, x, mixed, *rest, block=block), cache

    def forward_decode_paged(self, x, cache, page_table, lens, live):
        a = self.input_layernorm(x)
        mixed, cache = (
            self.linear_attn.forward_decode(a, cache, live) if self.linear
            else self.self_attn.forward_decode_paged(a, cache, page_table,
                                                     lens, live))
        x, stats = self._rest(x, mixed, live[:, None])
        return x, cache, stats


class GigaChat35Model(Layer):
    def __init__(self, config: GigaChat35Config):
        super().__init__(dtype=config.dtype)
        from ..nn.layer.container import LayerList

        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = LayerList([GigaChat35DecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = _norm(config, config.hidden_size)

    def forward_with_cache(self, input_ids, caches, pos=0, last_idx=None):
        if not (isinstance(pos, int) and pos == 0):
            raise NotImplementedError(
                "prefill at an offset (chunked prefill, a warm prefix hit) "
                "is not implemented beside a recurrent state: it would "
                "need the state at the offset")
        x = self.embed_tokens(input_ids)
        s = x.shape[1]
        # bucket padding past the prompt's last token takes no expert
        valid = (None if last_idx is None
                 else (jnp.arange(s) <= last_idx)[None, :])
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            x, cache = layer.forward_with_cache(x, cache, valid=valid,
                                                last_idx=last_idx)
            new_caches.append(cache)
        if last_idx is not None:
            # only the position that is sampled goes through the head
            x = apply_op(lambda v: jax.lax.dynamic_slice_in_dim(
                v, last_idx, 1, axis=1), x, op_name="last_position")
        return self.norm(x), new_caches

    def forward_decode_paged(self, input_ids, caches, page_table, lens,
                             live):
        x = self.embed_tokens(input_ids)
        # a row at its last position writes there again, never past it
        ps = next(cache[0].shape[1] for layer, cache
                  in zip(self.layers, caches) if not layer.linear)
        lens = jnp.minimum(lens, page_table.shape[1] * ps - 1)
        counts = {"experts_hit": jnp.int32(0),
                  "expert_rows_max": jnp.int32(0),
                  "expert_rows_here": jnp.int32(0)}
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            x, cache, stats = layer.forward_decode_paged(
                x, cache, page_table, lens, live)
            new_caches.append(cache)
            if stats is not None:
                counts = {k: v + _val(stats[k]) if k in stats else v
                          for k, v in counts.items()}
        # the cache rows one full layer's decode attends this step, and the
        # (row, step) pairs whose state a linear layer updates
        counts["latent_rows_attended"] = jnp.sum(
            jnp.where(live, lens + 1, 0)).astype(jnp.int32)
        counts["state_rows"] = jnp.sum(live).astype(jnp.int32)
        return self.norm(x), new_caches, counts


class GigaChat35ForCausalLM(Layer):
    def __init__(self, config: GigaChat35Config):
        super().__init__(dtype=config.dtype)
        self.config = config
        from ..core.dtype import get_default_dtype, set_default_dtype

        prev = get_default_dtype()
        set_default_dtype(config.dtype)  # params honor the config dtype
        try:
            self.model = GigaChat35Model(config)
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=False)
        finally:
            set_default_dtype(prev)

    def _logits(self, hidden):
        """The head's product with a float32 result, whatever the weights'
        dtype (as ``models/afmoe.py`` and for its reason)."""
        return apply_op(
            lambda h, w: jnp.matmul(h, w, preferred_element_type=F32),
            hidden, self.lm_head.weight, op_name="lm_head")

    def forward(self, input_ids):
        """Logits [B, S, V] of a whole sequence, no cache kept. Inference
        only (no tape): the kernels have no backward."""
        from ..core.autograd import no_grad

        ids = _val(input_ids)
        with no_grad():
            logits, _ = self.forward_with_cache(
                input_ids, self.init_cache(ids.shape[0], ids.shape[1]), 0)
        return logits

    def _row_entry(self, *lead):
        cfg = self.config
        return (jnp.zeros(lead + (cfg.cache_row,), jnp.dtype(cfg.dtype)),)

    def init_cache(self, batch_size: int, max_len: int):
        cfg = self.config
        return [gdn_state_entry(cfg, batch_size) if cfg.is_linear(i)
                else self._row_entry(batch_size, max_len)
                for i in range(cfg.num_hidden_layers)]

    def forward_with_cache(self, input_ids, caches, pos=0, last_idx=None):
        """(logits, new_caches) of a one-shot prefill from position 0.
        ``last_idx`` (a traced position): logits [B, 1, V] of that position
        only; the padding after it is routed nowhere, changes no state and
        scores nothing."""
        hidden, caches = self.model.forward_with_cache(
            input_ids, caches, pos, last_idx=last_idx)
        return self._logits(hidden), caches

    def paged_layout(self, page_size: int) -> dict:
        """What the paged engine has to know of this model's cache: one
        table and no ring; which layers keep a fixed-size state a ROW and
        no pages; the others' pages hold latent rows, no heads; prefill
        takes ``last_idx`` and runs its row-wise work in blocks; a decode
        step hands out counters."""
        cfg = self.config
        return {"ring": None, "last_idx": True, "counters": True,
                "prefill_row_block": PREFILL_ROW_BLOCK,
                "state_layers": tuple(cfg.is_linear(i)
                                      for i in range(cfg.num_hidden_layers)),
                "rows": "latent rows (one compressed KV row a token, no "
                        "heads) of its full-attention layers, beside a "
                        "recurrent state a row"}

    def init_paged_cache(self, num_pages: int, page_size: int,
                         state_rows: int = 0):
        """Per layer: a full layer's page pool (rows [pages, page,
        cache_row],), or a linear layer's (states, convolution inputs) of
        ``state_rows`` rows."""
        cfg = self.config
        return [gdn_state_entry(cfg, state_rows) if cfg.is_linear(i)
                else self._row_entry(num_pages, page_size)
                for i in range(cfg.num_hidden_layers)]

    def forward_decode_paged(self, input_ids, caches, page_table, lens,
                             live):
        """(logits [B, 1, V], new_caches, counters) — one decode step: the
        rows ARE the engine's slots, so a linear layer's entry is indexed
        by row."""
        hidden, caches, counts = self.model.forward_decode_paged(
            input_ids, caches, page_table, lens, live)
        return self._logits(hidden), caches, counts
