"""A decoder whose layers are gated-delta-rule linear attention, with a
full-attention layer every fourth (``model_type: olmo_hybrid``), for the
paged serving engine.

``layer_types[l]`` names layer ``l``'s mixer. Every norm is an RMSNorm with
a weight; a norm FOLLOWS each sublayer (the Olmo family's block):

    x = x + N_a(mixer(x))
    x = x + N_f(Wd(silu(Wg x) * Wu x))

    linear_attention (H heads, dk key and dv value dims a head):
      u      = x [Wq | Wk | Wv]
      c_t    = silu(sum_j w_conv[:, j] u_{t-K+1+j})      # causal, depthwise
      q,k,v  = split(c) ;  q,k = l2norm(q) dk^-0.5, l2norm(k)
      beta   = 2 sigmoid(x Wb) ;  g = -exp(A_log) softplus(x Wa + dt_bias)
      o      = the gated delta rule over (q, k, v, g, beta)   # ops/gated_delta_rule
      y      = (N_o(o) * silu(x Wz)) Wo
    full_attention:
      q,k,v  = N_q(x Wq), N_k(x Wk), x Wv        # norms over the whole width
      y      = causal_softmax(q k^T / sqrt(head_dim)) v Wo   # no rotary embedding

Float32: ``g``, ``beta``, the L2 norms, the state and every accumulation
into it, the gated norm's statistics, QK-norm, the head's logits. Weights
and activations are the configuration's dtype.

The cache of a linear layer is FIXED in size: the state ``[H, dk, dv]``
(float32) and the last ``K - 1`` pre-convolution inputs ``u``; it belongs
to a ROW (an engine slot), not to pages. A full layer keeps per-head K and
V in pages. ``paged_layout`` says which layers are which, and the engine
keeps the states as ``[max_batch, ...]`` arrays beside the page pools.
What the engine cannot do with a recurrent state (it is not a function of
a prefix's pages) it refuses at construction.
``tests/reference_gdn_hybrid_decoder.py`` is the plain reference.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.autograd import apply_op
from ..core.tensor import Tensor
from ..distributed.fleet.layers.mpu import (ColumnParallelLinear,
                                            RowParallelLinear,
                                            VocabParallelEmbedding)
from ..nn.initializer import Constant, Initializer
from ..nn.layer.layers import Layer
from ..nn.layer.norm import (RMSNorm, ZeroCenteredGatedNorm,
                             zero_centered_scale)
from ..ops import gated_delta_rule as gdn
from .llama import LlamaMLP

__all__ = ["OlmoHybridConfig", "OlmoHybridModel", "OlmoHybridForCausalLM",
           "GatedDeltaNet", "gdn_conv_dim", "gdn_state_entry"]

LINEAR, FULL = "linear_attention", "full_attention"
F32 = jnp.float32


@dataclass
class OlmoHybridConfig:
    """The published ``config.json`` keys, every one a field (a key the
    file has and the class lacks would be dropped in silence by a caller
    that filters on fields). Values this implementation does not compute
    are refused in ``__post_init__``, not ignored."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    hidden_act: str = "silu"
    max_position_embeddings: int = 65536
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    # the first num_hidden_layers entries are used (a longer list is a
    # depth cut laid over the published pattern)
    layer_types: Optional[list] = None
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rope_parameters: dict = field(default_factory=lambda: {"rope_theta": None})
    model_type: str = "olmo_hybrid"
    dtype: str = "float32"

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = [FULL if (i + 1) % 4 == 0 else LINEAR
                                for i in range(self.num_hidden_layers)]
        unknown = set(self.layer_types) - {LINEAR, FULL}
        if unknown or len(self.layer_types) < self.num_hidden_layers:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers as "
                f"{LINEAR!r} or {FULL!r}; got {self.layer_types!r}")
        for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                          ("tie_word_embeddings", False),
                          ("linear_num_value_heads",
                           self.linear_num_key_heads)):
            if getattr(self, key) != want:
                raise ValueError(
                    f"{key}={getattr(self, key)!r} is not implemented "
                    f"(only {want!r})")
        if (self.rope_parameters or {}).get("rope_theta") is not None:
            raise ValueError(
                "a rotary embedding on the full-attention layers is not "
                "implemented (rope_parameters.rope_theta must be null: "
                "position comes from the recurrence)")

    def is_linear(self, layer: int) -> bool:
        return self.layer_types[layer] == LINEAR

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def cache_kv_heads(self) -> int:
        """KV heads a cache row is stored with: the chip lays a pool's
        head axis out in tiles of 16 (bf16) and copies a page only whole
        tiles wide, so a count past 8 that does not fill them (30) is
        stored as the next multiple of 16, zeros after the heads."""
        n = self.num_key_value_heads
        return n if n <= 8 else -(-n // 16) * 16

    @property
    def conv_dim(self) -> int:
        """The width of ``u``: q | k | v of every head."""
        return gdn_conv_dim(self)


def gdn_conv_dim(cfg) -> int:
    """The width of a linear layer's ``u``: q and k of every key head, v of
    every value head."""
    return (cfg.linear_num_key_heads * 2 * cfg.linear_key_head_dim
            + cfg.linear_num_value_heads * cfg.linear_value_head_dim)


def gdn_state_entry(cfg, rows: int):
    """A linear layer's cache for ``rows`` rows, zeros: (the float32 state
    [rows, value heads, dk, dv], the last K - 1 inputs of the convolution
    [rows, K - 1, conv width] in the configuration's dtype)."""
    return (jnp.zeros((rows, cfg.linear_num_value_heads,
                       cfg.linear_key_head_dim,
                       cfg.linear_value_head_dim), F32),
            jnp.zeros((rows, cfg.linear_conv_kernel_dim - 1,
                       gdn_conv_dim(cfg)), jnp.dtype(cfg.dtype)))


class _LogUniform(Initializer):
    """``A_log = log(A)``, ``A`` uniform in (0, high): the decay rates'
    published initialiser."""

    def __init__(self, high: float):
        self.high = high

    def _generate(self, key, shape, dtype):
        a = jax.random.uniform(key, tuple(shape), F32, 1e-3, self.high)
        return jnp.log(a).astype(dtype)


def _val(t):
    return t.value if isinstance(t, Tensor) else t


def _rms(x, w, eps):
    """RMSNorm over the last axis in float32 (x float32)."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _f32_product(x, w):
    """x w with a float32 result from the operands as they are."""
    return jnp.matmul(x, w, preferred_element_type=F32)


class GatedDeltaNet(Layer):
    """The linear-attention mixer. Its cache is ``(state [B, Hv, dk, dv]
    float32, rows [B, K-1, conv_dim])``.

    ``linear_num_value_heads`` may be a multiple of ``linear_num_key_heads``
    (grouped heads: value head j reads key head j // (Hv / Hk); the gates,
    the decay rates and the state are a value head's). ``gating`` (static)
    is the output's: ``"silu"`` (this module's block: ``N_o(o) * silu(z)``,
    ``N_o`` an RMSNorm with a weight) or ``"sigmoid_zero_centered"``
    (``N_o(o) * 2 sigmoid(z)``, ``N_o`` a zero-centred gated norm:
    ``nn.layer.norm.ZeroCenteredGatedNorm``), with ``norm_eps`` (None =
    ``rms_norm_eps``)."""

    GATINGS = ("silu", "sigmoid_zero_centered")

    def __init__(self, config, gating: str = "silu", norm_eps=None):
        super().__init__(dtype=config.dtype)
        if gating not in self.GATINGS:
            raise ValueError(f"gating={gating!r} is not implemented (only "
                             f"{self.GATINGS})")
        self.config = config
        self.gating = gating
        self.norm_eps = config.rms_norm_eps if norm_eps is None else norm_eps
        h, heads = config.hidden_size, config.linear_num_value_heads
        v_width = heads * config.linear_value_head_dim
        conv_dim = gdn_conv_dim(config)
        lin = dict(has_bias=False, gather_output=False)
        self.in_proj_qkv = ColumnParallelLinear(h, conv_dim, **lin)
        self.in_proj_z = ColumnParallelLinear(h, v_width, **lin)
        self.in_proj_b = ColumnParallelLinear(h, heads, **lin)
        self.in_proj_a = ColumnParallelLinear(h, heads, **lin)
        self.conv1d = self.create_parameter(
            [conv_dim, config.linear_conv_kernel_dim])
        self.A_log = self.create_parameter(
            [heads], dtype="float32", default_initializer=_LogUniform(16.0))
        self.dt_bias = self.create_parameter(
            [heads], dtype="float32", default_initializer=Constant(1.0))
        self.norm = (RMSNorm(config.linear_value_head_dim,
                             epsilon=self.norm_eps) if gating == "silu"
                     else ZeroCenteredGatedNorm(config.linear_value_head_dim,
                                                epsilon=self.norm_eps))
        self.out_proj = RowParallelLinear(v_width, h, has_bias=False,
                                          input_is_parallel=True)

    def _gates(self, x, wa, wb, a_log, dt_bias):
        """(g, beta) [..., H] float32 of activations x [..., hidden]."""
        cfg = self.config
        beta = jax.nn.sigmoid(_f32_product(x, wb))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(a_log.astype(F32)) * jax.nn.softplus(
            _f32_product(x, wa) + dt_bias.astype(F32))
        return g, beta

    def _split(self, c):
        """The convolution's output [..., conv_dim] -> q, k [..., Hk, dk]
        (L2-normed, q scaled; float32) and v [..., Hv, dv]."""
        cfg = self.config
        heads, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
        lead = c.shape[:-1]
        q, k, v = jnp.split(c, [heads * dk, 2 * heads * dk], axis=-1)
        q = gdn.l2norm(q.reshape(lead + (heads, dk)).astype(F32))
        k = gdn.l2norm(k.reshape(lead + (heads, dk)).astype(F32))
        return q * dk ** -0.5, k, v.reshape(
            lead + (cfg.linear_num_value_heads, -1))

    def _out(self, o, z, norm_w):
        """(N_o(o) * gate(z)) as [..., Hv*dv] in z's dtype; o [..., Hv, dv]
        float32."""
        if self.gating == "silu":
            o = _rms(o.astype(F32), norm_w, self.norm_eps)
            gate = jax.nn.silu(z.astype(F32)).reshape(o.shape)
        else:
            o = _rms(o.astype(F32), zero_centered_scale(norm_w),
                     self.norm_eps)
            gate = 2.0 * jax.nn.sigmoid(z.astype(F32)).reshape(o.shape)
        return (o * gate).reshape(z.shape).astype(z.dtype)

    def _weights(self):
        return (self.in_proj_a.weight, self.in_proj_b.weight, self.A_log,
                self.dt_bias, self.conv1d, self.norm.weight)

    def forward_with_cache(self, x, cache, last_idx=None):
        """Prefill from position 0 (state zero): x [B, S, h]. Returns
        (out, (the state after ``last_idx``, the K-1 inputs that end
        there)); ``last_idx`` None = the last position."""
        s = x.shape[1]
        last = s - 1 if last_idx is None else last_idx
        width = self.config.linear_conv_kernel_dim

        def mix(xv, u, z, wa, wb, a_log, dt_bias, conv_w, norm_w):
            q, k, v = self._split(gdn.causal_conv(u, conv_w))
            g, beta = self._gates(xv, wa, wb, a_log, dt_bias)
            o, state = gdn.gdn_chunk_prefill(q, k, v, g, beta, last)
            return (self._out(o, z, norm_w), state,
                    gdn.conv_rows(u, last, width))

        y, state, rows = apply_op(
            mix, x, self.in_proj_qkv(x), self.in_proj_z(x), *self._weights(),
            op_name="gated_delta_prefill")
        return self.out_proj(y), (_val(state),
                                  _val(rows).astype(cache[1].dtype))

    def forward_decode(self, x, cache, live):
        """One token a row: x [R, 1, h]; ``cache`` the rows' (state
        [R, H, dk, dv], inputs [R, K-1, conv_dim]). A dead row's state and
        inputs stay as they are."""
        def mix(xv, u, z, wa, wb, a_log, dt_bias, conv_w, norm_w, state,
                rows):
            c, shifted = gdn.conv_step(rows, u[:, 0], conv_w)
            q, k, v = self._split(c)
            g, beta = self._gates(xv[:, 0], wa, wb, a_log, dt_bias)
            o, state = gdn.gdn_decode_step(state, q, k, v.astype(F32), g,
                                           beta, live)
            rows = jnp.where(live[:, None, None], shifted, rows)
            return self._out(o[:, None], z, norm_w), state, rows

        y, state, rows = apply_op(
            mix, x, self.in_proj_qkv(x), self.in_proj_z(x), *self._weights(),
            *cache, op_name="gated_delta_decode")
        return self.out_proj(y), (_val(state), _val(rows))


class OlmoHybridAttention(Layer):
    """Full attention with QK-norm over the whole width and no position
    encoding. Its cache is per-head K and V."""

    def __init__(self, config: OlmoHybridConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
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
        self.q_norm = RMSNorm(self.num_heads * hd,
                              epsilon=config.rms_norm_eps)
        self.k_norm = RMSNorm(self.kv_heads * hd,
                              epsilon=config.rms_norm_eps)

    def _heads(self, qv, kv, vv, qw, kw):
        """Projections [B, S, H*D] -> normed heads in the cache dtype."""
        b, s = qv.shape[0], qv.shape[1]
        hd, eps = self.config.head_dim, self.config.rms_norm_eps
        qh = _rms(qv.astype(F32), qw, eps).astype(qv.dtype)
        kh = _rms(kv.astype(F32), kw, eps).astype(kv.dtype)
        return (qh.reshape(b, s, self.num_heads, hd),
                kh.reshape(b, s, self.kv_heads, hd),
                vv.reshape(b, s, self.kv_heads, hd))

    def _stored(self, heads, per_kv: int = 1):
        """[..., kv_heads x per_kv, D] -> the cache's head count x
        ``per_kv`` (``cache_kv_heads``), zeros after the heads."""
        extra = (self.config.cache_kv_heads - self.kv_heads) * per_kv
        if not extra:
            return heads
        return jnp.pad(heads, ((0, 0),) * (heads.ndim - 2)
                       + ((0, extra), (0, 0)))

    def _project(self, x):
        return (self.q_proj(x), self.k_proj(x), self.v_proj(x),
                self.q_norm.weight, self.k_norm.weight)

    def forward_with_cache(self, x, cache):
        """Prefill from position 0: ``cache`` (k, v) [B, S_max, Hkv, D]
        takes the prompt's keys and values at [0, S)."""
        from ..ops.pallas import flash_attention

        b, s = x.shape[0], x.shape[1]

        def attend(qv, kv, vv, qw, kw, kc, vc):
            qh, kh, vh = self._heads(qv, kv, vv, qw, kw)
            ctx = flash_attention(qh, kh, vh, causal=True)
            kc = jax.lax.dynamic_update_slice_in_dim(
                kc, self._stored(kh).astype(kc.dtype), 0, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(
                vc, self._stored(vh).astype(vc.dtype), 0, axis=1)
            return ctx.reshape(b, s, -1), kc, vc

        ctx, kc, vc = apply_op(attend, *self._project(x), *cache,
                               op_name="cached_attention")
        return self.o_proj(ctx), (_val(kc), _val(vc))

    def forward_decode_paged(self, x, cache, page_table, lens, live):
        """One token per row at per-row position ``lens``, through the
        row's pages."""
        from ..ops.paged_attention import paged_decode_mha

        b = x.shape[0]

        def attend(qv, kv, vv, qw, kw, kp, vp):
            ps, cols = kp.shape[1], page_table.shape[1]
            qh, kh, vh = self._heads(qv, kv, vv, qw, kw)
            page = page_table[jnp.arange(b),
                              jnp.minimum(lens // ps, cols - 1)]
            # dead rows / unmapped pages -> sentinel, dropped by scatter
            page = jnp.where(live & (page >= 0), page, kp.shape[0])
            kp = kp.at[page, lens % ps].set(
                self._stored(kh[:, 0]).astype(kp.dtype), mode="drop")
            vp = vp.at[page, lens % ps].set(
                self._stored(vh[:, 0]).astype(vp.dtype), mode="drop")
            # a dead row attends nothing: length 0 costs the kernel no page
            ctx = paged_decode_mha(
                self._stored(qh[:, 0], self.num_heads // self.kv_heads),
                kp, vp, page_table, jnp.where(live, lens + 1, 0))
            ctx = ctx[:, :self.num_heads]
            return ctx.reshape(b, 1, -1).astype(qv.dtype), kp, vp

        ctx, kp, vp = apply_op(attend, *self._project(x), *cache,
                               op_name="paged_attention")
        return self.o_proj(ctx), (_val(kp), _val(vp))


class OlmoHybridDecoderLayer(Layer):
    def __init__(self, config: OlmoHybridConfig, index: int):
        super().__init__(dtype=config.dtype)
        self.linear = config.is_linear(index)
        if self.linear:
            self.linear_attn = GatedDeltaNet(config)
        else:
            self.self_attn = OlmoHybridAttention(config)
        self.mlp = LlamaMLP(config)
        h, eps = config.hidden_size, config.rms_norm_eps
        self.post_attention_layernorm = RMSNorm(h, epsilon=eps)
        self.post_feedforward_layernorm = RMSNorm(h, epsilon=eps)

    def _rest(self, x, mixed):
        x = x + self.post_attention_layernorm(mixed)
        return x + self.post_feedforward_layernorm(self.mlp(x))

    def forward_with_cache(self, x, cache, last_idx=None):
        mixed, cache = (
            self.linear_attn.forward_with_cache(x, cache, last_idx)
            if self.linear else self.self_attn.forward_with_cache(x, cache))
        return self._rest(x, mixed), cache

    def forward_decode_paged(self, x, cache, page_table, lens, live):
        mixed, cache = (
            self.linear_attn.forward_decode(x, cache, live) if self.linear
            else self.self_attn.forward_decode_paged(x, cache, page_table,
                                                     lens, live))
        return self._rest(x, mixed), cache


class OlmoHybridModel(Layer):
    def __init__(self, config: OlmoHybridConfig):
        super().__init__(dtype=config.dtype)
        from ..nn.layer.container import LayerList

        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = LayerList([OlmoHybridDecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward_with_cache(self, input_ids, caches, pos=0, last_idx=None):
        if not (isinstance(pos, int) and pos == 0):
            raise NotImplementedError(
                "prefill at an offset (chunked prefill, a warm prefix hit) "
                "is not implemented beside a recurrent state: it would "
                "need the state at the offset")
        x = self.embed_tokens(input_ids)
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            x, cache = layer.forward_with_cache(x, cache, last_idx)
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
        page_size = next((cache[0].shape[1] for layer, cache
                          in zip(self.layers, caches) if not layer.linear),
                         1)
        lens = jnp.minimum(lens, page_table.shape[1] * page_size - 1)
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            x, cache = layer.forward_decode_paged(x, cache, page_table,
                                                  lens, live)
            new_caches.append(cache)
        # the (row, step) pairs whose state a linear layer updates
        return (self.norm(x), new_caches,
                {"state_rows": jnp.sum(live).astype(jnp.int32)})


class OlmoHybridForCausalLM(Layer):
    def __init__(self, config: OlmoHybridConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        from ..core.dtype import get_default_dtype, set_default_dtype

        prev = get_default_dtype()
        set_default_dtype(config.dtype)  # params honor the config dtype
        try:
            self.model = OlmoHybridModel(config)
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=False)
        finally:
            set_default_dtype(prev)

    def _logits(self, hidden):
        """The head's product with a float32 result, whatever the weights'
        dtype (as ``models/afmoe.py`` and for its reason)."""
        return apply_op(_f32_product, hidden, self.lm_head.weight,
                        op_name="lm_head")

    def forward(self, input_ids):
        """Logits [B, S, V] of a whole sequence, no cache kept. Inference
        only (no tape): the chunked scan has no backward."""
        from ..core.autograd import no_grad

        ids = _val(input_ids)
        with no_grad():
            logits, _ = self.forward_with_cache(
                input_ids, self.init_cache(ids.shape[0], ids.shape[1]), 0)
        return logits

    def _state_entry(self, rows: int):
        """A linear layer's cache for ``rows`` rows, zeros."""
        return gdn_state_entry(self.config, rows)

    def _kv_entry(self, *lead):
        cfg = self.config
        shape = lead + (cfg.cache_kv_heads, cfg.head_dim)
        dt = jnp.dtype(cfg.dtype)
        return (jnp.zeros(shape, dt), jnp.zeros(shape, dt))

    def init_cache(self, batch_size: int, max_len: int):
        cfg = self.config
        return [self._state_entry(batch_size) if cfg.is_linear(i)
                else self._kv_entry(batch_size, max_len)
                for i in range(cfg.num_hidden_layers)]

    def forward_with_cache(self, input_ids, caches, pos=0, last_idx=None):
        """(logits, new_caches) of a one-shot prefill from position 0.
        ``last_idx`` (a traced position): logits [B, 1, V] of that
        position only; a linear layer's new cache is its state AFTER that
        position, and the padding past it changes no state."""
        hidden, caches = self.model.forward_with_cache(
            input_ids, caches, pos, last_idx=last_idx)
        return self._logits(hidden), caches

    def paged_layout(self, page_size: int) -> dict:
        """What the paged engine has to know of this model's cache: one
        table and no ring; which layers keep a fixed-size state a ROW and
        no pages; prefill takes ``last_idx``; a decode step hands out
        counters."""
        cfg = self.config
        return {"ring": None, "last_idx": True, "counters": True,
                "state_layers": tuple(cfg.is_linear(i)
                                      for i in range(cfg.num_hidden_layers)),
                "rows": "per-head K and V of its full-attention layers "
                        "only, beside a recurrent state a row (a linear-"
                        "attention layer's state is no function of a "
                        "prefix's pages)"}

    def init_paged_cache(self, num_pages: int, page_size: int,
                         state_rows: int = 0):
        """Per layer: a full layer's page pools (K, V), or a linear
        layer's (states, convolution inputs) of ``state_rows`` rows."""
        cfg = self.config
        return [self._state_entry(state_rows) if cfg.is_linear(i)
                else self._kv_entry(num_pages, page_size)
                for i in range(cfg.num_hidden_layers)]

    def forward_decode_paged(self, input_ids, caches, page_table, lens,
                             live):
        """(logits [B, 1, V], new_caches, counters) — one decode step:
        the rows ARE the engine's slots, so a linear layer's entry is
        indexed by row."""
        hidden, caches, counts = self.model.forward_decode_paged(
            input_ids, caches, page_table, lens, live)
        return self._logits(hidden), caches, counts
