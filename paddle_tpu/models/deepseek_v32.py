"""A latent-attention decoder with a learned sparse-attention indexer and
group-limited routed experts (``model_type: deepseek_v32``), as ONE chip's
share of an expert-parallel deployment, for the paged serving engine.

The block (every norm an RMSNorm but the indexer's key norm, a LayerNorm
with a bias; ``rope`` is YaRN, :func:`yarn_inv_freq`; the softmax scale is
``(nope + rope)^-0.5 * mscale^2``, :func:`yarn_mscale`):

    a      = N1(x)
    c_q    = Nq(a Wqa)
    q      = c_q Wqb -> heads x (nope | rope) ;  q_rope = rope(q_rope)
    ckv|kr = a Wkva ;  c = Nkv(ckv) ;  k_rope = rope(kr)      # one a token
    k_nope|v = c Wkvb -> heads x (nope | v)
    qI = c_q WqbI -> index heads x index dim, rope on the first rope dims
    kI = LayerNorm(a WkI), rope on its first rope dims        # one a token
    w  = (a Ww) * index_heads^-0.5 * index_dim^-0.5
    I[t,u] = sum_j w[t,j] relu(qI[t,j] . kI[u])               # u <= t
    S_t = the min(index_topk, t+1) positions u <= t of largest I[t,u]
    o   = concat_h softmax_{u in S_t}(s (q_nope.k_nope + q_rope.k_rope)) v
    x   = x + o Wo
    m   = N2(x)
    f   = dense(m)   or   shared(m) + routed(m)   # nn/layer/routed_experts
    x   = x + f

What a token leaves in the cache is the 576-wide row ``(c | k_rope)``, shared
by all heads (stored 640 wide, ``DeepseekV32Config.cache_row``), and the
128-wide ``kI``: a page holds no heads. Prefill expands ``k_nope | v``
head group by head group and attends under the selection's mask
(``ops/sparse_latent_attention.selected_attention``); decode scores the
row's pages with ``dsa_index_scores`` of that module, takes the top
``index_topk`` and attends over them in latent space, ``Wkvb`` absorbed
into the query and the output (the same mathematics). A prefill is told
where its prompt ends in the bucket (``last_idx``) and runs what is a
function of one position alone (projections, norms, ropes, the FFN) over the
row blocks up to there and over no other (``_live_rows``).

The share: ``ep_size`` chips hold one layer's ``n_routed_experts`` between
them. The router scores all of them; this chip (rank ``ep_rank``) holds
``n_routed_experts // ep_size`` from ``ep_rank`` times that on and adds
their part of the routed sum, the rest is the other chips' to add and no
code stands in for them. With ``ep_size`` 1 the layer is whole.

It implements the paged engine's model contract: ``forward``,
``init_cache`` / ``forward_with_cache`` (one-shot prefill from position 0),
``paged_layout``, ``init_paged_cache`` / ``forward_decode_paged``. The
multi-token-prediction layer is not built (``num_nextn_predict_layers``
is a field only). ``tests/reference_mla_dsa_decoder.py`` is the plain
reference.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.autograd import apply_op
from ..core.tensor import Tensor
from ..distributed.fleet.layers.mpu import (ColumnParallelLinear,
                                            RowParallelLinear,
                                            VocabParallelEmbedding)
from ..nn.layer.layers import Layer
from ..nn.layer.norm import LayerNorm, RMSNorm
from ..nn.layer.routed_experts import RoutedExperts
from ..ops.sparse_latent_attention import (dsa_index_scores, index_scores,
                                           selected_attention,
                                           sparse_latent_decode, top_k_mask)
from ._live_rows import live_rows, row_block
from .llama import LlamaMLP

__all__ = ["DeepseekV32Config", "DeepseekV32Model", "DeepseekV32ForCausalLM",
           "LatentAttention", "yarn_inv_freq", "yarn_mscale"]

INDEX_NORM_EPS = 1e-6       # the indexer's LayerNorm (assumed)
INDEX_QUERY_BLOCK = 32      # queries of one block of the indexer's scores
HEAD_GROUP = 16             # heads whose k_nope | v are expanded at a time
# Rows of one block of a prefill's row-wise work (``_live_rows``): what is a
# function of one position alone runs over the blocks at or before
# ``last_idx`` and over no other. One value for every bucket, chosen on the
# chip (PERF.md, PR 35).
PREFILL_ROW_BLOCK = 512


@dataclass
class DeepseekV32Config:
    """The published ``config.json`` keys, every one a field, plus
    ``ep_rank`` (which share of the experts this chip holds) and ``dtype``.
    Values this implementation does not compute are refused in
    ``__post_init__``, not ignored."""
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    first_k_dense_replace: int = 3
    moe_layer_freq: int = 1
    n_routed_experts: int = 256     # of the layer; ep_size chips share them
    ep_size: int = 1
    ep_rank: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    attention_bias: bool = False
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 1
    model_type: str = "deepseek_v32"
    dtype: str = "float32"

    def __post_init__(self):
        for key, want in (("scoring_func", "sigmoid"), ("hidden_act", "silu"),
                          ("topk_method", "noaux_tc"), ("moe_layer_freq", 1),
                          ("attention_bias", False),
                          ("tie_word_embeddings", False)):
            if getattr(self, key) != want:
                raise ValueError(
                    f"{key}={getattr(self, key)!r} is not implemented "
                    f"(only {want!r})")
        rs = self.rope_scaling
        if rs is not None and rs.get("type") != "yarn":
            raise ValueError(f"rope_scaling type {rs.get('type')!r} is not "
                             f"implemented (only 'yarn')")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank {self.ep_rank} outside the "
                             f"{self.ep_size} shares")
        for key in ("n_group", "ep_size"):
            if self.n_routed_experts % getattr(self, key):
                raise ValueError(f"{self.n_routed_experts} experts do not "
                                 f"divide by {key}={getattr(self, key)}")

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts // self.ep_size

    @property
    def cache_row(self) -> int:
        """Width of a token's row in the cache: ``c | k_rope`` and zeros up
        to a whole number of the chip's 128 lanes (576 -> 640). The chip
        lays a 576-wide array out 640 wide whatever is asked, and a pool
        handed over unpadded costs a copy of the whole pool in every
        program that takes it (PERF.md, PR 32)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        rs = self.rope_scaling
        if rs is None:
            return scale
        return scale * yarn_mscale(rs["factor"],
                                   rs.get("mscale_all_dim", 0)) ** 2


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: Optional[dict]):
    """Inverse frequencies [dim / 2] float32 of the rotary embedding. YaRN
    blends ``theta^(-2i/dim)`` (kept where a dimension turns more than
    ``beta_fast`` times in the original context) with the same / ``factor``
    (where it turns less than ``beta_slow`` times) by a linear ramp between
    the two dimensions."""
    inv = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    if scaling is None:
        return inv.astype(np.float32)
    orig = scaling["original_max_position_embeddings"]

    def turns_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (inv / scaling["factor"] * ramp + inv * (1.0 - ramp)).astype(
        np.float32)


def _val(t):
    return t.value if isinstance(t, Tensor) else t


def _angles(positions, inv_freq):
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    return jnp.cos(ang), jnp.sin(ang)


def rope_pairs(x, cos, sin):
    """Rotate neighbouring pairs (x0, x1), (x2, x3), ... of the last axis:
    the attention's rope. cos/sin broadcast to [..., D / 2]."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def rope_halves(x, cos, sin):
    """Rotate (x_i, x_{i + D/2}) of the last axis: the indexer's rope."""
    d2 = x.shape[-1] // 2
    a, b = x[..., :d2], x[..., d2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rows(fn, last_idx, *args, block=None):
    """``fn(*args)`` for a row-wise ``fn`` of Tensors or arrays [B, S, ...]
    that gives one Tensor: over the whole bucket with ``last_idx`` None,
    else over the row blocks at or before it (``_live_rows``; a later
    row is zero), blocks of ``block`` rows (None: ``PREFILL_ROW_BLOCK``)."""
    if last_idx is None:
        return fn(*args)
    tensors = [isinstance(a, Tensor) for a in args]

    def on_slabs(*slabs):
        return _val(fn(*(Tensor(v) if t else v
                         for t, v in zip(tensors, slabs))))

    vals = [_val(a) for a in args]
    return Tensor(live_rows(
        on_slabs, vals, last_idx + 1,
        row_block(vals[0].shape[1], block or PREFILL_ROW_BLOCK),
        in_axes=1, out_axes=1))


def _block(n: int, want: int) -> int:
    return want if n % want == 0 else n


def _segments(s: int, block: int):
    """[(start, stop)] cutting the queries into up to four runs of whole
    blocks: a run's queries see keys [0, stop), so a causal prefill pays
    for 5/8 of the square, not all of it."""
    seg = max(block, (s // 4) // block * block)
    return [(a, min(a + seg, s)) for a in range(0, s, seg)]


class DeepseekV32Indexer(Layer):
    """The indexer's three projections and its key norm."""

    def __init__(self, config: DeepseekV32Config):
        super().__init__(dtype=config.dtype)
        lin = dict(has_bias=False, gather_output=False)
        self.wq_b = ColumnParallelLinear(
            config.q_lora_rank, config.index_n_heads * config.index_head_dim,
            **lin)
        self.wk = ColumnParallelLinear(config.hidden_size,
                                       config.index_head_dim, **lin)
        self.k_norm = LayerNorm(config.index_head_dim,
                                epsilon=INDEX_NORM_EPS)
        self.weights_proj = ColumnParallelLinear(
            config.hidden_size, config.index_n_heads, **lin)


def row_page(page_table, lens, live, num_pages, page_size):
    """The pool page each row's token at ``lens`` goes to; a dead row or
    an unmapped page gives ``num_pages``, a sentinel that a scatter with
    ``mode="drop"`` drops."""
    page = page_table[jnp.arange(lens.shape[0]),
                      jnp.minimum(lens // page_size, page_table.shape[1] - 1)]
    return jnp.where(live & (page >= 0), page, num_pages)


class LatentAttention(Layer):
    """The latent attention's projections (``q_a | q_b``, ``kv_a | kv_b``,
    ``o``, the two latent norms) and what is computed with them alike
    whatever chooses the positions a query attends: a token's cache row,
    the query's heads, the expanded prefill attention, the query absorbed
    into the latent space and the context out of it. ``norm`` is the
    latent norms' class (its ``weight`` is what ``_rms`` is handed, as the
    caller makes it)."""

    def __init__(self, config, norm=RMSNorm):
        super().__init__(dtype=config.dtype)
        self.config = config
        cfg, h = config, config.hidden_size
        heads = cfg.num_attention_heads
        lin = dict(has_bias=False, gather_output=False)
        self.q_a_proj = ColumnParallelLinear(h, cfg.q_lora_rank, **lin)
        self.q_a_layernorm = norm(cfg.q_lora_rank, epsilon=cfg.rms_norm_eps)
        self.q_b_proj = ColumnParallelLinear(
            cfg.q_lora_rank,
            heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim), **lin)
        self.kv_a_proj_with_mqa = ColumnParallelLinear(
            h, cfg.kv_lora_rank + cfg.qk_rope_head_dim, **lin)
        self.kv_a_layernorm = norm(cfg.kv_lora_rank,
                                   epsilon=cfg.rms_norm_eps)
        self.kv_b_proj = ColumnParallelLinear(
            cfg.kv_lora_rank,
            heads * (cfg.qk_nope_head_dim + cfg.v_head_dim), **lin)
        self.o_proj = RowParallelLinear(heads * cfg.v_head_dim, h,
                                        has_bias=False,
                                        input_is_parallel=True)

    def _latent(self, a, cos, sin, wkva, nkv):
        """a [..., h] -> (c [..., kv_lora_rank], k_rope [..., rope]),
        normed and rotated in float32."""
        cfg = self.config
        kv = jnp.matmul(a, wkva, preferred_element_type=jnp.float32)
        c = _rms(kv[..., :cfg.kv_lora_rank], nkv, cfg.rms_norm_eps)
        kr = rope_pairs(kv[..., cfg.kv_lora_rank:], cos, sin)
        return c, kr

    def _as_row(self, c, kr, dtype):
        """The cache's row ``c | k_rope | zeros`` [..., cache_row], rounded
        once to ``dtype``."""
        pad = jnp.zeros(c.shape[:-1] + (self.config.cache_row - c.shape[-1]
                                        - kr.shape[-1],), jnp.float32)
        return jnp.concatenate([c, kr, pad], axis=-1).astype(dtype)

    def _query(self, cq, cos, sin, wqb, scale=None):
        """(q_nope [..., H, nope], q_rope [..., H, rope]) in cq's dtype,
        the rope in float32; ``scale`` multiplies both before they are
        rounded."""
        cfg = self.config
        n, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        q = jnp.matmul(cq, wqb, preferred_element_type=jnp.float32)
        if scale is not None:
            q = q * scale
        q = q.reshape(q.shape[:-1] + (-1, n + r))
        qr = rope_pairs(q[..., n:], cos[..., None, :], sin[..., None, :])
        return q[..., :n].astype(cq.dtype), qr.astype(cq.dtype)

    def _absorbed_query(self, cq, cos, sin, wqb, wkvb):
        """Decode's query in latent space: (q_lat [B, H, kv_lora_rank]
        = q_nope Wkvb_k^T, float32 and rounded once to cq's dtype, q_rope
        [B, H, rope], Wkvb as [kv_lora_rank, H, nope + v])."""
        cfg = self.config
        n, v, c = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
        qn, qr = self._query(cq, cos, sin, wqb)
        up = wkvb.reshape(c, -1, n + v)
        q_lat = jnp.einsum("bhn,chn->bhc", qn, up[..., :n],
                           preferred_element_type=jnp.float32)
        return q_lat.astype(cq.dtype), qr, up

    def _latent_out(self, ctx, up, dtype):
        """A context in latent space [B, H, kv_lora_rank] -> the heads'
        values [B, H, v] float32 (Wkvb_v, ``ctx`` rounded to ``dtype``)."""
        return jnp.einsum("bhc,chv->bhv", ctx.astype(dtype),
                          up[..., self.config.qk_nope_head_dim:],
                          preferred_element_type=jnp.float32)

    def _attend_expanded(self, cq, row, mask, pos, wqb, wkvb, last_idx):
        """Attention with expanded heads: cq [S, q_lora_rank], row [S, 640]
        (the cache's rows), mask [S, S] or None (causal), pos [S]. Returns
        [H, S, v] in row's dtype, heads first as the kernel gives them. A
        group of heads at a time has its queries made and its
        ``k_nope | v`` expanded from the live rows, in the kernel's layout,
        and goes through ``selected_attention``."""
        cfg = self.config
        s, heads = cq.shape[0], cfg.num_attention_heads
        n, r, v, c = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim, cfg.kv_lora_rank)
        hg = _block(heads, HEAD_GROUP)
        last = s - 1 if last_idx is None else last_idx
        live = None if last_idx is None else last_idx + 1
        if mask is not None:
            mask = mask.astype(jnp.int8)

        def group(g, ctx):
            w = jax.lax.dynamic_slice_in_dim(wkvb, g * hg * (n + v),
                                             hg * (n + v), axis=1)
            wq = jax.lax.dynamic_slice_in_dim(wqb, g * hg * (n + r),
                                              hg * (n + r), axis=1)

            def operands(cq, row, pos):
                cos, sin = _angles(pos, self.inv_freq)
                kv = jnp.matmul(row[:, :c], w).reshape(-1, hg, n + v)
                k = jnp.concatenate(
                    [kv[..., :n], jnp.broadcast_to(
                        row[:, None, c:c + r], kv.shape[:2] + (r,))],
                    axis=-1)
                # the softmax's scale rides in q, folded in before q is
                # rounded
                q = jnp.concatenate(self._query(
                    cq, cos, sin, wq, scale=cfg.softmax_scale), axis=-1)
                return tuple(jnp.swapaxes(t, 0, 1)
                             for t in (q, k, kv[..., n:]))

            q, k, val = live_rows(operands, (cq, row, pos), live,
                                  row_block(s, PREFILL_ROW_BLOCK),
                                  out_axes=1)
            return jax.lax.dynamic_update_slice_in_dim(
                ctx, selected_attention(q, k, val, mask, last), g * hg,
                axis=0)

        return jax.lax.fori_loop(0, heads // hg, group,
                                 jnp.zeros((heads, s, v), row.dtype))


class DeepseekV32Attention(LatentAttention):
    def __init__(self, config: DeepseekV32Config):
        super().__init__(config)
        cfg = config
        self.indexer = DeepseekV32Indexer(config)
        self.inv_freq = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                                      cfg.rope_scaling)

    def _weights(self):
        ix = self.indexer
        return (self.q_a_proj.weight, self.q_a_layernorm.weight,
                self.q_b_proj.weight, self.kv_a_proj_with_mqa.weight,
                self.kv_a_layernorm.weight, self.kv_b_proj.weight,
                self.o_proj.weight, ix.wq_b.weight, ix.wk.weight,
                ix.k_norm.weight, ix.k_norm.bias, ix.weights_proj.weight)

    # -- what a token leaves in the cache, and what asks for it -------------
    def _cached(self, a, cos, sin, wkva, nkv, wki, lnw, lnb):
        """a [..., h] -> the cache's row (c | k_rope | zeros) [..., 640] and
        the indexer's key [..., 128], normed and rotated in float32 and
        rounded once, to ``a``'s dtype."""
        cfg = self.config
        c, kr = self._latent(a, cos, sin, wkva, nkv)
        ki = jnp.matmul(a, wki, preferred_element_type=jnp.float32)
        mu = jnp.mean(ki, axis=-1, keepdims=True)
        var = jnp.mean((ki - mu) ** 2, axis=-1, keepdims=True)
        ki = (ki - mu) * jax.lax.rsqrt(var + INDEX_NORM_EPS) \
            * lnw.astype(jnp.float32) + lnb.astype(jnp.float32)
        r = cfg.qk_rope_head_dim
        ki = jnp.concatenate([rope_halves(ki[..., :r], cos, sin),
                              ki[..., r:]], axis=-1)
        return self._as_row(c, kr, a.dtype), ki.astype(a.dtype)

    def _index_query(self, a, cq, cos, sin, wqbi, ww):
        """The indexer's queries [..., Hi, Di] float32 (rope on the first
        rope dims of each) and head weights [..., Hi] float32."""
        cfg = self.config
        hi, di, r = cfg.index_n_heads, cfg.index_head_dim, \
            cfg.qk_rope_head_dim
        qi = jnp.matmul(cq, wqbi, preferred_element_type=jnp.float32)
        qi = qi.reshape(qi.shape[:-1] + (hi, di))
        qi = jnp.concatenate(
            [rope_halves(qi[..., :r], cos[..., None, :], sin[..., None, :]),
             qi[..., r:]], axis=-1)
        w = jnp.matmul(a, ww, preferred_element_type=jnp.float32) \
            * (hi ** -0.5 * di ** -0.5)
        return qi, w

    # -- prefill ---------------------------------------------------------------
    def _selection(self, a, cq, ki, cos, sin, wqbi, ww, last_idx):
        """The mask [S, S] of the positions each query attends: causal, and
        where a query sees more than ``index_topk`` positions the
        ``index_topk`` of largest indexer score. None = causal alone (no
        query of the bucket sees more). A block of queries at a time has
        its indexer queries made and scored against the keys its run of
        the sequence can see. Queries past ``last_idx`` (bucket padding)
        score nothing; their rows of the mask are False."""
        cfg = self.config
        s = a.shape[0]
        if s <= cfg.index_topk:
            return None
        bq = _block(s, INDEX_QUERY_BLOCK)
        out = []
        for start, stop in _segments(s, bq):
            keys = ki[:stop]
            upos = jnp.arange(stop)[None, :]

            def block(args, keys=keys, upos=upos, stop=stop):
                q0, ab, cqb, cb, sb = args

                def scored():
                    qi, w = self._index_query(ab, cqb, cb, sb, wqbi, ww)
                    sc = index_scores(qi.astype(keys.dtype), w, keys)
                    tpos = q0 + jnp.arange(bq)[:, None]
                    causal = upos <= tpos
                    sc = jnp.where(causal, sc, -jnp.inf)
                    k = jnp.minimum(tpos[:, 0] + 1, cfg.index_topk)
                    return top_k_mask(sc, k) & causal

                if last_idx is None:
                    return scored()
                return jax.lax.cond(q0 <= last_idx, scored,
                                    lambda: jnp.zeros((bq, stop), bool))

            nb = (stop - start) // bq
            m = jax.lax.map(block, (start + bq * jnp.arange(nb), *(
                v[start:stop].reshape((nb, bq) + v.shape[1:])
                for v in (a, cq, cos, sin))))
            out.append(jnp.pad(m.reshape(stop - start, stop),
                               ((0, 0), (0, s - stop))))
        return jnp.concatenate(out, axis=0)

    def forward_with_cache(self, x, cache, last_idx=None):
        """Prefill from position 0: x [B, S, h]; ``cache`` (rows [B, S_max,
        640], index keys [B, S_max, 128]) takes the prompt's at [0, S).
        Returns (out, new_cache)."""
        live = None if last_idx is None else last_idx + 1

        def one(a, *w):
            (wqa, nq, wqb, wkva, nkv, wkvb, wo, wqbi, wki, lnw, lnb,
             ww) = w
            s = a.shape[0]
            block = row_block(s, PREFILL_ROW_BLOCK)
            pos = jnp.arange(s)

            def before(a, pos):
                cos, sin = _angles(pos, self.inv_freq)
                cq = _rms(jnp.matmul(a, wqa,
                                     preferred_element_type=jnp.float32),
                          nq, self.config.rms_norm_eps).astype(a.dtype)
                return (cq, *self._cached(a, cos, sin, wkva, nkv, wki, lnw,
                                          lnb))

            def after(ctx):
                return jnp.matmul(
                    jnp.swapaxes(ctx, 0, 1).reshape(ctx.shape[1], -1), wo)

            cq, row, ki = live_rows(before, (a, pos), live, block)
            mask = self._selection(a, cq, ki, *_angles(pos, self.inv_freq),
                                   wqbi, ww, last_idx)
            ctx = self._attend_expanded(cq, row, mask, pos, wqb, wkvb,
                                        last_idx)
            return live_rows(after, (ctx,), live, block, in_axes=1), row, ki

        def attend(xv, rows, keys, *w):
            out, row, ki = (jnp.stack(v) for v in zip(
                *(one(a, *w) for a in xv)))
            rows = jax.lax.dynamic_update_slice_in_dim(
                rows, row.astype(rows.dtype), 0, axis=1)
            keys = jax.lax.dynamic_update_slice_in_dim(
                keys, ki.astype(keys.dtype), 0, axis=1)
            return out, rows, keys

        out, rows, keys = apply_op(attend, x, *cache, *self._weights(),
                                   op_name="latent_attention_prefill")
        return out, (_val(rows), _val(keys))

    # -- decode ----------------------------------------------------------------
    def forward_decode_paged(self, x, cache, page_table, lens, live):
        """One token a row at per-row position ``lens``: x [B, 1, h];
        ``cache`` the layer's pools (rows [pages, page, 640], index keys
        [pages, page, 128]). Returns (out [B, 1, h], new pools)."""
        cfg = self.config

        def attend(xv, lat_pool, key_pool, wqa, nq, wqb, wkva, nkv, wkvb,
                   wo, wqbi, wki, lnw, lnb, ww):
            a = xv[:, 0]
            b, ps = a.shape[0], lat_pool.shape[1]
            cos, sin = _angles(lens, self.inv_freq)
            cq = _rms(jnp.matmul(a, wqa,
                                 preferred_element_type=jnp.float32),
                      nq, cfg.rms_norm_eps).astype(a.dtype)
            row, ki = self._cached(a, cos, sin, wkva, nkv, wki, lnw, lnb)
            page = row_page(page_table, lens, live, lat_pool.shape[0], ps)
            lat_pool = lat_pool.at[page, lens % ps].set(
                row.astype(lat_pool.dtype), mode="drop")
            key_pool = key_pool.at[page, lens % ps].set(
                ki.astype(key_pool.dtype), mode="drop")
            # a dead row scores nothing: length 0 costs the kernel no page
            new_len = jnp.where(live, lens + 1, 0)
            qi, wi = self._index_query(a, cq, cos, sin, wqbi, ww)
            scores = dsa_index_scores(qi.astype(key_pool.dtype), wi,
                                      key_pool, page_table, new_len)
            # the top index_topk: ONE sort of (score, where the position's
            # row lies in the pool) pairs, so the chosen come out as rows
            # of the pool and no second lookup through the table is made
            width = scores.shape[1]
            pos = jnp.arange(width)
            where = jnp.repeat(jnp.maximum(page_table, 0), ps,
                               axis=1) * ps + pos % ps
            worst_first = jnp.where(pos[None, :] < new_len[:, None],
                                    -scores, jnp.inf)
            worst_first, where = jax.lax.sort((worst_first, where),
                                              dimension=1, num_keys=1)
            k = min(cfg.index_topk, width)
            q_lat, qr, up = self._absorbed_query(cq, cos, sin, wqb, wkvb)
            ctx = sparse_latent_decode(
                q_lat, qr, lat_pool, where[:, :k],
                worst_first[:, :k] < jnp.inf, cfg.softmax_scale)
            o = self._latent_out(ctx, up, a.dtype)
            out = jnp.matmul(o.astype(a.dtype).reshape(b, -1), wo)
            return out[:, None], lat_pool, key_pool

        out, lat_pool, key_pool = apply_op(
            attend, x, *cache, *self._weights(),
            op_name="latent_attention_decode")
        return out, (_val(lat_pool), _val(key_pool))


class DeepseekV32SparseMLP(Layer):
    """shared(m) + this chip's part of routed(m); returns (f, stats)."""

    def __init__(self, config: DeepseekV32Config):
        super().__init__(dtype=config.dtype)
        held = (None if config.ep_size == 1 else
                (config.ep_rank * config.experts_held, config.experts_held))
        self.experts = RoutedExperts(
            config.hidden_size, config.moe_intermediate_size,
            config.n_routed_experts, config.num_experts_per_tok,
            route_scale=config.routed_scaling_factor,
            route_norm=config.norm_topk_prob, n_group=config.n_group,
            topk_group=config.topk_group, held=held)
        self.shared_experts = LlamaMLP(SimpleNamespace(
            hidden_size=config.hidden_size,
            intermediate_size=(config.moe_intermediate_size
                               * config.n_shared_experts),
            dtype=config.dtype))

    def forward(self, m, valid=None):
        routed, stats = self.experts(m, valid=valid)
        return self.shared_experts(m) + routed, stats


class DeepseekV32DecoderLayer(Layer):
    def __init__(self, config: DeepseekV32Config, index: int):
        super().__init__(dtype=config.dtype)
        self.sparse = index >= config.first_k_dense_replace
        self.self_attn = DeepseekV32Attention(config)
        self.mlp = (DeepseekV32SparseMLP(config) if self.sparse
                    else LlamaMLP(config))
        h, eps = config.hidden_size, config.rms_norm_eps
        self.input_layernorm = RMSNorm(h, epsilon=eps)
        self.post_attention_layernorm = RMSNorm(h, epsilon=eps)

    def _ffn(self, x, valid):
        m = self.post_attention_layernorm(x)
        f, stats = (self.mlp(m, valid=valid) if self.sparse
                    else (self.mlp(m), None))
        return x + f, stats

    def forward_with_cache(self, x, cache, valid=None, last_idx=None):
        """(x, cache) of a prefill; what is row-wise here (the norms, the
        residuals, the FFN) runs over the prompt's row blocks alone."""
        attn, cache = self.self_attn.forward_with_cache(
            _rows(self.input_layernorm, last_idx, x), cache,
            last_idx=last_idx)

        def after(x, attn, valid=None):
            return self._ffn(x + attn, valid)[0]

        # the routed experts read every held expert's weights once a call,
        # whatever its rows: their layers' blocks are as wide as the
        # experts take whole (what else runs there is a norm and the
        # shared expert, a tenth of a dense layer's row)
        block = self.mlp.experts.token_block if self.sparse else None
        rest = () if valid is None else (valid,)
        return _rows(after, last_idx, x, attn, *rest, block=block), cache

    def forward_decode_paged(self, x, cache, page_table, lens, live):
        attn, cache = self.self_attn.forward_decode_paged(
            self.input_layernorm(x), cache, page_table, lens, live)
        x, stats = self._ffn(x + attn, live[:, None])
        return x, cache, stats


class DeepseekV32Model(Layer):
    def __init__(self, config: DeepseekV32Config):
        super().__init__(dtype=config.dtype)
        from ..nn.layer.container import LayerList

        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = LayerList([DeepseekV32DecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward_with_cache(self, input_ids, caches, pos=0, last_idx=None):
        if not (isinstance(pos, int) and pos == 0):
            raise NotImplementedError(
                "prefill at an offset (chunked prefill, a warm prefix hit) "
                "is not implemented for latent rows")
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
        cfg = self.config
        x = self.embed_tokens(input_ids)
        ps = caches[0][0].shape[1]
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
        # the cache rows one layer's attention reads this step
        counts["ctx_tokens_selected"] = jnp.sum(jnp.where(
            live, jnp.minimum(lens + 1, cfg.index_topk), 0)).astype(
                jnp.int32)
        return self.norm(x), new_caches, counts


class DeepseekV32ForCausalLM(Layer):
    def __init__(self, config: DeepseekV32Config):
        super().__init__(dtype=config.dtype)
        self.config = config
        from ..core.dtype import get_default_dtype, set_default_dtype

        prev = get_default_dtype()
        set_default_dtype(config.dtype)  # params honor the config dtype
        try:
            self.model = DeepseekV32Model(config)
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=False)
        finally:
            set_default_dtype(prev)

    def _logits(self, hidden):
        """The head's product with a float32 result, whatever the weights'
        dtype (as ``models/afmoe.py`` and for its reason)."""
        return apply_op(
            lambda h, w: jnp.matmul(h, w,
                                    preferred_element_type=jnp.float32),
            hidden, self.lm_head.weight, op_name="lm_head")

    def forward(self, input_ids):
        """Logits [B, S, V] of a whole sequence, no cache kept. Inference
        only (no tape): the routed layer and the attention kernels have
        no backward."""
        from ..core.autograd import no_grad

        ids = _val(input_ids)
        with no_grad():
            logits, _ = self.forward_with_cache(
                input_ids, self.init_cache(ids.shape[0], ids.shape[1]), 0)
        return logits

    def init_cache(self, batch_size: int, max_len: int):
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        return [(jnp.zeros((batch_size, max_len, cfg.cache_row), dt),
                 jnp.zeros((batch_size, max_len, cfg.index_head_dim), dt))
                for _ in range(cfg.num_hidden_layers)]

    def forward_with_cache(self, input_ids, caches, pos=0, last_idx=None):
        """(logits, new_caches) of a one-shot prefill from position 0.
        ``last_idx`` (a traced position): logits [B, 1, V] of that position
        only; the padding after it is routed nowhere and scores nothing."""
        hidden, caches = self.model.forward_with_cache(
            input_ids, caches, pos, last_idx=last_idx)
        return self._logits(hidden), caches

    def paged_layout(self, page_size: int) -> dict:
        """What the paged engine has to know of this model's cache: one
        table and no ring; prefill takes ``last_idx``; a decode step hands
        out counters; and its pages hold no per-head K and V."""
        return {"ring": None, "last_idx": True, "counters": True,
                "prefill_row_block": PREFILL_ROW_BLOCK,
                "rows": "latent rows (one compressed KV row and one "
                        "indexer key a token, no heads)"}

    def init_paged_cache(self, num_pages: int, page_size: int):
        """Per-layer page pools: (rows [pages, page, 640], index keys
        [pages, page, 128])."""
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        return [(jnp.zeros((num_pages, page_size, cfg.cache_row), dt),
                 jnp.zeros((num_pages, page_size, cfg.index_head_dim), dt))
                for _ in range(cfg.num_hidden_layers)]

    def forward_decode_paged(self, input_ids, caches, page_table, lens,
                             live):
        """(logits [B, 1, V], new_caches, counters) — one decode step."""
        hidden, caches, counts = self.model.forward_decode_paged(
            input_ids, caches, page_table, lens, live)
        return self._logits(hidden), caches, counts
