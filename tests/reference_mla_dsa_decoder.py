"""Plain reference of a latent-attention decoder with a learned
sparse-attention indexer and group-limited routed experts (``model_type:
deepseek_v32``), as one chip's share of an expert-parallel deployment.

Straightforward ``jax.numpy`` in float32 with matmul precision "highest":
no kernel, no cache, no batching, no absorbed projection, no routing by
sorting. Positions go a block at a time and heads a group at a time, and
one held expert at a time (each computed for every token and weighted by 0
where it was not chosen), so that 17,408 positions fit beside 9 GB of
weights and 2 GB of pages. For layer ``l``, every norm an RMSNorm (float32,
eps inside the root) with a weight, but the indexer's key norm, a LayerNorm
with a bias:

    a      = N1(x)
    c_q    = Nq(a Wqa)
    q      = c_q Wqb   -> heads x (nope | rope) ;  q_rope = rope(q_rope)
    ckv|kr = a Wkva ;  c = Nkv(ckv) ;  k_rope = rope(kr)    # one a token, all heads
    k_nope|v = c Wkvb  -> heads x (nope | v)
    qI     = c_q WqbI  -> index heads x index dim, rope on the first rope dims of each
    kI     = LayerNorm(a WkI), rope on its first rope dims
    w      = (a Ww) * index_heads^-0.5 * index_dim^-0.5
    I[t,u] = sum_j w[t,j] * relu(qI[t,j] . kI[u])           # u <= t
    S_t    = the min(index_topk, t+1) positions u <= t of largest I[t,u]
    p[t,h,u] = softmax_{u in S_t}(s * (q_nope[t,h].k_nope[u,h] + q_rope[t,h].k_rope[u]))
    o      = concat_h(sum_u p[t,h,u] v[u,h]) Wo
    x      = x + o
    m      = N2(x)
    l <  first_k_dense_replace: f = Wd(silu(Wg m) * Wu m)
    l >= first_k_dense_replace: sc = sigmoid(float32(m) float32(Wr))
        g  = sc + e_score_correction_bias ; a group's score = the sum of its 2 largest g
        keep the topk_group best of n_group groups, S = top-k of g inside them
        wt = routed_scaling_factor * sc[S] / (sum sc[S] + 1e-20)      # bias in selection only
        f  = shared(m) + sum_{e in S, e held here} wt_e * expert_e(m)
    x      = x + f
    logits = Whead . N(x)

``rope`` is YaRN: the inverse frequencies ``theta^(-2i/d)`` are blended with
the same / ``factor`` by a linear ramp between the dimensions that turn
``beta_fast`` and ``beta_slow`` times in the original context; the softmax
scale is ``s = (nope + rope)^-0.5 * (0.1 * mscale_all_dim * ln(factor) + 1)^2``.
The attention rotates neighbouring pairs (x0, x1), (x2, x3), ...; the
indexer rotates (x_i, x_{i+rope/2}) of the first ``rope`` dims.

The share: the layer has ``n_routed_experts`` experts, scored by the router
all; the ``n_routed_experts // ep_size`` from ``ep_rank`` times that on are
held here and their terms are summed, the others' terms are left out. Embedding and head hold the rows this chip holds and no other.

Weights are the RUN'S OWN weights, fetched by name through ``get(name)``
and upcast where they are used. Linear weights are laid out [in, out]; the
held experts are stacked: ``mlp.experts.{gate,up}_proj`` [held, hidden,
width], ``mlp.experts.down_proj`` [held, width, hidden].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
ROW_BLOCK = 512          # positions a row-wise stage or the attention takes at a time;
                         # a longer sequence is padded to a multiple of it
QUERY_BLOCK = 128        # queries the indexer scores at a time
HEAD_GROUP = 8           # heads attended at a time
INDEX_NORM_EPS = 1e-6


def _mm(a, b):
    return jnp.matmul(a, b.astype(F32), precision=HIGHEST)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32) + b.astype(F32)


def inv_freq(dim, theta, scaling):
    """[dim / 2] inverse frequencies; ``scaling`` None = plain rope."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if scaling is None:
        return inv.astype(np.float32)
    orig, factor = (scaling["original_max_position_embeddings"],
                    scaling["factor"])
    out = np.empty_like(inv)
    # the dimension (a real number) that turns n times in the original context
    lo, hi = (dim * math.log(orig / (n * 2 * math.pi)) / (2 * math.log(theta))
              for n in (scaling["beta_fast"], scaling["beta_slow"]))
    lo, hi = max(math.floor(lo), 0), min(math.ceil(hi), dim - 1)
    for i in range(dim // 2):
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        out[i] = (1.0 - ramp) * inv[i] + ramp * inv[i] / factor
    return out.astype(np.float32)


def softmax_scale(cfg):
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    rs = cfg.rope_scaling
    if rs is None:
        return scale
    return scale * (0.1 * rs["mscale_all_dim"] * math.log(rs["factor"])
                    + 1.0) ** 2


def rope_pairs(x, ang):
    """x [S, ..., D], ang [S, D/2]: rotate (x0, x1), (x2, x3), ..."""
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + ang.shape[1:])
    a, b = x[..., 0::2], x[..., 1::2]
    c, s = jnp.cos(ang), jnp.sin(ang)
    out = jnp.zeros_like(x)
    out = out.at[..., 0::2].set(a * c - b * s)
    return out.at[..., 1::2].set(b * c + a * s)


def rope_halves(x, ang):
    """x [S, ..., D], ang [S, D/2]: rotate (x_i, x_{i + D/2})."""
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + ang.shape[1:])
    d2 = x.shape[-1] // 2
    a, b = x[..., :d2], x[..., d2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def selection(qi, wi, ki, topk):
    """[S, S] bool: position u is attended by query t. One block of queries
    at a time: its scores against every key, the causal mask, and the
    value of the min(topk, t + 1)-th largest of each row from a full sort."""
    s = qi.shape[0]
    bq = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    upos = jnp.arange(s)[None, :]

    def block(args):
        t0, qb, wb = args
        dots = jnp.einsum("qjd,ud->qju", qb, ki, precision=HIGHEST)
        score = jnp.sum(wb[:, :, None] * jnp.maximum(dots, 0.0), axis=1)
        tpos = t0 + jnp.arange(bq)[:, None]
        causal = upos <= tpos
        score = jnp.where(causal, score, -jnp.inf)
        k = jnp.minimum(tpos + 1, topk)                     # [bq, 1]
        kth = jnp.take_along_axis(jnp.sort(score, axis=-1), s - k, axis=-1)
        return causal & (score >= kth)

    nb = s // bq
    return jax.lax.map(block, (
        bq * jnp.arange(nb), qi.reshape(nb, bq, *qi.shape[1:]),
        wi.reshape(nb, bq, -1))).reshape(s, s)


def by_rows(fn, *arrays):
    """``fn`` over blocks of ROW_BLOCK rows of ``arrays``, one after
    another; ``fn`` returns an array or a tuple of arrays, rows first."""
    s = arrays[0].shape[0]
    if s <= ROW_BLOCK:
        return fn(*arrays)
    nb = s // ROW_BLOCK
    out = jax.lax.map(lambda a: fn(*a), tuple(
        a.reshape((nb, ROW_BLOCK) + a.shape[1:]) for a in arrays))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((s,) + o.shape[2:]), out)


def attention(q, k, v, mask, scale):
    """q/k [S, H, D], v [S, H, Dv], mask [S, S] -> [S, H, Dv]; ROW_BLOCK
    queries at a time."""
    def block(qb, mb):
        sc = jnp.einsum("thd,uhd->htu", qb, k, precision=HIGHEST) * scale
        p = jax.nn.softmax(jnp.where(mb[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("htu,uhv->thv", p, v, precision=HIGHEST)

    return by_rows(block, q, mask)


def swiglu(m, gate, up, down):
    return _mm(jax.nn.silu(_mm(m, gate)) * _mm(m, up), down)


def route(m, router, bias, top_k, n_group, topk_group, scale, norm):
    """[S, E] float32: the weight of every expert of the WHOLE layer for
    every token, 0 where it was not chosen."""
    sc = jax.nn.sigmoid(_mm(m, router))
    g = sc + bias.astype(F32)
    s, e = g.shape
    if n_group > 1:
        per = e // n_group
        grouped = g.reshape(s, n_group, per)
        group_score = jnp.sort(grouped, axis=-1)[..., -2:].sum(axis=-1)
        cut = jnp.sort(group_score, axis=-1)[:, n_group - topk_group]
        kept = group_score >= cut[:, None]                  # [S, n_group]
        g = jnp.where(jnp.repeat(kept, per, axis=1), g, -jnp.inf)
    _, sel = jax.lax.top_k(g, top_k)
    w = jnp.take_along_axis(sc, sel, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * scale
    return jnp.zeros_like(sc).at[jnp.arange(s)[:, None], sel].set(w)


def experts(m, weights, gate, up, down):
    """sum_e weights[:, e] * expert_e(m) over the experts held."""
    def body(e, acc):
        return acc + weights[:, e][:, None] * swiglu(m, gate[e], up[e],
                                                     down[e])

    return jax.lax.fori_loop(0, gate.shape[0], body, jnp.zeros_like(m))


class _Static:
    """The numbers of a layer as one hashable argument of the jitted
    layer."""

    def __init__(self, cfg):
        self.key = (
            cfg.num_attention_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank,
            cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk,
            float(cfg.rms_norm_eps), float(softmax_scale(cfg)),
            cfg.num_experts_per_tok, cfg.n_group, cfg.topk_group,
            float(cfg.routed_scaling_factor), bool(cfg.norm_topk_prob),
            cfg.ep_rank * (cfg.n_routed_experts // cfg.ep_size),
            cfg.n_routed_experts // cfg.ep_size,
            tuple(inv_freq(cfg.qk_rope_head_dim, float(cfg.rope_theta),
                           cfg.rope_scaling).tolist()))

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return self.key == other.key


@functools.partial(jax.jit, static_argnames=("st",))
def _layer(x, w, *, st):
    """One layer on x [S, hidden]; ``"router" in w`` = an expert layer.
    What is computed row by row goes ROW_BLOCK rows at a time, the
    attention HEAD_GROUP heads at a time."""
    (heads, nope, rope, vdim, lat, ih, idim, topk, eps, scale, top_k,
     n_group, topk_group, route_scale, route_norm, first, held,
     freqs) = st.key
    s = x.shape[0]
    ang = jnp.outer(jnp.arange(s, dtype=F32), jnp.asarray(freqs, F32))

    def latents(x, ang):
        a = rms_norm(x, w["n1"], eps)
        cq = rms_norm(_mm(a, w["q_a"]), w["q_norm"], eps)
        kv = _mm(a, w["kv_a"])
        c = rms_norm(kv[:, :lat], w["kv_norm"], eps)
        k_rope = rope_pairs(kv[:, lat:], ang)
        qi = _mm(cq, w["iq_b"]).reshape(-1, ih, idim)
        qi = jnp.concatenate(
            [rope_halves(qi[..., :rope], ang), qi[..., rope:]], axis=-1)
        ki = layer_norm(_mm(a, w["ik"]), w["ik_norm_w"], w["ik_norm_b"],
                        INDEX_NORM_EPS)
        ki = jnp.concatenate(
            [rope_halves(ki[:, :rope], ang), ki[:, rope:]], axis=-1)
        wi = _mm(a, w["iw"]) * (ih ** -0.5 * idim ** -0.5)
        return cq, c, k_rope, qi, ki, wi

    cq, c, k_rope, qi, ki, wi = by_rows(latents, x, ang)
    mask = selection(qi, wi, ki, topk)
    hg = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads

    def group(g, acc):
        """acc + (the heads of group g, attended) x their rows of Wo."""
        q_b = jax.lax.dynamic_slice_in_dim(
            w["q_b"], g * hg * (nope + rope), hg * (nope + rope), axis=1)
        kv_b = jax.lax.dynamic_slice_in_dim(
            w["kv_b"], g * hg * (nope + vdim), hg * (nope + vdim), axis=1)
        o_w = jax.lax.dynamic_slice_in_dim(
            w["o"], g * hg * vdim, hg * vdim, axis=0)
        q = _mm(cq, q_b).reshape(s, hg, nope + rope)
        q = jnp.concatenate([q[..., :nope], rope_pairs(q[..., nope:], ang)],
                            axis=-1)
        kvb = _mm(c, kv_b).reshape(s, hg, nope + vdim)
        k = jnp.concatenate(
            [kvb[..., :nope],
             jnp.broadcast_to(k_rope[:, None, :], (s, hg, rope))], axis=-1)
        o = attention(q, k, kvb[..., nope:], mask, scale)
        return acc + _mm(o.reshape(s, hg * vdim), o_w)

    x = jax.lax.fori_loop(0, heads // hg, group, x)

    def ffn(x):
        m = rms_norm(x, w["n2"], eps)
        if "router" not in w:
            return x + swiglu(m, w["ffn_gate"], w["ffn_up"], w["ffn_down"])
        weights = route(m, w["router"], w["bias"], top_k, n_group,
                        topk_group, route_scale, route_norm)
        return (x + swiglu(m, w["sh_gate"], w["sh_up"], w["sh_down"])
                + experts(m, weights[:, first:first + held], w["ex_gate"],
                          w["ex_up"], w["ex_down"]))

    return by_rows(ffn, x)


_ATTN = {"q_a": "self_attn.q_a_proj.weight",
         "q_norm": "self_attn.q_a_layernorm.weight",
         "q_b": "self_attn.q_b_proj.weight",
         "kv_a": "self_attn.kv_a_proj_with_mqa.weight",
         "kv_norm": "self_attn.kv_a_layernorm.weight",
         "kv_b": "self_attn.kv_b_proj.weight",
         "o": "self_attn.o_proj.weight",
         "iq_b": "self_attn.indexer.wq_b.weight",
         "ik": "self_attn.indexer.wk.weight",
         "ik_norm_w": "self_attn.indexer.k_norm.weight",
         "ik_norm_b": "self_attn.indexer.k_norm.bias",
         "iw": "self_attn.indexer.weights_proj.weight",
         "n1": "input_layernorm.weight",
         "n2": "post_attention_layernorm.weight"}
_DENSE = {"ffn_gate": "mlp.gate_proj.weight", "ffn_up": "mlp.up_proj.weight",
          "ffn_down": "mlp.down_proj.weight"}
_SPARSE = {"router": "mlp.experts.router", "bias": "mlp.experts.expert_bias",
           "ex_gate": "mlp.experts.gate_proj",
           "ex_up": "mlp.experts.up_proj",
           "ex_down": "mlp.experts.down_proj",
           "sh_gate": "mlp.shared_experts.gate_proj.weight",
           "sh_up": "mlp.shared_experts.up_proj.weight",
           "sh_down": "mlp.shared_experts.down_proj.weight"}


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, eps):
    return _mm(rms_norm(x, norm, eps), head)


def stacked_getter(params):
    """``get(name)`` over a dict of the model's parameters by name (this
    model is served, never trained: nothing is stacked)."""
    return params.__getitem__


def forward(get, cfg, ids, last: int = None):
    """float32 logits [B, S', V] for token ids [B, S]; ``last`` keeps only
    the final ``last`` positions.

    ``get(name)`` returns the weight stored under its name
    (``model.embed_tokens.weight``, ``model.layers.<i>.<...>``,
    ``model.norm.weight``, ``lm_head.weight``); ``cfg`` has the published
    keys as attributes, and ``ep_rank``. Rows of the batch are computed one
    after another."""
    ids = jnp.asarray(ids)
    st = _Static(cfg)
    n = ids.shape[1]
    if n > ROW_BLOCK:
        # whole blocks: what is appended comes after every real position,
        # which sees none of it
        ids = jnp.pad(ids, ((0, 0), (0, -n % ROW_BLOCK)))
    out = []
    for row in ids:
        x = jnp.take(get("model.embed_tokens.weight"), row,
                     axis=0).astype(F32)
        for i in range(cfg.num_hidden_layers):
            names = dict(_ATTN, **(_DENSE if i < cfg.first_k_dense_replace
                                   else _SPARSE))
            w = {k: get(f"model.layers.{i}.{n}") for k, n in names.items()}
            x = _layer(x, w, st=st)
        x = x[:n] if last is None else x[n - last:n]
        out.append(_head(x, get("model.norm.weight"), get("lm_head.weight"),
                         float(cfg.rms_norm_eps)))
    return jnp.stack(out)
