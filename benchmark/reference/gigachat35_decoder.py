"""Plain reference of a hybrid of latent attention and gated-delta-rule
linear attention with sparse experts (``model_type: gigachat3_5``), as one
chip's share of an expert-parallel deployment.

Straightforward ``jax.numpy`` in float32 with matmul precision "highest":
no kernel, no cache, no batching, no absorbed projection, no routing by
sorting. The recurrence runs as it is written, one position at a time.
So that 34,000 positions fit beside 9.5 GB of weights, the sequence goes
a block of ``ROW_BLOCK`` positions at a time (a linear layer carries its
state and the convolution's last inputs from block to block), the
attention a group of ``HEAD_GROUP`` heads and a block of queries at a
time, and the experts one held expert at a time (each computed for every
position and weighted by 0 where it was not chosen). For layer ``l``, every
norm ``N(x) = x rsqrt(mean(x^2) + eps) * g sigmoid(w)`` (float32, ``g`` =
``layernorm_gating_weight``):

    x = E[ids]
    a = N1(x)
    full layer (l in full_attention_layers), heads h:
      c_q      = Nqa(a Wqa)
      q_nope | q_rope = c_q Wqb, per head ;  q_rope = rope(q_rope)
      c | k_r  = a Wkva ;  c = Nkva(c) ;  k_rope = rope(k_r)      # all heads
      k_nope | v = c Wkvb, per head
      p        = causal_softmax(s (q_nope . k_nope + q_rope . k_rope))
      M        = concat_h(p v * sigmoid(a Wg)) Wo
    linear layer (Hk key heads, Hv value heads, value head j reads key head
    j // (Hv / Hk)):
      u        = a Wqkv ;  z = a Wz
      c_t      = silu(sum_{j<K} w_conv[:, j] u_{t-K+1+j})    # zeros before 0
      q, k, v  = split(c) ;  q = l2norm(q) dk^-0.5 ;  k = l2norm(k)   # eps 1e-6
      beta     = sigmoid(a Wb) ;  g = -exp(A_log) softplus(a Wa + dt_bias)
      S        = exp(g_t) S_{t-1} ;  d = beta (v_t - k_t . S) ;  S = S + k_t (x) d
      o_t      = q_t . S
      M        = (N_o(o) * 2 sigmoid(z)) Wo    # N_o over dv, eps linear_attn_o_norm_eps
    x = x + N2(M)
    m = N3(x)
    l < first_k_dense_replace: F = swiglu(m)
    else: sc = sigmoid(m Wr) ; S = top-k of sc + bias ; w = scale sc[S] / (sum sc[S] + 1e-20)
          F = swiglu_shared(m) + sum_{e in S, e held here} w_e swiglu_e(m)
    x = x + N4(F)
    logits = Whead . Nf(x)

with ``swiglu(m) = (silu(min(m Wg, L)) * clip(m Wu, -L, L)) Wd``, L =
``swiglu_limit``. ``rope`` rotates neighbouring pairs with YaRN inverse
frequencies; ``s = (nope + rope)^-0.5 * (0.1 mscale_all_dim ln(factor) +
1)^2``. The share: the router scores all ``n_routed_experts``; the
``n_routed_experts // ep_size`` from ``ep_rank`` times that on are held
and their terms summed; embedding and head hold this chip's rows.

Weights are the RUN'S OWN weights, fetched by name through ``get(name)``
and upcast where they are used. Linear weights are laid out [in, out]; the
held experts are stacked ``mlp.experts.{gate,up}_proj`` [held, hidden,
width], ``mlp.experts.down_proj`` [held, width, hidden].
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from benchmark.reference.mla_dsa_decoder import inv_freq, rope_pairs

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
ROW_BLOCK = 512          # positions a block; the sequence is padded to whole blocks
HEAD_GROUP = 8           # heads attended at a time
KEY_PAD = 4096           # the attention's keys padded to a multiple (few shapes)
HEAD_COLUMNS = 32768     # of the output head at a time


def _mm(a, b):
    return jnp.matmul(a, b.astype(F32), precision=HIGHEST)


def norm(x, w, eps, gating):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gating * jax.nn.sigmoid(
        w.astype(F32))


def l2norm(x, eps=1e-6):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def swiglu(m, gate, up, down, limit):
    g, u = _mm(m, gate), _mm(m, up)
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return _mm(jax.nn.silu(g) * u, down)


class _Static(NamedTuple):
    """The numbers of the model, one hashable argument of the jitted
    blocks."""
    eps: float
    gating: float
    heads: int
    nope: int
    rope: int
    vdim: int
    lat: int
    scale: float
    key_heads: int
    value_heads: int
    dk: int
    dv: int
    o_eps: float
    top_k: int
    route_scale: float
    route_norm: bool
    first: int
    held: int
    limit: Optional[float]
    freqs: tuple

    @classmethod
    def of(cls, cfg):
        rs = cfg.rope_scaling
        scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
        if rs is not None and cfg.use_mla_scaling_factor:
            scale *= (0.1 * rs["mscale_all_dim"] * math.log(rs["factor"])
                      + 1.0) ** 2
        held = cfg.n_routed_experts // cfg.ep_size
        return cls(
            float(cfg.rms_norm_eps), float(cfg.layernorm_gating_weight),
            cfg.num_attention_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank,
            float(scale), cfg.linear_num_key_heads,
            cfg.linear_num_value_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, float(cfg.linear_attn_o_norm_eps),
            cfg.num_experts_per_tok, float(cfg.routed_scaling_factor),
            bool(cfg.norm_topk_prob), cfg.ep_rank * held, held,
            None if cfg.swiglu_limit is None else float(cfg.swiglu_limit),
            tuple(inv_freq(cfg.qk_rope_head_dim, float(cfg.rope_theta),
                           rs).tolist()))


def _angles(pos, st):
    return jnp.outer(pos.astype(F32), jnp.asarray(st.freqs, F32))


def _n(x, w, st, eps=None):
    return norm(x, w, st.eps if eps is None else eps, st.gating)


def route(m, router, bias, st):
    """[S, E] float32: the weight of every expert of the WHOLE layer for
    every position, 0 where it was not chosen."""
    sc = jax.nn.sigmoid(_mm(m, router))
    _, sel = jax.lax.top_k(sc + bias.astype(F32), st.top_k)
    w = jnp.take_along_axis(sc, sel, axis=-1)
    if st.route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(sc).at[jnp.arange(sc.shape[0])[:, None], sel].set(
        w * st.route_scale)


def ffn(x, w, st):
    """x + N4(F(N3(x))) of one block."""
    m = _n(x, w["n3"], st)
    if "router" not in w:
        f = swiglu(m, w["ffn_gate"], w["ffn_up"], w["ffn_down"], st.limit)
    else:
        weights = route(m, w["router"], w["bias"], st)[
            :, st.first:st.first + st.held]

        def expert(e, acc):
            return acc + weights[:, e][:, None] * swiglu(
                m, w["ex_gate"][e], w["ex_up"][e], w["ex_down"][e], st.limit)

        f = swiglu(m, w["sh_gate"], w["sh_up"], w["sh_down"], st.limit) \
            + jax.lax.fori_loop(0, st.held, expert, jnp.zeros_like(m))
    return x + _n(f, w["n4"], st)


def delta_rule(q, k, v, g, beta, state):
    """The recurrence as written over one block: q/k/v [B, H, d] (the key
    heads already repeated for their value heads), g/beta [B, H], state
    [H, dk, dv] -> (o [B, H, dv], the state after the block)."""
    def step(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[:, None, None]
        d = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, state,
                                           precision=HIGHEST))
        state = state + kt[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", qt, state, precision=HIGHEST)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


@functools.partial(jax.jit, static_argnames=("st",))
def _linear_block(x, state, tail, w, *, st):
    """One block of a linear layer, mixer and FFN: x [B, hidden]; ``state``
    [Hv, dk, dv] and ``tail`` (the last K - 1 inputs of the convolution)
    carried in from the block before. Returns (x, state, tail)."""
    b = x.shape[0]
    hk, hv, dk, dv = st.key_heads, st.value_heads, st.dk, st.dv
    a = _n(x, w["n1"], st)
    u = _mm(a, w["qkv"])
    win = jnp.concatenate([tail, u])
    width = w["conv"].shape[1]
    c = jax.nn.silu(sum(win[j:j + b] * w["conv"][:, j].astype(F32)
                        for j in range(width)))
    q, k, v = jnp.split(c, [hk * dk, 2 * hk * dk], axis=-1)
    rep = hv // hk          # value head j reads key head j // rep
    q = jnp.repeat(l2norm(q.reshape(b, hk, dk)) * dk ** -0.5, rep, axis=1)
    k = jnp.repeat(l2norm(k.reshape(b, hk, dk)), rep, axis=1)
    beta = jax.nn.sigmoid(_mm(a, w["b"]))
    g = -jnp.exp(w["A_log"].astype(F32)) * jax.nn.softplus(
        _mm(a, w["a"]) + w["dt_bias"].astype(F32))
    o, state = delta_rule(q, k, v.reshape(b, hv, dv), g, beta, state)
    o = norm(o, w["o_norm"], st.o_eps, 2.0) * 2.0 * jax.nn.sigmoid(
        _mm(a, w["z"])).reshape(b, hv, dv)
    x = x + _n(_mm(o.reshape(b, hv * dv), w["o"]), w["n2"], st)
    return ffn(x, w, st), state, win[b:]


@functools.partial(jax.jit, static_argnames=("st",))
def _latent_rows(x, pos, w, *, st):
    """c_q, c and k_rope of one block of positions."""
    a = _n(x, w["n1"], st)
    cq = _n(_mm(a, w["q_a"]), w["q_norm"], st)
    kv = _mm(a, w["kv_a"])
    return (cq, _n(kv[:, :st.lat], w["kv_norm"], st),
            rope_pairs(kv[:, st.lat:], _angles(pos, st)))


@functools.partial(jax.jit, static_argnames=("st",))
def _expand(c, k_rope, kv_b, *, st):
    """The keys [S, hg, nope + rope] and values [S, hg, v] of a group of
    heads (``kv_b`` its columns of Wkvb)."""
    kv = _mm(c, kv_b).reshape(c.shape[0], -1, st.nope + st.vdim)
    k = jnp.concatenate([kv[..., :st.nope], jnp.broadcast_to(
        k_rope[:, None, :], kv.shape[:2] + (st.rope,))], axis=-1)
    return k, kv[..., st.nope:]


@functools.partial(jax.jit, static_argnames=("st",))
def _attend(x, cq, pos, k, v, w, q_b, gate, o, *, st):
    """A group of heads for one block of queries, gated and through its
    rows of Wo: [B, hidden]."""
    b = cq.shape[0]
    q = _mm(cq, q_b).reshape(b, -1, st.nope + st.rope)
    q = jnp.concatenate([q[..., :st.nope],
                         rope_pairs(q[..., st.nope:], _angles(pos, st))],
                        axis=-1)
    sc = jnp.einsum("thd,uhd->htu", q, k, precision=HIGHEST) * st.scale
    causal = jnp.arange(k.shape[0])[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    ctx = jnp.einsum("htu,uhv->thv", p, v, precision=HIGHEST)
    a = _n(x, w["n1"], st)
    return _mm(ctx.reshape(b, -1) * jax.nn.sigmoid(_mm(a, gate)), o)


@functools.partial(jax.jit, static_argnames=("st",))
def _latent_after(x, mix, w, *, st):
    return ffn(x + _n(mix, w["n2"], st), w, st)


def latent_layer(xs, w, st):
    """A full layer over the blocks ``xs`` ([B, hidden] each)."""
    pos = [jnp.arange(i * ROW_BLOCK, (i + 1) * ROW_BLOCK) for i in
           range(len(xs))]
    rows = [_latent_rows(x, p, w, st=st) for x, p in zip(xs, pos)]
    s = len(xs) * ROW_BLOCK
    keys = -(-s // KEY_PAD) * KEY_PAD     # later keys: no query sees them
    c, k_rope = (jnp.pad(jnp.concatenate([r[i] for r in rows]),
                         ((0, keys - s), (0, 0))) for i in (1, 2))
    hg = HEAD_GROUP if st.heads % HEAD_GROUP == 0 else st.heads
    mix = [jnp.zeros_like(x) for x in xs]
    for g in range(st.heads // hg):
        k, v = _expand(c, k_rope, w["kv_b"][:, g * hg * (st.nope + st.vdim):
                                           (g + 1) * hg
                                           * (st.nope + st.vdim)], st=st)
        q_b = w["q_b"][:, g * hg * (st.nope + st.rope):
                       (g + 1) * hg * (st.nope + st.rope)]
        cols = slice(g * hg * st.vdim, (g + 1) * hg * st.vdim)
        for i, x in enumerate(xs):
            mix[i] = mix[i] + _attend(x, rows[i][0], pos[i], k, v, w, q_b,
                                      w["gate"][:, cols], w["o"][cols],
                                      st=st)
    return [_latent_after(x, m, w, st=st) for x, m in zip(xs, mix)]


def linear_layer(xs, w, st):
    state = jnp.zeros((st.value_heads, st.dk, st.dv), F32)
    tail = jnp.zeros((w["conv"].shape[1] - 1, w["conv"].shape[0]), F32)
    out = []
    for x in xs:
        x, state, tail = _linear_block(x, state, tail, w, st=st)
        out.append(x)
    return out


_NORMS = {"n1": "input_layernorm.weight",
          "n2": "post_attention_layernorm.weight",
          "n3": "pre_feedforward_layernorm.weight",
          "n4": "post_feedforward_layernorm.weight"}
_LINEAR = {"qkv": "linear_attn.in_proj_qkv.weight",
           "z": "linear_attn.in_proj_z.weight",
           "b": "linear_attn.in_proj_b.weight",
           "a": "linear_attn.in_proj_a.weight",
           "conv": "linear_attn.conv1d", "A_log": "linear_attn.A_log",
           "dt_bias": "linear_attn.dt_bias",
           "o_norm": "linear_attn.norm.weight",
           "o": "linear_attn.out_proj.weight"}
_FULL = {"q_a": "self_attn.q_a_proj.weight",
         "q_norm": "self_attn.q_a_layernorm.weight",
         "q_b": "self_attn.q_b_proj.weight",
         "kv_a": "self_attn.kv_a_proj_with_mqa.weight",
         "kv_norm": "self_attn.kv_a_layernorm.weight",
         "kv_b": "self_attn.kv_b_proj.weight",
         "o": "self_attn.o_proj.weight",
         "gate": "self_attn.gate_proj.weight"}
_DENSE = {"ffn_gate": "mlp.gate_proj.weight", "ffn_up": "mlp.up_proj.weight",
          "ffn_down": "mlp.down_proj.weight"}
_SPARSE = {"router": "mlp.experts.router", "bias": "mlp.experts.expert_bias",
           "ex_gate": "mlp.experts.gate_proj",
           "ex_up": "mlp.experts.up_proj",
           "ex_down": "mlp.experts.down_proj",
           "sh_gate": "mlp.shared_experts.gate_proj.weight",
           "sh_up": "mlp.shared_experts.up_proj.weight",
           "sh_down": "mlp.shared_experts.down_proj.weight"}


@functools.partial(jax.jit, static_argnames=("st", "start", "stop"))
def _head(x, w_norm, head, st, start, stop):
    return _mm(_n(x, w_norm, st), head[:, start:stop])


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
    keys as attributes, and ``ep_size`` / ``ep_rank``. Rows of the batch
    are computed one after another."""
    ids = jnp.asarray(ids)
    st = _Static.of(cfg)
    n = ids.shape[1]
    # whole blocks: what is appended comes after every real position, which
    # sees none of it
    ids = jnp.pad(ids, ((0, 0), (0, -n % ROW_BLOCK)))
    keep = n if last is None else last
    out = []
    for row in ids:
        x = jnp.take(get("model.embed_tokens.weight"), row,
                     axis=0).astype(F32)
        xs = [x[i:i + ROW_BLOCK] for i in range(0, x.shape[0], ROW_BLOCK)]
        del x
        for i in range(cfg.num_hidden_layers):
            linear = i not in cfg.full_attention_layers
            names = dict(_NORMS, **(_LINEAR if linear else _FULL),
                         **(_DENSE if i < cfg.first_k_dense_replace
                            else _SPARSE))
            w = {k: get(f"model.layers.{i}.{nm}") for k, nm in names.items()}
            xs = (linear_layer if linear else latent_layer)(xs, w, st)
        x = jnp.concatenate(xs)[n - keep:n]
        head = get("lm_head.weight")
        out.append(jnp.concatenate([
            _head(x, get("model.norm.weight"), head, st, c,
                  min(c + HEAD_COLUMNS, head.shape[1]))
            for c in range(0, head.shape[1], HEAD_COLUMNS)], axis=-1))
    return jnp.stack(out)
