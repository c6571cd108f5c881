"""Plain reference of a sparse-expert decoder with window and full
attention layers side by side (``model_type: afmoe``).

Straightforward ``jax.numpy`` in float32 with matmul precision "highest":
no kernels, no cache, no batching, no routing by sorting. One head at a
time and one expert at a time (a masked loop over ALL experts, each
computed for every token and weighted by 0 where it was not chosen), so
that 8,704 positions fit beside 12 GB of weights. For layer ``l`` of type
``t = layer_types[l]``, every norm an RMSNorm (float32, eps inside the
root) with a weight:

    x   = E[ids] * sqrt(hidden_size)                    # mup_enabled
    a   = N1(x)
    q,k,v = a Wq, a Wk, a Wv ;  g = a Wg                # heads x head_dim
    q,k = RMSNorm_head(q), RMSNorm_head(k)              # per head, own weights
    sliding_attention: q,k = rope(q,k; rope_theta)      # full_attention: none
    o   = softmax(q k^T / sqrt(head_dim) + mask) v      # causal; sliding: key j
                                                        # visible iff i-W < j <= i
    x   = x + N2((o * sigmoid(g)) Wo)
    m   = N3(x)
    l <  num_dense_layers: f = Wd(silu(Wg' m) * Wu m)
    l >= num_dense_layers: s = sigmoid(float32(m) float32(Wr))
                           S = top_k(s + expert_bias)   # bias in selection only
                           w = route_scale * s[S] / (sum s[S] + 1e-20)
                           f = shared(m) + sum_{e in S} w_e * expert_e(m)
    x   = x + N4(f)
    logits = Whead . N(x)

Rotate-half rotary embedding over the whole head; grouped-query attention
in which query head i reads KV head i // (heads / kv_heads); SwiGLU
experts; no bias in any product; an untied output head.

Weights are the RUN'S OWN weights, fetched by name through ``get(name)``
and upcast where they are used. Linear weights are laid out [in, out];
the routed experts are stacked: ``mlp.experts.{gate,up}_proj`` [E, hidden,
width], ``mlp.experts.down_proj`` [E, width, hidden].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _mm(a, b):
    return jnp.matmul(a, b.astype(F32), precision=HIGHEST)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def rope(x, theta):
    """x [S, H, D]: rotate-half at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=F32), inv)
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def attention(q, k, v, window):
    """q [S, H, D], k/v [S, Hkv, D] -> [S, H, D]; causal, and with
    ``window`` key j is visible to query i iff i - window < j <= i. One
    head at a time: a head's [S, S] scores are the largest value alive."""
    s, h, d = q.shape
    group = h // k.shape[1]
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    mask = j <= i
    if window is not None:
        mask = mask & (j > i - window)

    def head(n):
        kn, vn = k[:, n // group], v[:, n // group]
        scores = jnp.matmul(q[:, n], kn.T, precision=HIGHEST)
        scores = jnp.where(mask, scores / jnp.sqrt(F32(d)), -jnp.inf)
        return jnp.matmul(jax.nn.softmax(scores, axis=-1), vn,
                          precision=HIGHEST)

    return jnp.swapaxes(jax.lax.map(head, jnp.arange(h)), 0, 1)


def swiglu(m, gate, up, down):
    return _mm(jax.nn.silu(_mm(m, gate)) * _mm(m, up), down)


def route(m, router, bias, top_k, route_scale, route_norm):
    """[S, E] float32: the weight of every expert for every token, 0
    where it was not chosen."""
    s = jax.nn.sigmoid(_mm(m, router))
    _, sel = jax.lax.top_k(s + bias.astype(F32), top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * route_scale
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, sel].set(w)


def experts(m, weights, gate, up, down):
    """sum_e weights[:, e] * expert_e(m): every expert for every token."""
    def body(e, acc):
        return acc + weights[:, e][:, None] * swiglu(m, gate[e], up[e],
                                                     down[e])

    return jax.lax.fori_loop(0, gate.shape[0], body, jnp.zeros_like(m))


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "eps", "theta", "window", "top_k",
    "route_scale", "route_norm"))
def _layer(x, w, *, heads, kv_heads, head_dim, eps, theta, window, top_k,
           route_scale, route_norm):
    """One layer on x [S, hidden]. ``window`` None = full attention (and
    no position encoding); ``"router" in w`` = an expert layer."""
    s = x.shape[0]
    a = rms_norm(x, w["n1"], eps)
    q = _mm(a, w["q"]).reshape(s, heads, head_dim)
    k = _mm(a, w["k"]).reshape(s, kv_heads, head_dim)
    v = _mm(a, w["v"]).reshape(s, kv_heads, head_dim)
    q = rms_norm(q, w["q_norm"], eps)
    k = rms_norm(k, w["k_norm"], eps)
    if window is not None:
        q, k = rope(q, theta), rope(k, theta)
    o = attention(q, k, v, window).reshape(s, heads * head_dim)
    o = o * jax.nn.sigmoid(_mm(a, w["gate"]))
    x = x + rms_norm(_mm(o, w["o"]), w["n2"], eps)
    m = rms_norm(x, w["n3"], eps)
    if "router" in w:
        weights = route(m, w["router"], w["bias"], top_k, route_scale,
                        route_norm)
        f = (swiglu(m, w["sh_gate"], w["sh_up"], w["sh_down"])
             + experts(m, weights, w["ex_gate"], w["ex_up"], w["ex_down"]))
    else:
        f = swiglu(m, w["ffn_gate"], w["ffn_up"], w["ffn_down"])
    return x + rms_norm(f, w["n4"], eps)


_ATTN = {"q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
         "v": "self_attn.v_proj.weight", "o": "self_attn.o_proj.weight",
         "gate": "self_attn.gate_proj.weight",
         "q_norm": "self_attn.q_norm.weight",
         "k_norm": "self_attn.k_norm.weight",
         "n1": "input_layernorm.weight",
         "n2": "post_attention_layernorm.weight",
         "n3": "pre_mlp_layernorm.weight",
         "n4": "post_mlp_layernorm.weight"}
_DENSE = {"ffn_gate": "mlp.gate_proj.weight", "ffn_up": "mlp.up_proj.weight",
          "ffn_down": "mlp.down_proj.weight"}
_SPARSE = {"router": "mlp.experts.router", "bias": "mlp.experts.expert_bias",
           "ex_gate": "mlp.experts.gate_proj",
           "ex_up": "mlp.experts.up_proj",
           "ex_down": "mlp.experts.down_proj",
           "sh_gate": "mlp.shared_experts.gate_proj.weight",
           "sh_up": "mlp.shared_experts.up_proj.weight",
           "sh_down": "mlp.shared_experts.down_proj.weight"}


HEAD_COLUMNS = 32768     # of the output head at a time: its float32 copy
                         # is 0.27 GB so, not the whole head's 1.64 GB


@functools.partial(jax.jit, static_argnames=("eps", "start", "stop"))
def _head(x, norm, head, eps, start, stop):
    return _mm(rms_norm(x, norm, eps), head[:, start:stop])


def forward(get, cfg, ids, last: int = None):
    """float32 logits [B, S', V] for token ids [B, S]; ``last`` keeps only
    the final ``last`` positions (the head is the widest product).

    ``get(name)`` returns the weight stored under its name
    (``model.embed_tokens.weight``, ``model.layers.<i>.<...>``,
    ``model.norm.weight``, ``lm_head.weight``); ``cfg`` has the published
    keys as attributes. Rows of the batch are computed one after another."""
    ids = jnp.asarray(ids)
    eps = float(cfg.rms_norm_eps)
    out = []
    for row in ids:
        x = jnp.take(get("model.embed_tokens.weight"), row,
                     axis=0).astype(F32)
        if cfg.mup_enabled:
            x = x * jnp.sqrt(F32(cfg.hidden_size))
        for i in range(cfg.num_hidden_layers):
            names = dict(_ATTN, **(_DENSE if i < cfg.num_dense_layers
                                   else _SPARSE))
            w = {k: get(f"model.layers.{i}.{n}") for k, n in names.items()}
            sliding = cfg.layer_types[i] == "sliding_attention"
            x = _layer(
                x, w, heads=cfg.num_attention_heads,
                kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
                eps=eps, theta=float(cfg.rope_theta),
                window=int(cfg.sliding_window) if sliding else None,
                top_k=cfg.num_experts_per_tok,
                route_scale=float(cfg.route_scale),
                route_norm=bool(cfg.route_norm))
        if last is not None:
            x = x[-last:]
        head = get("lm_head.weight")
        out.append(jnp.concatenate([
            _head(x, get("model.norm.weight"), head, eps, c,
                  min(c + HEAD_COLUMNS, head.shape[1]))
            for c in range(0, head.shape[1], HEAD_COLUMNS)], axis=-1))
    return jnp.stack(out)
