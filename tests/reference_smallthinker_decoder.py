"""Plain reference of a sparse-expert decoder whose router reads the
attention's input, with window and full attention layers side by side
(``model_type: smallthinker``).

Straightforward ``jax.numpy`` in float32 with matmul precision "highest":
no kernels, no cache, no batching, no routing by sorting. One head at a
time and one expert at a time (a masked loop over ALL experts, each
computed for every token and weighted by 0 where it was not chosen), so
that 8,192 positions fit beside 11 GB of weights. For layer ``l``, every
norm an RMSNorm (float32, eps inside the root) with a weight:

    x   = E[ids]                                        # no embedding scale
    a   = N1(x)
    z   = float32(a) float32(Wr)
    S   = top_k(z) ;  w = softmax(z[S])                 # k = 6 of 64
    q,k,v = a Wq, a Wk, a Wv                            # heads x head_dim
    sliding_window_layout[l] = 1: q,k = rope(q,k; rope_theta)
                                  key j visible to query i iff i-W < j <= i
    sliding_window_layout[l] = 0: no position encoding, j <= i
    o   = softmax(q k^T / sqrt(head_dim) + mask) v
    x   = x + o Wo
    m   = N2(x)
    x   = x + sum_{e in S} w_e * Wd_e(relu(Wg_e m) * Wu_e m)
    logits = Whead . N(x)

Rotate-half rotary embedding over the whole head (``rope_layout`` equals
``sliding_window_layout``: the configuration refuses layouts that
disagree); grouped-query attention in which query head i reads KV head
i // (heads / kv_heads); no QK-norm, no output gate, no shared expert, no
bias in any product; an untied output head.

Departures from the published description, which the program makes too:
the router's product is float32 at the highest precision from the bf16
activations and the bf16 router weight (bf16 cannot tell the 6th score
from the 7th often enough); the head's logits are float32.

Weights are the RUN'S OWN weights, fetched by name through ``get(name)``
and upcast where they are used. Linear weights are laid out [in, out];
the routed experts are stacked: ``mlp.{gate,up}_proj`` [E, hidden,
width], ``mlp.down_proj`` [E, width, hidden], ``mlp.router`` [hidden, E].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

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


def reglu(m, gate, up, down):
    return _mm(jax.nn.relu(_mm(m, gate)) * _mm(m, up), down)


def route(a, router, top_k):
    """[S, E] float32: the weight of every expert for every token, 0
    where it was not chosen: a softmax over the ``top_k`` largest of
    ``a Wr``."""
    z = _mm(a, router)
    top, sel = jax.lax.top_k(z, top_k)
    rows = jnp.arange(a.shape[0])[:, None]
    return jnp.zeros_like(z).at[rows, sel].set(jax.nn.softmax(top, -1))


def experts(m, weights, gate, up, down):
    """sum_e weights[:, e] * expert_e(m): every expert for every token."""
    def body(e, acc):
        return acc + weights[:, e][:, None] * reglu(m, gate[e], up[e],
                                                    down[e])

    return jax.lax.fori_loop(0, gate.shape[0], body, jnp.zeros_like(m))


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "eps", "theta", "window", "top_k"))
def _layer(x, w, *, heads, kv_heads, head_dim, eps, theta, window, top_k):
    """One layer on x [S, hidden]. ``window`` None = full attention and no
    position encoding."""
    s = x.shape[0]
    a = rms_norm(x, w["n1"], eps)
    weights = route(a, w["router"], top_k)
    q = _mm(a, w["q"]).reshape(s, heads, head_dim)
    k = _mm(a, w["k"]).reshape(s, kv_heads, head_dim)
    v = _mm(a, w["v"]).reshape(s, kv_heads, head_dim)
    if window is not None:
        q, k = rope(q, theta), rope(k, theta)
    o = attention(q, k, v, window).reshape(s, heads * head_dim)
    x = x + _mm(o, w["o"])
    m = rms_norm(x, w["n2"], eps)
    return x + experts(m, weights, w["ex_gate"], w["ex_up"], w["ex_down"])


_NAMES = {"q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
          "v": "self_attn.v_proj.weight", "o": "self_attn.o_proj.weight",
          "n1": "input_layernorm.weight",
          "n2": "post_attention_layernorm.weight",
          "router": "mlp.router", "ex_gate": "mlp.gate_proj",
          "ex_up": "mlp.up_proj", "ex_down": "mlp.down_proj"}


HEAD_COLUMNS = 32768     # of the output head at a time: its float32 copy
                         # is 0.34 GB so, not the whole head's 1.56 GB


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, eps):
    """x [1, S, hidden] -> float32 logits [1, S, V], written a block of
    HEAD_COLUMNS columns at a time into the one result (2,048 positions of
    151,936 columns are 1.2 GB: no second copy of them fits beside the
    run's weights and pools)."""
    xn = rms_norm(x, norm, eps)
    v = head.shape[1]
    out = jnp.zeros(x.shape[:2] + (v,), F32)
    for c in range(0, v, HEAD_COLUMNS):
        out = out.at[:, :, c:c + HEAD_COLUMNS].set(
            _mm(xn, head[:, c:c + HEAD_COLUMNS]))
    return out


def forward(get, cfg, ids, last: int = None):
    """float32 logits [B, S', V] for token ids [B, S], as a host (numpy)
    array; ``last`` keeps only the final ``last`` positions (the head is
    the widest product). On the host because a row's logits are as large
    as the room left on the device beside the run's weights and pools: a
    caller that indexes or keeps them would hold a second copy there.

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
        for i in range(cfg.num_hidden_layers):
            w = {k: get(f"model.layers.{i}.{n}") for k, n in _NAMES.items()}
            sliding = cfg.sliding_window_layout[i] == 1
            x = _layer(
                x, w, heads=cfg.num_attention_heads,
                kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
                eps=eps, theta=float(cfg.rope_theta),
                window=int(cfg.sliding_window_size) if sliding else None,
                top_k=cfg.moe_num_active_primary_experts)
        if last is not None:
            x = x[-last:]
        out.append(np.asarray(_head(x[None], get("model.norm.weight"),
                                    get("lm_head.weight"), eps)))
    return np.concatenate(out)
