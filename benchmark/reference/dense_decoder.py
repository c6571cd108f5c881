"""Plain reference of a dense GQA decoder-only language model.

Straightforward ``jax.numpy`` in float32 with matmul precision "highest":
no kernels, no cache, no batching tricks, no scan. One file for every
configuration whose published block is

    x = x + Wo . Attention(rope(Wq . n1(x)), rope(Wk . n1(x)), Wv . n1(x))
    x = x + Wdown . (silu(Wgate . n2(x)) * (Wup . n2(x)))
    logits = Whead . n(x)

with RMSNorm (float32, eps inside the root), rotate-half rotary embedding
at ``rope_theta`` over the whole head, causal softmax attention scaled by
1/sqrt(head_dim), grouped-query attention in which query head i reads KV
head i // (heads / kv_heads), SwiGLU, no bias anywhere, and an untied
output head (``tie_word_embeddings`` true reuses the embedding).

Departures from the published descriptions (InternLM2-1.8B,
Mistral-7B-v0.3), each the same mathematics or out of every cell's reach:

- InternLM2 stores q, k, v packed in one matrix ``wqkv``; here they are
  three matrices.
- InternLM2's dynamic-NTK rope scaling acts only past 32768 positions;
  Mistral-7B-v0.3 has no sliding window. Neither is modelled.

Weights are the RUN'S OWN (bf16) weights, fetched by their names through
``get(name)`` and upcast one layer at a time, so that the reference fits
beside the model it checks. Linear weights are laid out [in, out], as the
program stores them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope_tables(seq: int, head_dim: int, theta: float):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    ang = jnp.outer(jnp.arange(seq, dtype=jnp.float32), inv)
    return jnp.cos(ang), jnp.sin(ang)


def rope(x, cos, sin):
    """x [B, S, H, D]: rotate-half."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v):
    """q [B, S, H, D], k/v [B, S, Hkv, D] -> [B, S, H, D], causal."""
    b, s, h, d = q.shape
    group = h // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
    scores = scores / jnp.sqrt(jnp.float32(d))
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)


def _layer(x, w, cos, sin, heads, kv_heads, eps):
    w = {k: a.astype(jnp.float32) for k, a in w.items()}
    b, s, hidden = x.shape
    hd = hidden // heads
    xn = rms_norm(x, w["n1"], eps)
    q = rope(_mm(xn, w["q"]).reshape(b, s, heads, hd), cos, sin)
    k = rope(_mm(xn, w["k"]).reshape(b, s, kv_heads, hd), cos, sin)
    v = _mm(xn, w["v"]).reshape(b, s, kv_heads, hd)
    ctx = attention(q, k, v).reshape(b, s, heads * hd)
    x = x + _mm(ctx, w["o"])
    xn = rms_norm(x, w["n2"], eps)
    return x + _mm(jax.nn.silu(_mm(xn, w["gate"])) * _mm(xn, w["up"]),
                   w["down"])


_layer_jit = jax.jit(_layer, static_argnums=(4, 5, 6))

_NAMES = {"q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
          "v": "self_attn.v_proj.weight", "o": "self_attn.o_proj.weight",
          "gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight",
          "down": "mlp.down_proj.weight", "n1": "input_layernorm.weight",
          "n2": "post_attention_layernorm.weight"}


def _head(x, norm, head, eps):
    return _mm(rms_norm(x, norm.astype(jnp.float32), eps),
               head.astype(jnp.float32))


_head_jit = jax.jit(_head, static_argnums=(3,))


def forward(get, cfg, ids, last: int = None):
    """float32 logits [B, S', V] for token ids [B, S]; ``last`` keeps only
    the final ``last`` positions (the head is the widest product).

    ``get(name)`` returns the weight stored under the published name
    (``model.embed_tokens.weight``, ``model.layers.<i>.<...>``,
    ``model.norm.weight``, ``lm_head.weight``); ``cfg`` has the published
    keys as attributes."""
    ids = jnp.asarray(ids)
    heads = cfg.num_attention_heads
    kv_heads = cfg.num_key_value_heads or heads
    x = jnp.take(get("model.embed_tokens.weight"), ids,
                 axis=0).astype(jnp.float32)
    cos, sin = rope_tables(ids.shape[1], cfg.hidden_size // heads,
                           float(cfg.rope_theta))
    for i in range(cfg.num_hidden_layers):
        w = {k: get(f"model.layers.{i}.{n}") for k, n in _NAMES.items()}
        x = _layer_jit(x, w, cos, sin, heads, kv_heads,
                       float(cfg.rms_norm_eps))
    if last is not None:
        x = x[:, -last:]
    head = (get("model.embed_tokens.weight").T
            if getattr(cfg, "tie_word_embeddings", False)
            else get("lm_head.weight"))
    return _head_jit(x, get("model.norm.weight"), head,
                     float(cfg.rms_norm_eps))


def stacked_getter(stacked: dict, rest: dict):
    """``get`` over the scan-over-layers layout: ``stacked[<...>]`` holds
    every layer's weight along a leading axis, ``rest`` the others."""
    import re

    pat = re.compile(r"^model\.layers\.(\d+)\.(.+)$")

    def get(name):
        m = pat.match(name)
        return stacked[m.group(2)][int(m.group(1))] if m else rest[name]

    return get
