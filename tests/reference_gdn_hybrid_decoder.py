"""Plain reference of a decoder whose layers are gated-delta-rule linear
attention with a full-attention layer among them (``model_type:
olmo_hybrid``).

Straightforward ``jax.numpy`` in float32 with matmul precision "highest":
no kernels, no chunking, no cache, no batching. The recurrence runs as it
is written, one position at a time (``lax.scan``, in blocks of
``SCAN_BLOCK`` positions so that the scan's stacked inputs and outputs
stay small beside 8 GB of weights), and a full layer's attention one head
at a time. ``layer_types[l]`` names layer ``l``'s mixer; every norm is an
RMSNorm (float32, eps inside the root) with a weight, and FOLLOWS its
sublayer:

    x = E[ids]
    x = x + N_a(mixer(x))
    x = x + N_f(Wd(silu(Wg x) * Wu x))
    logits = Whead . N(x)

    linear_attention, per token t (H heads, dk key dims, dv value dims):
      u_t    = x_t [Wq | Wk | Wv]
      c_t    = silu(sum_{j<K} w_conv[:, j] * u_{t-K+1+j})   # zeros before 0
      q,k,v  = split(c_t) -> [H, dk], [H, dk], [H, dv]
      q      = q / sqrt(sum q^2 + 1e-6) * dk^-0.5
      k      = k / sqrt(sum k^2 + 1e-6)
      beta_t = 2 * sigmoid(x_t Wb)           # 2: linear_allow_neg_eigval
      g_t    = -exp(A_log) * softplus(x_t Wa + dt_bias)
      S      = exp(g_t) * S_{t-1}            # S: [H, dk, dv], S_0 = 0
      d_t    = beta_t * (v_t - k_t . S)
      S_t    = S + k_t (x) d_t
      o_t    = q_t . S_t
      y_t    = (N_o(o_t) * silu(x_t Wz)) Wo  # N_o over dv, one weight [dv]
    full_attention:
      q, k   = N_q(x Wq), N_k(x Wk)          # norms over the whole width
      v      = x Wv                          # heads x head_dim; no rotary
      y      = causal_softmax(q k^T / sqrt(head_dim)) v Wo

Grouped-query attention in which query head i reads KV head
i // (heads / kv_heads); no bias in any product; an untied output head.

Weights are the RUN'S OWN weights, fetched by name through ``get(name)``
and upcast where they are used. Linear weights are laid out [in, out]; the
convolution's ``linear_attn.conv1d`` is [q|k|v width, K].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
SCAN_BLOCK = 512         # positions a ``lax.scan`` of the recurrence
HEAD_COLUMNS = 32768     # of the output head at a time


def _mm(a, b):
    return jnp.matmul(a, b.astype(F32), precision=HIGHEST)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def l2norm(x, eps=1e-6):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def causal_conv(u, w):
    """u [S, C], w [C, K] -> [S, C]: position t sees u[t-K+1 .. t], zeros
    before position 0."""
    s, width = u.shape[0], w.shape[1]
    pad = jnp.concatenate([jnp.zeros((width - 1, u.shape[1]), F32), u])
    return sum(pad[j:j + s] * w[:, j].astype(F32) for j in range(width))


def delta_rule(q, k, v, g, beta):
    """The recurrence as written. q/k [S, H, dk], v [S, H, dv], g/beta
    [S, H] -> o [S, H, dv]; the state starts at zero."""
    def step(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[:, None, None]
        d = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, state,
                                           precision=HIGHEST))
        state = state + kt[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", qt, state, precision=HIGHEST)

    s = q.shape[0]
    state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    out = []
    for lo in range(0, s, SCAN_BLOCK):
        state, o = jax.lax.scan(
            step, state, tuple(a[lo:lo + SCAN_BLOCK]
                               for a in (q, k, v, g, beta)))
        out.append(o)
    return jnp.concatenate(out)


def attention(q, k, v):
    """q [S, H, D], k/v [S, Hkv, D] -> [S, H, D], causal. One head at a
    time: a head's [S, S] scores are the largest value alive."""
    s, h, d = q.shape
    group = h // k.shape[1]
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def head(n):
        kn, vn = k[:, n // group], v[:, n // group]
        scores = jnp.matmul(q[:, n], kn.T, precision=HIGHEST)
        scores = jnp.where(mask, scores / jnp.sqrt(F32(d)), -jnp.inf)
        return jnp.matmul(jax.nn.softmax(scores, axis=-1), vn,
                          precision=HIGHEST)

    return jnp.swapaxes(jax.lax.map(head, jnp.arange(h)), 0, 1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "lin_heads", "dk", "dv", "eps", "neg_eigval"))
def _layer(x, w, *, heads, kv_heads, lin_heads, dk, dv, eps, neg_eigval):
    """One layer on x [S, hidden]; ``"conv" in w`` = a linear layer."""
    s = x.shape[0]
    if "conv" in w:
        c = jax.nn.silu(causal_conv(_mm(x, w["qkv"]), w["conv"]))
        q, k, v = jnp.split(c, [lin_heads * dk, 2 * lin_heads * dk], axis=-1)
        q = l2norm(q.reshape(s, lin_heads, dk)) * dk ** -0.5
        k = l2norm(k.reshape(s, lin_heads, dk))
        beta = jax.nn.sigmoid(_mm(x, w["b"])) * (2.0 if neg_eigval else 1.0)
        g = -jnp.exp(w["A_log"].astype(F32)) * jax.nn.softplus(
            _mm(x, w["a"]) + w["dt_bias"].astype(F32))
        o = delta_rule(q, k, v.reshape(s, lin_heads, dv), g, beta)
        o = rms_norm(o, w["o_norm"], eps) * jax.nn.silu(
            _mm(x, w["z"])).reshape(s, lin_heads, dv)
        y = _mm(o.reshape(s, lin_heads * dv), w["o"])
    else:
        head_dim = w["q"].shape[1] // heads
        q = rms_norm(_mm(x, w["q"]), w["q_norm"], eps)
        k = rms_norm(_mm(x, w["k"]), w["k_norm"], eps)
        o = attention(q.reshape(s, heads, head_dim),
                      k.reshape(s, kv_heads, head_dim),
                      _mm(x, w["v"]).reshape(s, kv_heads, head_dim))
        y = _mm(o.reshape(s, heads * head_dim), w["o"])
    x = x + rms_norm(y, w["n_a"], eps)
    f = _mm(jax.nn.silu(_mm(x, w["ffn_gate"])) * _mm(x, w["ffn_up"]),
            w["ffn_down"])
    return x + rms_norm(f, w["n_f"], eps)


_REST = {"n_a": "post_attention_layernorm.weight",
         "n_f": "post_feedforward_layernorm.weight",
         "ffn_gate": "mlp.gate_proj.weight", "ffn_up": "mlp.up_proj.weight",
         "ffn_down": "mlp.down_proj.weight"}
_LINEAR = {"qkv": "linear_attn.in_proj_qkv.weight",
           "z": "linear_attn.in_proj_z.weight",
           "b": "linear_attn.in_proj_b.weight",
           "a": "linear_attn.in_proj_a.weight",
           "conv": "linear_attn.conv1d", "A_log": "linear_attn.A_log",
           "dt_bias": "linear_attn.dt_bias",
           "o_norm": "linear_attn.norm.weight",
           "o": "linear_attn.out_proj.weight"}
_FULL = {"q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
         "v": "self_attn.v_proj.weight", "o": "self_attn.o_proj.weight",
         "q_norm": "self_attn.q_norm.weight",
         "k_norm": "self_attn.k_norm.weight"}


@functools.partial(jax.jit, static_argnames=("eps", "start", "stop"))
def _head(x, norm, head, eps, start, stop):
    return _mm(rms_norm(x, norm, eps), head[:, start:stop])


def stacked_getter(params):
    """``get(name)`` over a dict of the model's parameters by name (this
    model is served, never trained: nothing is stacked)."""
    return params.__getitem__


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
        for i in range(cfg.num_hidden_layers):
            linear = cfg.layer_types[i] == "linear_attention"
            names = dict(_REST, **(_LINEAR if linear else _FULL))
            w = {k: get(f"model.layers.{i}.{n}") for k, n in names.items()}
            x = _layer(x, w, heads=cfg.num_attention_heads,
                       kv_heads=cfg.num_key_value_heads,
                       lin_heads=cfg.linear_num_key_heads,
                       dk=cfg.linear_key_head_dim,
                       dv=cfg.linear_value_head_dim, eps=eps,
                       neg_eigval=bool(cfg.linear_allow_neg_eigval))
        if last is not None:
            x = x[-last:]
        head = get("lm_head.weight")
        out.append(jnp.concatenate([
            _head(x, get("model.norm.weight"), head, eps, c,
                  min(c + HEAD_COLUMNS, head.shape[1]))
            for c in range(0, head.shape[1], HEAD_COLUMNS)], axis=-1))
    return jnp.stack(out)
