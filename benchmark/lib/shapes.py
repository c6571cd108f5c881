"""Operations a dense GQA decoder needs, computed from its shapes.

The yardstick's own arithmetic: nothing here is read from the program.
``cfg`` is any object or dict with the published keys (``hidden_size``,
``intermediate_size``, ``num_hidden_layers``, ``num_attention_heads``,
``num_key_value_heads``, ``vocab_size``).
"""
from __future__ import annotations


def _get(cfg, key):
    return cfg[key] if isinstance(cfg, dict) else getattr(cfg, key)


def dims(cfg) -> dict:
    h = _get(cfg, "hidden_size")
    nh = _get(cfg, "num_attention_heads")
    nkv = _get(cfg, "num_key_value_heads") or nh
    return {"h": h, "nh": nh, "nkv": nkv, "hd": h // nh,
            "ffn": _get(cfg, "intermediate_size"),
            "layers": _get(cfg, "num_hidden_layers"),
            "vocab": _get(cfg, "vocab_size")}


def layer_matmul_params(cfg) -> int:
    """Parameters of one layer that a token meets in a matrix product:
    q, k, v, o and the three SwiGLU matrices. Norm weights are not."""
    d = dims(cfg)
    attn = d["h"] * d["nh"] * d["hd"] * 2 + d["h"] * d["nkv"] * d["hd"] * 2
    return attn + 3 * d["h"] * d["ffn"]


def matmul_params(cfg) -> int:
    """All matmul parameters: the layers and the untied output head. The
    embedding table is a lookup, not a product, and is left out."""
    d = dims(cfg)
    return d["layers"] * layer_matmul_params(cfg) + d["h"] * d["vocab"]


def total_params(cfg) -> int:
    """Every parameter, for memory reckoning: matmuls, embedding, norms."""
    d = dims(cfg)
    return (matmul_params(cfg) + d["vocab"] * d["h"]
            + (2 * d["layers"] + 1) * d["h"])


def causal_attention_unit(cfg, batch: int, seq: int) -> float:
    """FLOPs of ONE causal [seq, seq] product over all heads of one layer
    (q k^T, or p v): 2 * batch * heads * head_dim * seq * (seq + 1) / 2."""
    d = dims(cfg)
    return 2.0 * batch * d["nh"] * d["hd"] * seq * (seq + 1) / 2.0


def train_flops_per_step(cfg, batch: int, seq: int) -> float:
    """Forward + backward of one step, as the mathematics requires them:
    6 FLOPs per matmul parameter per token, and per layer the causal
    attention's 2 forward and 4 backward products. Recomputation (remat,
    the flash backward's second q k^T) is not counted: it is work the
    method adds, not work the model needs."""
    d = dims(cfg)
    tokens = batch * seq
    attn = d["layers"] * 6 * causal_attention_unit(cfg, batch, seq)
    return 6.0 * matmul_params(cfg) * tokens + attn


def flash_train_flops_per_step(cfg, batch: int, seq: int) -> float:
    """What the flash kernels of one train step with full remat have to
    compute at the least: the forward twice (forward pass and remat
    re-forward, 2 products each) and one backward (q k^T again, dp, dv,
    dk, dq: 5 products). The kernels as written run more (the two
    backward kernels each rebuild q k^T and dp: 7 products), so a share
    worked out from this count errs low, never high."""
    d = dims(cfg)
    return d["layers"] * (2 * 2 + 5) * causal_attention_unit(cfg, batch, seq)


def prefill_flops(cfg, prompt_len: int) -> float:
    """Forward of one prompt of ``prompt_len`` tokens; the head runs on
    the last position only."""
    d = dims(cfg)
    body = 2.0 * d["layers"] * layer_matmul_params(cfg) * prompt_len
    attn = d["layers"] * 2 * causal_attention_unit(cfg, 1, prompt_len)
    return body + attn + 2.0 * d["h"] * d["vocab"]


def decode_step_bytes(cfg, bytes_per_weight: int = 2) -> float:
    """Bytes of weights one decode step streams from HBM: every matmul
    parameter once. The KV pages read come on top (they need each call's
    context lengths, which the engine does not count yet)."""
    return float(bytes_per_weight) * matmul_params(cfg)
