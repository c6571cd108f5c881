"""What the readers of the hybrid of latent attention and grouped
gated-delta-rule layers share (``serve-gigachat3.5-longdocs``): which
device operations are its two mixers, and the bytes and FLOPs each has to
move or do at the least.

The operations are recognised as follows (an event's text is the compiled
instruction: its name, the first array type of its result, its operands'
types):

- the dense latent decode: the Pallas kernel named ``paged_latent_decode``;
- the causal prefill attention of a full layer: the Pallas kernel named
  ``mla_selected_prefill``, whose first operand is a group's queries
  ``[heads, bucket, nope + rope]``;
- the linear layers' one-token update: the Pallas kernel named
  ``gdn_decode_step``, every other operation whose result is
  state-shaped, ``f32[rows, value heads, dk, dv]`` (``rows`` = the
  engine's ``max_batch``), and the operations that lay q and k out for the
  kernel's blocks: a float32 result of four axes, ``rows`` first, that
  holds a row's key heads x dk with dk one of its last two axes (``[rows,
  groups, key heads a group, dk]`` and its transpose ``[rows, groups, dk,
  key heads a group]``);
- the linear layers' convolution: every other operation with the
  convolution's weight ``[conv width, K]`` or one tap of it ``[conv
  width]`` among its operands (conv width = key heads x 2 dk + value heads
  x dv).

``benchmark/lib/gated_delta.py`` reads the other hybrid, whose heads are
one count; its shapes do not match these.
"""
import bisect

from benchmark.lib import host_spans as hs
from benchmark.lib import trace_reduce as tr
from benchmark.lib.gated_delta import array_types, peaks_of

MODULE = "jit_segment"
SEGMENT = "engine.segment"
PREFILL = "engine.prefill"
LATENT_KERNEL = "paged_latent_decode"
CAUSAL_KERNEL = "mla_selected_prefill"
STEP_KERNEL = "gdn_decode_step"


def geometry(ctx) -> dict:
    cfg = ctx["config"]
    full = len([i for i in cfg["full_attention_layers"]
                if i < cfg["num_hidden_layers"]])
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return {"rows": ctx["mix"]["engine"]["max_batch"],
            "full_layers": full,
            "linear_layers": cfg["num_hidden_layers"] - full,
            "heads": cfg["num_attention_heads"],
            "qk": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "latent": cfg["kv_lora_rank"],
            "row": cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
            "key_heads": hk, "value_heads": hv, "dk": dk, "dv": dv,
            "conv_dim": hk * 2 * dk + hv * dv,
            "conv_k": cfg["linear_conv_kernel_dim"]}


def dtype_bytes(config: dict) -> int:
    import jax.numpy as jnp

    return jnp.dtype(config["dtype"]).itemsize


def kind(event, geo) -> str:
    """"latent", "causal", "update", "conv" or "" for a device
    operation."""
    if tr.is_pallas(event):
        name = tr.op_name(event)
        return ("latent" if name.startswith(LATENT_KERNEL) else
                "causal" if name.startswith(CAUSAL_KERNEL) else
                "update" if name.startswith(STEP_KERNEL) else "")
    types = array_types(event)
    if not types:
        return ""
    dtype, dims = types[0]
    if dims == [geo["rows"], geo["value_heads"], geo["dk"], geo["dv"]]:
        return "update"
    if (dtype == "f32" and len(dims) == 4 and dims[0] == geo["rows"]
            and geo["dk"] in dims[2:]
            and dims[1] * dims[2] * dims[3] == geo["key_heads"] * geo["dk"]):
        return "update"
    taps = ([geo["conv_dim"], geo["conv_k"]], [geo["conv_dim"]])
    if any(d in taps for _, d in types[1:]):
        return "conv"
    return ""


def segment_times(ctx):
    """(matched runs, {kind: self ns}, self ns of every operation) inside
    the ``jit_segment`` runs that a traced ``engine.segment`` span with the
    hybrid's counters dispatched; (None, None, None) without one."""
    runs = [r for r in hs.segment_runs(ctx, MODULE, SEGMENT)
            if "latent_rows_attended" in r[2] and "state_rows" in r[2]]
    if not runs:
        return None, None, None
    geo = geometry(ctx)
    starts = [r[0] for r in runs]
    ops = tr.line_events(tr.device_planes(ctx["raw"])[0], tr.OPS_LINE)
    by, whole = {"latent": 0, "causal": 0, "update": 0, "conv": 0}, 0
    for ev, self_ns in zip(ops, tr.self_times(ops)):
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i < 0 or ev[1] >= runs[i][1]:
            continue
        whole += self_ns
        k = kind(ev, geo)
        if k:
            by[k] += self_ns
    return runs, by, whole


def latent_decode_least_s(ctx, rows_attended: int) -> float:
    """The least time of the dense latent decode over ``rows_attended``
    (row, step, position) triples of one full layer: every full layer
    reads each position's ``c | k_rope`` once, ``row`` wide; the FLOPs
    (2 x heads x (row + latent) a position: the scores and the context)
    are under the bytes at the chip's ridge and are counted as the larger
    of the two."""
    geo, peaks = geometry(ctx), peaks_of(ctx)
    n = geo["full_layers"] * rows_attended
    return max(n * geo["row"] * dtype_bytes(ctx["config"])
               / peaks["hbm_bytes_per_s"],
               n * 2 * geo["heads"] * (geo["row"] + geo["latent"])
               / peaks["flops_bf16"])


def update_least_s(ctx, state_rows: int) -> float:
    """The least time of the linear layers' updates of ``state_rows``
    (row, step) pairs: one read and one write of each value head's float32
    state, every linear layer."""
    geo = geometry(ctx)
    return (state_rows * geo["linear_layers"] * 2 * geo["value_heads"]
            * geo["dk"] * geo["dv"] * 4 / peaks_of(ctx)["hbm_bytes_per_s"])


def causal_flops(geo, heads: int, plen: int) -> float:
    """The causal attention's FLOPs of ``heads`` heads over a prompt of
    ``plen``: 2 x (qk + v) a (query, key) pair at or under the diagonal."""
    return 2.0 * heads * (geo["qk"] + geo["v"]) * plen * (plen + 1) / 2


def prefill_spans(ctx):
    """The window's ``engine.prefill`` spans with a ``plen``, by start;
    None without a view."""
    v = hs.view(ctx)
    if v is None:
        return None
    return sorted((s for s in v["spans"].values()
                   if s["name"] == PREFILL and "plen" in s["attrs"]),
                  key=lambda s: s["start"])
