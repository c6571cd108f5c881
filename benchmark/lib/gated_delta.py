"""What the readers of the gated-delta-rule metrics share: which device
operations are the linear-attention mechanism, and the bytes and FLOPs it
needs at the least.

The operations are recognised as follows (an event's text is the compiled
instruction: its name, the first array type of its result, its operands'
types):

- the chunked scan of an admission: the Pallas kernel named
  ``gdn_chunk_prefill``;
- the one-token update of a decode step: the Pallas kernel named
  ``gdn_decode_step``, and every other operation whose result is
  state-shaped, ``f32[rows, heads, dk, dv]`` (``rows`` = the engine's
  ``max_batch``): what an XLA composition of the update makes, and the
  copies of the states if a program makes any;
- the convolution, in both forms: every other operation with the
  convolution's weight ``[conv_dim, K]``, or one tap of it ``[conv_dim]``
  (the prefill's form multiplies tap by tap), among its operands;
- the elementwise work around them (the L2 norm of q and k, the gates'
  cumulative sums, the gated norm, the transposes into and out of the
  kernels' layouts): every other operation whose result has the linear
  heads among its dimensions and ``dk`` or ``dv`` last. The full
  layers' heads are 128 wide and do not match.

The projections (``Wq | Wk | Wv``, ``Wz``, ``Wa``, ``Wb``, ``Wo``) are
weight products like the FFN's and are not counted.
"""
import re

from benchmark.lib import trace_reduce as tr

SCAN_KERNEL = "gdn_chunk_prefill"
STEP_KERNEL = "gdn_decode_step"
LINEAR_LAYER = "linear_attention"
_TYPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")


def geometry(ctx) -> dict:
    cfg = ctx["config"]
    heads, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    dv = cfg["linear_value_head_dim"]
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    linear = sum(k == LINEAR_LAYER for k in kinds)
    return {"rows": ctx["mix"]["engine"]["max_batch"], "heads": heads,
            "dk": dk, "dv": dv, "conv_dim": heads * (2 * dk + dv),
            "conv_k": cfg["linear_conv_kernel_dim"],
            "linear_layers": linear, "full_layers": len(kinds) - linear}


def array_types(event) -> list:
    """[(dtype, [dims])] of every array type in an instruction's text, the
    result's first."""
    return [(m.group(1), [int(d) for d in m.group(2).split(",") if d])
            for m in _TYPE.finditer(event[0])]


def kind(event, geo) -> str:
    """"scan", "update", "conv", "elementwise" or "" for a device
    operation."""
    if tr.is_pallas(event):
        name = tr.op_name(event)
        return ("scan" if name.startswith(SCAN_KERNEL) else
                "update" if name.startswith(STEP_KERNEL) else "")
    types = array_types(event)
    if not types:
        return ""
    dims = types[0][1]
    if dims == [geo["rows"], geo["heads"], geo["dk"], geo["dv"]]:
        return "update"
    taps = ([geo["conv_dim"], geo["conv_k"]], [geo["conv_dim"]])
    if any(d in taps for _, d in types[1:]):
        return "conv"
    if (len(dims) >= 2 and dims[-1] in (geo["dk"], geo["dv"])
            and geo["heads"] in dims[:-1]):
        return "elementwise"
    return ""


def times(ctx, lo=None, hi=None) -> dict:
    """{kind: self ns} of the first chip's operations (those that start in
    ``[lo, hi)`` where given)."""
    geo = geometry(ctx)
    ops = tr.line_events(tr.device_planes(ctx["raw"])[0], tr.OPS_LINE)
    by = {"scan": 0, "update": 0, "conv": 0, "elementwise": 0}
    for ev, self_ns in zip(ops, tr.self_times(ops)):
        if (lo is None or ev[1] >= lo) and (hi is None or ev[1] < hi):
            k = kind(ev, geo)
            if k:
                by[k] += self_ns
    return by


def peaks_of(ctx) -> dict:
    peaks = ctx.get("peaks")
    if peaks is None:
        import jax

        from benchmark.lib.peaks import peaks as table

        peaks = table(jax.devices()[0].device_kind)
    return peaks


def state_bytes(geo) -> int:
    """One row's float32 state of one linear layer."""
    return geo["heads"] * geo["dk"] * geo["dv"] * 4


def row_state_bytes(config: dict, geo) -> int:
    """What one row keeps in one linear layer: the state and the last
    ``K - 1`` inputs of the convolution."""
    import jax.numpy as jnp

    return state_bytes(geo) + ((geo["conv_k"] - 1) * geo["conv_dim"]
                               * jnp.dtype(config["dtype"]).itemsize)


def step_least_s(geo, state_rows: int, peaks) -> float:
    """The least time of the decode updates of ``state_rows`` (row, step)
    pairs: one read and one write of each state, every linear layer."""
    return (state_rows * geo["linear_layers"] * 2 * state_bytes(geo)
            / peaks["hbm_bytes_per_s"])


def scan_least_s_per_position(geo, peaks) -> float:
    """The least time a position of one linear layer's scan takes: the
    larger of the recurrence's own FLOPs (decay, k.S, the outer product,
    q.S: 6 a state element, which every chunked form exceeds) over the
    peak and the bf16 bytes of q, k, v, o over the bandwidth."""
    flops = 6 * geo["dk"] * geo["dv"] * geo["heads"]
    nbytes = geo["heads"] * 2 * (geo["dk"] + geo["dv"]) * 2
    return max(flops / peaks["flops_bf16"],
               nbytes / peaks["hbm_bytes_per_s"])


def full_kv_bytes_per_token(config: dict, geo) -> int:
    """K and V of one token over the full-attention layers (the layers
    that keep pages), in the configuration's dtype."""
    import jax.numpy as jnp

    head = config["hidden_size"] // config["num_attention_heads"]
    return (2 * config["num_key_value_heads"] * head
            * jnp.dtype(config["dtype"]).itemsize * geo["full_layers"])
