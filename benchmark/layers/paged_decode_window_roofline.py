"""paged_decode_window_roofline — layer "Pallas kernels".

The decode attention kernel against its bandwidth roofline in a model
whose layers are part full attention and part sliding window (and whose
head size is the configuration's ``head_dim``, not hidden / heads: why
this cell is not read by ``paged_decode_roofline``). A full layer reads a
row's whole context, a window layer at most the last ``sliding_window``
positions. A segment of ``steps`` steps over ``rows`` live rows that hold
``ctx_tokens`` tokens, ``ctx_tokens_window`` of them inside their rows'
windows, at its start reads at the least

    full layers   x (steps x ctx_tokens + rows x steps x (steps - 1) / 2)
  + window layers x  steps x ctx_tokens_window

tokens (a row that has filled its window reads no more as it grows, one
that has not is counted as if it had: the share errs low), each
``2 (K, V) x KV heads x head_dim x dtype bytes``. The counters are
attributes of the engine's ``engine.segment`` span; the span is matched to
the ``jit_segment`` run it dispatched through the profiler's host plane
(``lib/host_spans.py``). Time: self time of the ``paged_decode*`` Pallas
operations inside those runs, both kinds of layer together. KV only, tokens
and not whole pages. Moves ``serve_tpot_p50_ms``.
"""
MODULE = "jit_segment"
SPAN = "engine.segment"
KERNEL = "paged_decode"
WINDOW_LAYER = "sliding_attention"


def kv_bytes_per_token_layer(config: dict) -> int:
    """K and V of one token in one layer, in the configuration's dtype."""
    import jax.numpy as jnp

    return (2 * config["num_key_value_heads"] * config["head_dim"]
            * jnp.dtype(config["dtype"]).itemsize)


def layer_tokens(config: dict, a: dict) -> int:
    """Tokens one segment reads at the least, all layers together."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    window = sum(k == WINDOW_LAYER for k in kinds)
    full = len(kinds) - window
    steps = a["steps"]
    return (full * (steps * a["ctx_tokens"]
                    + a["rows"] * steps * (steps - 1) // 2)
            + window * steps * a["ctx_tokens_window"])


def read(ctx):
    import bisect

    from benchmark.lib import host_spans as hs
    from benchmark.lib import trace_reduce as tr

    runs = [r for r in hs.segment_runs(ctx, MODULE, SPAN)
            if "ctx_tokens_window" in r[2]]
    if not runs:
        return None
    peaks = ctx.get("peaks")
    if peaks is None:
        import jax

        from benchmark.lib.peaks import peaks as peaks_of

        peaks = peaks_of(jax.devices()[0].device_kind)
    starts = [r[0] for r in runs]
    ops = tr.line_events(tr.device_planes(ctx["raw"])[0], tr.OPS_LINE)
    kernel_ns = 0
    for ev, self_ns in zip(ops, tr.self_times(ops)):
        if tr.is_pallas(ev) and tr.op_name(ev).startswith(KERNEL):
            i = bisect.bisect_right(starts, ev[1]) - 1
            if i >= 0 and ev[1] < runs[i][1]:
                kernel_ns += self_ns
    if not kernel_ns:
        raise ValueError(f"{len(runs)} {MODULE} runs matched a {SPAN} span "
                         f"but hold no {KERNEL} kernel operation")
    tokens = sum(layer_tokens(ctx["config"], a) for _, _, a in runs)
    least_s = tokens * kv_bytes_per_token_layer(ctx["config"]) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (kernel_ns / 1e9)
