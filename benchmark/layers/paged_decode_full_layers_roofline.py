"""paged_decode_full_layers_roofline — layer "Pallas kernels".

``paged_decode_roofline``'s count for a model in which only SOME layers
attend through pages: the KV bytes the decode attention kernel must read,

    tokens = steps x ctx_tokens + rows x steps x (steps - 1) / 2
    bytes  = tokens x full layers x 2 (K, V) x KV heads x head size x dtype bytes

with the number of ``full_attention`` layers of ``layer_types`` in place of
all layers (a linear-attention layer reads a state, not a context;
``lib/gated_delta.py`` counts them), over
the self time of the ``paged_decode*`` Pallas operations inside the
``jit_segment`` runs matched to a traced ``engine.segment`` span, x 819
GB/s. KV only, tokens and not pages: it errs low. Moves
``serve_tpot_p50_ms``.
"""
MODULE = "jit_segment"
SPAN = "engine.segment"
KERNEL = "paged_decode"


def read(ctx):
    import bisect

    from benchmark.lib import gated_delta as gd
    from benchmark.lib import host_spans as hs
    from benchmark.lib import trace_reduce as tr

    runs = [r for r in hs.segment_runs(ctx, MODULE, SPAN)
            if "ctx_tokens" in r[2]]
    if not runs:
        return None
    tokens = sum(a["steps"] * a["ctx_tokens"]
                 + a["rows"] * a["steps"] * (a["steps"] - 1) // 2
                 for _, _, a in runs)
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
    least_s = (tokens * gd.full_kv_bytes_per_token(ctx["config"],
                                                   gd.geometry(ctx))
               / gd.peaks_of(ctx)["hbm_bytes_per_s"])
    return 100.0 * least_s / (kernel_ns / 1e9)
