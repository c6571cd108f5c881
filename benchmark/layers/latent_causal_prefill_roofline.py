"""latent_causal_prefill_roofline — layer "Pallas kernels".

The full layers' causal prefill attention (the Pallas kernel
``mla_selected_prefill`` with no selection mask: the causal compare in
the diagonal block alone) against its FLOP roofline. Least FLOPs of one
call: 2 x heads x (qk_nope + qk_rope + v) a (query, key) pair at or under
the diagonal up to the admission's ``plen``, ``heads`` being the call's own
(the first dimension of its queries: a call is one group of heads). The
kernel walks whole blocks up to the block of the prompt's last position,
so the share errs low by the blocks' overhang.

An admission's calls are those that start after its ``engine.prefill``
span begins (``plen`` is the span's) and before the next's. Each call is
counted with its own heads, so an admission cut by the traced window's
edge counts the calls seen and no more; calls before the first span of the
trace belong to an admission whose span is not in it and are left out of
both sides. Time: the calls' self time, over 197 TFLOP/s (bf16).
Moves ``serve_tpot_p50_ms`` (an admission stalls the rows in flight).
"""


def read(ctx):
    import bisect

    from benchmark.lib import latent_hybrid as lh
    from benchmark.lib import trace_reduce as tr
    from benchmark.lib.gated_delta import array_types, peaks_of

    spans = lh.prefill_spans(ctx)
    if not spans:
        return None
    geo = lh.geometry(ctx)
    starts = [s["start"] for s in spans]
    ops = tr.line_events(tr.device_planes(ctx["raw"])[0], tr.OPS_LINE)
    flops = kernel_ns = 0
    for ev, self_ns in zip(ops, tr.self_times(ops)):
        if lh.kind(ev, geo) != "causal":
            continue
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i < 0:
            continue
        # the queries [heads, S, qk], the first such operand
        heads = next(d[0] for _, d in array_types(ev)[1:]
                     if len(d) == 3 and d[-1] == geo["qk"])
        flops += lh.causal_flops(geo, heads, spans[i]["attrs"]["plen"])
        kernel_ns += self_ns
    if not kernel_ns:
        return None            # no admission's attention inside the trace
    return 100.0 * flops / peaks_of(ctx)["flops_bf16"] / (kernel_ns / 1e9)
