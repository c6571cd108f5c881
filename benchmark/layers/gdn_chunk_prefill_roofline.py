"""gdn_chunk_prefill_roofline — layer "Pallas kernels".

The chunked scan of an admission (the Pallas kernel ``gdn_chunk_prefill``)
against its roofline. Least time a position and linear layer: the larger
of the recurrence's own ``6 x dk x dv x heads`` FLOPs over 197 TFLOP/s and
the bf16 bytes of q, k, v, o over 819 GB/s (``lib/gated_delta.py``; at 96 x
192 x 30 the bytes bind). The count is the recurrence's, which every
chunked form exceeds, and of the prompt's ``plen`` positions, not the
bucket's, so the share errs low.

An admission's kernel calls are those that start after its
``engine.prefill`` span begins (``plen`` is the span's) and before the
next's; one cut by the traced window's edge has fewer than one call a
linear layer inside it, and counts ``plen`` x the calls seen (a whole
admission: x linear layers). Calls before the first span of the trace
belong to an admission whose span is not in it and are left out of both
sides. Moves ``serve_tpot_p50_ms`` (an admission stalls the rows in
flight).
"""
SPAN = "engine.prefill"


def read(ctx):
    import bisect

    from benchmark.lib import gated_delta as gd
    from benchmark.lib import host_spans as hs
    from benchmark.lib import trace_reduce as tr

    v = hs.view(ctx)
    if v is None:
        return None
    spans = sorted((s for s in v["spans"].values()
                    if s["name"] == SPAN and "plen" in s["attrs"]),
                   key=lambda s: s["start"])
    if not spans:
        return None
    starts = [s["start"] for s in spans]
    ops = tr.line_events(tr.device_planes(ctx["raw"])[0], tr.OPS_LINE)
    positions = kernel_ns = 0
    for ev, self_ns in zip(ops, tr.self_times(ops)):
        if tr.is_pallas(ev) and tr.op_name(ev).startswith(gd.SCAN_KERNEL):
            i = bisect.bisect_right(starts, ev[1]) - 1
            if i >= 0:
                positions += spans[i]["attrs"]["plen"]
                kernel_ns += self_ns
    if not kernel_ns:
        return None            # no admission's scan inside the trace
    least_s = positions * gd.scan_least_s_per_position(
        gd.geometry(ctx), gd.peaks_of(ctx))
    return 100.0 * least_s / (kernel_ns / 1e9)
