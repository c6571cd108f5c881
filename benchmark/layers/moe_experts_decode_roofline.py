"""moe_experts_decode_roofline — layer "Pallas kernels".

The routed experts' products in DECODE against their bandwidth roofline.
A decode step of an expert layer has to stream the three matrices of
every expert that at least one live row chose, once; with a couple of
rows an expert it computes under 4 FLOPs a byte, so the least time is

    experts_hit x 3 x hidden x expert width x dtype bytes / HBM bandwidth

``experts_hit`` (an attribute of ``engine.segment``, counted inside the
segment's own program) is the number of experts chosen by at least one
live row, summed over the segment's steps and expert layers; the span is
matched to the ``jit_segment`` run it dispatched through the profiler's
host plane (``lib/host_spans.py``). Time: self time, inside those runs, of
the grouped-matmul Pallas kernel, found by its name ``gmm`` (see
``moe_experts_time_share.serve``). Weights only (no activations, which
are a few hundred rows), so the share errs low. Moves
``serve_tpot_p50_ms``.
"""
MODULE = "jit_segment"
SPAN = "engine.segment"
KERNEL = "gmm"


def expert_bytes(config: dict) -> int:
    """The three matrices of one routed expert, in the configuration's
    dtype."""
    import jax.numpy as jnp

    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * jnp.dtype(config["dtype"]).itemsize)


def read(ctx):
    import bisect

    from benchmark.lib import host_spans as hs
    from benchmark.lib import trace_reduce as tr

    runs = [r for r in hs.segment_runs(ctx, MODULE, SPAN)
            if "experts_hit" in r[2]]
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
        if tr.is_pallas(ev) and tr.op_name(ev) == KERNEL:
            i = bisect.bisect_right(starts, ev[1]) - 1
            if i >= 0 and ev[1] < runs[i][1]:
                kernel_ns += self_ns
    if not kernel_ns:
        raise ValueError(f"{len(runs)} {MODULE} runs matched a {SPAN} span "
                         f"but hold no Pallas operation named {KERNEL!r}")
    hit = sum(a["experts_hit"] for _, _, a in runs)
    least_s = hit * expert_bytes(ctx["config"]) / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (kernel_ns / 1e9)
