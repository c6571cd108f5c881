"""paged_decode_roofline — layer "Pallas kernels".

The decode attention kernel against its bandwidth roofline. Least time:
the KV bytes the kernel must read over the chip's HBM bandwidth. A
decode segment of ``steps`` steps over ``rows`` live rows that hold
``ctx_tokens`` tokens (prompt + generated) at its start reads, at step j,
every row's context plus the j tokens written so far:

    tokens = steps x ctx_tokens + rows x steps x (steps - 1) / 2
    bytes  = tokens x layers x 2 (K, V) x KV heads x head size x dtype bytes

The three counters are attributes of the engine's ``engine.segment``
span (host bookkeeping, ``paddle_tpu.tracing``); the span is matched to
the ``jit_segment`` run it dispatched through the profiler's host plane
(``lib/host_spans.py``). Time: self time of the ``paged_decode*`` Pallas
operations inside those runs. KV only (no query, output, page table),
tokens and not whole pages, rows counted to the segment's end as the
program computes them: close to the least the method needs, so the
share errs low. Bandwidth-bound by construction (one query token per
row: under 1 FLOP per byte). Moves ``serve_tpot_p50_ms``.
"""
MODULE = "jit_segment"
SPAN = "engine.segment"
KERNEL = "paged_decode"


def kv_bytes_per_token(config: dict) -> int:
    """K and V of one token over all layers, in the configuration's
    dtype."""
    import jax.numpy as jnp

    from benchmark.lib.shapes import dims

    d = dims(config)
    return (2 * d["nkv"] * d["hd"] * jnp.dtype(config["dtype"]).itemsize
            * d["layers"])


def read(ctx):
    import bisect

    from benchmark.lib import host_spans as hs
    from benchmark.lib import trace_reduce as tr

    runs = [r for r in hs.segment_runs(ctx, MODULE, SPAN)
            if "ctx_tokens" in r[2]]
    if not runs:
        return None
    peaks = ctx.get("peaks")
    if peaks is None:
        import jax

        from benchmark.lib.peaks import peaks as peaks_of

        peaks = peaks_of(jax.devices()[0].device_kind)
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
    least_s = tokens * kv_bytes_per_token(ctx["config"]) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (kernel_ns / 1e9)
