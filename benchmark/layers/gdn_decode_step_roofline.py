"""gdn_decode_step_roofline — layer "Pallas kernels".

The one-token state update of the linear-attention layers against its
bandwidth roofline. Least time: one read and one write of every live
row's float32 state, every linear layer and step:

    state_rows x linear layers x 2 x heads x dk x dv x 4 B / 819 GB/s

``state_rows`` (an attribute of ``engine.segment``, counted inside the
segment's own program and summed over its steps: the (row, step) pairs
whose state was updated). Time: self time of the update's operations (the
Pallas kernel ``gdn_decode_step``, and any operation with a state-shaped
result: ``lib/gated_delta.py``) inside the ``jit_segment`` runs matched to
the span. Bandwidth-bound by construction (6 FLOPs a state element read
and written). Moves ``serve_tpot_p50_ms``.
"""
MODULE = "jit_segment"
SPAN = "engine.segment"


def read(ctx):
    from benchmark.lib import gated_delta as gd
    from benchmark.lib import host_spans as hs

    runs = [r for r in hs.segment_runs(ctx, MODULE, SPAN)
            if "state_rows" in r[2]]
    if not runs:
        return None
    update_ns = sum(gd.times(ctx, lo, hi)["update"] for lo, hi, _ in runs)
    if not update_ns:
        raise ValueError(f"{len(runs)} {MODULE} runs matched a {SPAN} span "
                         f"but hold no {gd.STEP_KERNEL} operation")
    least_s = gd.step_least_s(gd.geometry(ctx),
                              sum(a["state_rows"] for _, _, a in runs),
                              gd.peaks_of(ctx))
    return 100.0 * least_s / (update_ns / 1e9)
