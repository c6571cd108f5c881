"""gdn_grouped_decode_roofline — layer "Pallas kernels".

The one-token state update of the linear layers with grouped heads (64
value heads over 32 key heads) against its bandwidth roofline. Least time:
one read and one write of every live row's float32 state, every value
head, linear layer and step:

    state_rows x linear layers x 2 x value heads x dk x dv x 4 B / HBM
    bandwidth

``state_rows`` (an attribute of ``engine.segment``, counted inside the
segment's own program and summed over its steps: the (row, step) pairs
whose state was updated). Time: self time of the update's operations (the
Pallas kernel ``gdn_decode_step``, any operation with a state-shaped
result, and the copies that lay q and k out for the kernel's blocks:
``lib/latent_hybrid.py``) inside the matched ``jit_segment`` runs. The key
heads' bytes (q and k, a few KB a row) are not counted, so the share errs
low by as much. Moves ``serve_tpot_p50_ms``.
"""


def read(ctx):
    from benchmark.lib import latent_hybrid as lh

    runs, by, _ = lh.segment_times(ctx)
    if runs is None:
        return None
    if not by["update"]:
        raise ValueError(f"{len(runs)} {lh.MODULE} runs matched a "
                         f"{lh.SEGMENT} span but hold no {lh.STEP_KERNEL} "
                         f"operation")
    least_s = lh.update_least_s(ctx, sum(a["state_rows"]
                                         for _, _, a in runs))
    return 100.0 * least_s / (by["update"] / 1e9)
