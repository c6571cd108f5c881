"""latent_dense_decode_roofline — layer "Pallas kernels".

The dense latent decode (the Pallas kernel ``paged_latent_decode``: one
absorbed query a row against every cached row of its context) against its
bandwidth roofline. A step of a full layer has to read each position's
``c | k_rope`` row once: at the least

    full layers x latent_rows_attended x (kv_lora_rank + qk_rope_head_dim)
    x dtype bytes / HBM bandwidth

a segment (``latent_rows_attended``: an attribute of ``engine.segment``,
counted inside the segment's own program and summed over its steps: each
step, the sum over live rows of the context attended). The FLOP term,
2 x heads x (576 + 512) a position, is under it at the chip's ridge
(``lib/latent_hybrid.py``). Time: self time of the kernel inside the
matched ``jit_segment`` runs. The rows are stored 640 wide and a page is
copied whole, so the share sits under 100 by construction. Moves
``serve_tpot_p50_ms``.
"""


def read(ctx):
    from benchmark.lib import latent_hybrid as lh

    runs, by, _ = lh.segment_times(ctx)
    if runs is None:
        return None
    if not by["latent"]:
        raise ValueError(f"{len(runs)} {lh.MODULE} runs matched a "
                         f"{lh.SEGMENT} span but hold no "
                         f"{lh.LATENT_KERNEL} kernel")
    least_s = lh.latent_decode_least_s(
        ctx, sum(a["latent_rows_attended"] for _, _, a in runs))
    return 100.0 * least_s / (by["latent"] / 1e9)
