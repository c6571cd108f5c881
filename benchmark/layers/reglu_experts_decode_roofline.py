"""reglu_experts_decode_roofline — layer "Pallas kernels".

``moe_experts_decode_roofline``'s arithmetic for a configuration that names
its experts with the SmallThinker family's keys: the least time of a
segment's expert products in DECODE is

    experts_hit x 3 x hidden_size x moe_ffn_hidden_size x dtype bytes / HBM bandwidth

(``experts_hit`` from the ``engine.segment`` spans, the experts chosen by
at least one live row, summed over the segment's steps and its expert
layers, here every layer), over the self time of the grouped-matmul
kernel ``gmm`` inside the ``jit_segment`` runs those spans dispatched.
Weights only: it errs low. Moves ``serve_tpot_p50_ms``.
"""


def read(ctx):
    import os

    from benchmark.run import HERE, load_module

    cfg = ctx["config"]
    return load_module(os.path.join(
        HERE, "layers", "moe_experts_decode_roofline.py")).read(dict(
        ctx, config=dict(cfg, moe_intermediate_size=cfg[
            "moe_ffn_hidden_size"])))
