"""paged_decode_gqa7_roofline — layer "Pallas kernels".

``paged_decode_window_roofline``'s count for a configuration that lays
its window and full layers out as ``sliding_window_layout`` (1 = a window
layer of ``sliding_window_size`` keys, 0 = a full layer): a segment reads
at the least

    full layers   x (steps x ctx_tokens + rows x steps x (steps - 1) / 2)
  + window layers x  steps x ctx_tokens_window

tokens, each ``2 (K, V) x num_key_value_heads x head_dim x dtype bytes``
(4 KV heads x 128 for 28 query heads: 7 a group), over the self time of
the ``paged_decode*`` kernels inside the ``jit_segment`` runs matched to
the ``engine.segment`` spans that carry the counters, x 819 GB/s. KV
only, tokens and not pages: it errs low. Moves ``serve_tpot_p50_ms``.
"""


def read(ctx):
    import os

    from benchmark.run import HERE, load_module

    base = load_module(os.path.join(
        HERE, "layers", "paged_decode_window_roofline.py"))
    cfg = ctx["config"]
    kinds = ["full_attention" if s == 0 else base.WINDOW_LAYER
             for s in cfg["sliding_window_layout"]]
    return base.read(dict(ctx, config=dict(cfg, layer_types=kinds)))
