"""mla_sparse_decode_roofline — layer "Pallas kernels".

The decode attention over the chosen cache rows against its bandwidth
roofline. A step of a layer has to read the ``(c | k_rope)`` row of every
position a live row chose: at the least

    layers x ctx_tokens_selected x (kv_lora_rank + qk_rope_head_dim) x dtype

bytes a segment (``ctx_tokens_selected``: an attribute of
``engine.segment``, counted inside the segment's own program and summed over
its steps: each step, the sum over live rows of min(context,
``index_topk``)). Time: self time, inside the matched ``jit_segment`` runs,
of the operations whose result holds the chosen positions as a dimension:
the flat indices, XLA's ``gather`` of the chosen rows, the scores over them,
the softmax and the probabilities (``lib/sparse_attention.py`` names the
shapes; no kernel: PR 32 measured the gather, 852 us, against a kernel
that walks the list, 1,722 us: ``PERF.md`` section 6). The rows are stored 640 wide and the
gather reads all of it and all ``max_batch`` rows, live or not, so the
share sits under 100 by construction. Moves ``serve_tpot_p50_ms``.
"""


def read(ctx):
    from benchmark.lib import sparse_attention as sa

    runs, by, _ = sa.segment_times(ctx)
    if runs is None:
        return None
    if not by["attention"]:
        raise ValueError(f"{len(runs)} {sa.MODULE} runs matched a {sa.SPAN} "
                         f"span but hold no operation over "
                         f"{sa.geometry(ctx)['chosen']} chosen positions")
    least_s = sum(sa.chosen_row_bytes(ctx["config"], a)
                  for _, _, a in runs) / sa.hbm_bytes_per_s(ctx)
    return 100.0 * least_s / (by["attention"] / 1e9)
