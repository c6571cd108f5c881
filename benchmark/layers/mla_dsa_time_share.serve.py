"""mla_dsa_time_share.serve — layer "Pallas kernels".

How much of a decode step the learned sparse attention is: self time of the
indexer's kernel (``dsa_index_scores``), the selection (the masking of the
scores and ``lax.top_k``, a ``sort`` on the chip) and the attention over the
chosen rows (gather, scores, softmax), over the self time of every
operation, inside the ``jit_segment`` runs matched to a traced
``engine.segment`` span (``lib/sparse_attention.py`` names what is
recognised how). The rest of a step is weights: projections, the dense FFN,
the shared and the held experts, the head. Prefill is not in it. Moves
``serve_tpot_p50_ms``.
"""


def read(ctx):
    from benchmark.lib import sparse_attention as sa

    runs, by, whole = sa.segment_times(ctx)
    if runs is None or not whole:
        return None
    if not by["index"]:
        raise ValueError(f"{len(runs)} {sa.MODULE} runs matched a {sa.SPAN} "
                         f"span but hold no {sa.INDEX_KERNEL} kernel")
    return 100.0 * sum(by.values()) / whole
