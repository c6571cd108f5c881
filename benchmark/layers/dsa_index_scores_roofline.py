"""dsa_index_scores_roofline — layer "Pallas kernels".

The indexer's decode kernel against its bandwidth roofline. A decode step
of a layer scores each live row's WHOLE context (the selection has to see
every position), so a segment of ``steps`` steps over ``rows`` live rows
that hold ``ctx_tokens`` tokens at its start reads at the least

    layers x (steps x ctx_tokens + rows x steps x (steps - 1) / 2)

indexer keys of ``index_head_dim`` x dtype bytes (256 B). Time: self time of
the Pallas kernel ``dsa_index_scores`` inside the ``jit_segment`` runs
matched to the span (``lib/sparse_attention.py``). Keys only, tokens and not
whole pages, no query or page table: a perfect kernel reads under 100. The
kernel pays a copy for each 16-token page of keys (4 KB), so it sits far
under the roofline. Moves ``serve_tpot_p50_ms``.
"""


def read(ctx):
    from benchmark.lib import sparse_attention as sa

    runs, by, _ = sa.segment_times(ctx)
    if runs is None:
        return None
    if not by["index"]:
        raise ValueError(f"{len(runs)} {sa.MODULE} runs matched a {sa.SPAN} "
                         f"span but hold no {sa.INDEX_KERNEL} kernel")
    least_s = sum(sa.index_key_bytes(ctx["config"], a)
                  for _, _, a in runs) / sa.hbm_bytes_per_s(ctx)
    return 100.0 * least_s / (by["index"] / 1e9)
