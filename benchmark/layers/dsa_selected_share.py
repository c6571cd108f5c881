"""dsa_selected_share — layer "Model forwards".

How sparse the learned sparse attention is under this traffic: the cache
rows a decode step's attention reads over the rows the context holds.
``ctx_tokens_selected`` (an attribute of ``engine.segment``, counted inside
the segment's own program and SUMMED over its steps: each step, the sum
over live rows of min(context, ``index_topk``)) over the contexts of the
same steps: ``ctx_tokens`` (the live rows' contexts at the segment's
start, from the host's bookkeeping) grown by one token a row a step.
100 = every position attended (no context past ``index_topk``). Rows that
finish inside a segment are counted to its end in the denominator only, so
the share errs low by under a step in eight. From ``ctx["spans"]`` alone.
Moves ``serve_tpot_p50_ms``.
"""
SEGMENT = "engine.segment"


def contexts_over_steps(ev: dict) -> int:
    """Sum over the segment's steps of the live rows' contexts."""
    steps, rows = ev["steps"], ev["rows"]
    return steps * ev["ctx_tokens"] + rows * steps * (steps - 1) // 2


def read(ctx):
    selected = whole = 0
    for ev in ctx["spans"]:
        if ev["phase"] == SEGMENT and "ctx_tokens_selected" in ev:
            selected += ev["ctx_tokens_selected"]
            whole += contexts_over_steps(ev)
    if not whole:
        return None
    return 100.0 * selected / whole
