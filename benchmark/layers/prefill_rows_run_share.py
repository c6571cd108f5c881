"""prefill_rows_run_share — layer "Model forwards".

Share of the prefill programs' rows that the model's row-wise work (the
projections, the norms, the FFN: whatever is a function of one position
alone) ran: sum of ``rows_run`` over sum of ``bucket`` of the window's
``engine.prefill`` events (``paddle_tpu.tracing``). ``bucket`` is the width
of the compiled program, ``rows_run`` what the engine counts of it on the
host: the bucket's rows, or, with a model whose prefill runs that work in
row blocks up to the prompt's last position (``paged_layout()``'s
``prefill_row_block``), the prompt's length rounded up to whole blocks.
100 = every admission ran its bucket whole; ``prefill_pad_share`` is the
same admissions' padding, counted against the bucket whatever was run.
None where no event carries the counter (a program from before PR 35).
From ``ctx["spans"]`` alone. Moves ``serve_tpot_p50_ms`` (an admission
stalls the rows in flight for the rows it runs).
"""
PREFILL = "engine.prefill"


def read(ctx):
    ran = width = 0
    for ev in ctx["spans"]:
        if (ev["phase"] == PREFILL and isinstance(ev.get("bucket"), int)
                and isinstance(ev.get("rows_run"), int)):
            ran += ev["rows_run"]
            width += ev["bucket"]
    if not width:
        return None
    return 100.0 * ran / width
