"""prefill_pad_share — layer "Engine".

Share of the prefill programs' positions that are padding: 1 - (sum of
``plen`` - ``cached``) / (sum of ``bucket``) over the ``engine.prefill``
events of the window (``paddle_tpu.tracing``): ``plen`` the prompt's
tokens, ``cached`` those the program did not have to compute, ``bucket``
the width of the compiled program that ran. From ``ctx["spans"]`` alone.
Moves ``serve_tpot_p50_ms`` (every admission stalls the requests in
flight for the whole bucket).
"""
PREFILL = "engine.prefill"


def read(ctx):
    useful = width = 0
    for ev in ctx["spans"]:
        # before PR 25 a prefix-cache hit reported the bucket "warm"
        if ev["phase"] == PREFILL and isinstance(ev.get("bucket"), int):
            useful += ev["plen"] - ev.get("cached", 0)
            width += ev["bucket"]
    if not width:
        return None
    return 100.0 * (1.0 - useful / width)
