"""state_bytes_share — layer "Engine".

What part of the cache in use is recurrent: over the ``engine.segment``
events of the window that carry ``state_rows`` (``paddle_tpu.tracing``),
with ``rows`` the live rows and ``ctx_tokens`` the tokens of their contexts
at the segment's start,

    state = rows x linear layers x (heads x dk x dv x 4 B + (K-1) x conv_dim x dtype bytes)
    kv    = ctx_tokens x full layers x 2 x KV heads x head size x dtype bytes
    share = state / (state + kv)

A row's state is the same size at any context; its KV grows with it. From
``ctx["spans"]`` alone. Moves ``serve_tpot_p50_ms`` (the state's read and
write is a decode step's bytes beside the weights and the KV).
"""
SEGMENT = "engine.segment"


def read(ctx):
    from benchmark.lib import gated_delta as gd

    geo = gd.geometry(ctx)
    row = geo["linear_layers"] * gd.row_state_bytes(ctx["config"], geo)
    token = gd.full_kv_bytes_per_token(ctx["config"], geo)
    state = kv = 0
    for ev in ctx["spans"]:
        if ev["phase"] == SEGMENT and "state_rows" in ev:
            state += ev["rows"] * row
            kv += ev["ctx_tokens"] * token
    if not state:
        return None
    return 100.0 * state / (state + kv)
