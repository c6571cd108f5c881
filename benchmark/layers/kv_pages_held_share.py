"""kv_pages_held_share — layer "Engine".

What the two cache geometries hold of what ONE page table for every layer
would: a full-attention layer keeps a page for every position of a row, a
sliding-window layer only the ring that holds its window. Over the
``engine.segment`` events of the window (``paddle_tpu.tracing``), with
``pages_full`` / ``pages_window`` the pages that the live rows hold in a
layer of each kind at the segment's start:

    (pages_full x full layers + pages_window x window layers)
    / (pages_full x all layers)

100 % = no row has left the window. From ``ctx["spans"]`` alone. Moves
``serve_tpot_p50_ms`` (the pages not held are rows that fit beside the
weights: batch, and with it what a step's streamed weights are shared
over).
"""
SEGMENT = "engine.segment"
WINDOW_LAYER = "sliding_attention"


def layer_counts(config: dict) -> tuple:
    """(full layers, window layers) of the configuration as it is run."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    window = sum(k == WINDOW_LAYER for k in kinds)
    return len(kinds) - window, window


def read(ctx):
    n_full, n_window = layer_counts(ctx["config"])
    held = one_table = 0
    for ev in ctx["spans"]:
        if ev["phase"] == SEGMENT and "pages_window" in ev:
            held += ev["pages_full"] * n_full + ev["pages_window"] * n_window
            one_table += ev["pages_full"] * (n_full + n_window)
    if not one_table:
        return None
    return 100.0 * held / one_table
