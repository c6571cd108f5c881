"""expert_rows_held_share — layer "Model forwards".

Of the (row, choice) pairs the router made in decode, the share that landed
on the experts THIS chip holds. ``expert_rows_here`` (an attribute of
``engine.segment``, out of the segment's own program) counts the pairs
whose expert is held here, summed over the segment's steps and expert
layers; every live row makes ``num_experts_per_tok`` choices a step and
expert layer. With ``ep_size`` chips sharing a layer evenly the share is
``100 / ep_size`` (6.25 for 16): the share's sanity, and how thin the
held experts' rows are (16 rows x 8 choices / 256 experts = 0.5 rows an
expert a step). Rows that finish inside a segment stop choosing but stay in
the denominator, so the share errs low. From ``ctx["spans"]`` alone. Moves
``serve_tpot_p50_ms``.
"""
SEGMENT = "engine.segment"


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def read(ctx):
    cfg = ctx["config"]
    here = made = 0
    for ev in ctx["spans"]:
        if ev["phase"] == SEGMENT and "expert_rows_here" in ev:
            here += ev["expert_rows_here"]
            made += (ev["rows"] * ev["steps"] * expert_layers(cfg)
                     * cfg["num_experts_per_tok"])
    if not made:
        return None
    return 100.0 * here / made
