"""expert_load_max_over_mean — layer "Model forwards".

How unevenly the router loads the experts in decode: the rows the busiest
expert served, over the rows an expert would serve if all were chosen
alike. ``expert_rows_max`` (an attribute of ``engine.segment``, out of the
segment's own program) is the largest number of rows any one expert
served in a step of an expert layer, summed over the segment's steps and
expert layers; the even share is ``rows x experts per token / experts``
for each of those steps and layers. 1 = even; ``experts / experts per
token`` = every row chose the same ones. A busier expert is a longer
group of rows in the grouped product and, across chips, the straggler.
From ``ctx["spans"]`` alone. Moves ``serve_tpot_p50_ms``.
"""
SEGMENT = "engine.segment"


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["num_dense_layers"]


def read(ctx):
    cfg = ctx["config"]
    share = cfg["num_experts_per_tok"] / cfg["num_experts"]
    busiest = even = 0.0
    for ev in ctx["spans"]:
        if ev["phase"] == SEGMENT and "expert_rows_max" in ev:
            busiest += ev["expert_rows_max"]
            even += ev["steps"] * expert_layers(cfg) * ev["rows"] * share
    if not even:
        return None
    return busiest / even
