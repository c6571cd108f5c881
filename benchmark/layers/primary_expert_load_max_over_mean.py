"""primary_expert_load_max_over_mean — layer "Model forwards".

``expert_load_max_over_mean`` for a configuration that names its experts
with the SmallThinker family's keys: ``expert_rows_max`` (the rows of the
busiest expert in a step of an expert layer, summed over the segment's
steps and expert layers inside ``jit_segment``) over the even share,

    rows x steps x expert layers x moe_num_active_primary_experts / moe_num_primary_experts

every layer an expert layer (12 x 6 / 64 a row a step in the cut). 1 =
even. From ``ctx["spans"]`` alone. Moves ``serve_tpot_p50_ms``.
"""


def read(ctx):
    import os

    from benchmark.run import HERE, load_module

    cfg = ctx["config"]
    return load_module(os.path.join(
        HERE, "layers", "expert_load_max_over_mean.py")).read(dict(
        ctx, config=dict(
            cfg, num_dense_layers=0,
            num_experts=cfg["moe_num_primary_experts"],
            num_experts_per_tok=cfg["moe_num_active_primary_experts"])))
