"""train_mfu — layer "Model forwards".

FLOPs one step needs by its shapes (``benchmark/lib/shapes.py``: 6 per
matmul parameter per token with the head and without the embedding
lookup, plus causal attention; recomputation not counted) over (the
step's device time x the chip's bf16 peak). Moves ``train_tokens_per_s``.
"""
MODULES = ("jit_bench_train_step",)


def read(ctx):
    from benchmark.lib import trace_reduce as tr

    step_s = tr.mean_run_ns(ctx["raw"], MODULES) / 1e9
    return 100.0 * ctx["run"]["train_flops_per_step"] / (
        step_s * ctx["peaks"]["flops_bf16"])
