"""flash_train_roofline — layer "Pallas kernels".

The causal flash kernels of the train step against their roofline. They
are compute-bound (arithmetic intensity of a [2048, 128] head is far past
197e12 / 819e9 = 240 FLOP/byte), so the least time is FLOPs over the bf16
peak: the forward at its two sites and one backward, counted from shapes
at the least the method allows (``shapes.flash_train_flops_per_step``),
over (the kernels' device time per step x peak). Moves
``train_tokens_per_s``.
"""
# the train step holds two kinds of Pallas kernel; the flash kernels have
# no name of their own in the trace, so they are the ones that are not:
NOT_FLASH = ("fused_rope",)
MODULES = ("jit_bench_train_step",)


def read(ctx):
    from benchmark.lib import trace_reduce as tr

    steps = len(tr.module_runs(ctx["raw"], MODULES))
    kernel_s = tr.op_self_ns(
        ctx["raw"], lambda ev: tr.is_pallas(ev)
        and tr.op_name(ev) not in NOT_FLASH) / 1e9
    if not steps or not kernel_s:
        raise ValueError(f"no {MODULES} run or no flash kernel operation")
    least_s = ctx["run"]["flash_flops_per_step"] / ctx["peaks"]["flops_bf16"]
    return 100.0 * least_s / (kernel_s / steps)
