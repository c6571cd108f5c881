"""train_step_device_ms — layer "Model forwards".

Device time of one run of the train-step program (jitted by run.py under
the fixed name ``bench_train_step``). Moves ``train_tokens_per_s``.
"""
MODULES = ("jit_bench_train_step",)


def read(ctx):
    from benchmark.lib import trace_reduce as tr

    return tr.mean_run_ns(ctx["raw"], MODULES) / 1e6
