"""decode_device_ms_per_step — layer "Model forwards".

Device time of one run of the decode-segment program over the steps one
run scans. Moves ``serve_tpot_p50_ms``.
"""
MODULES = ("jit_segment",)


def read(ctx):
    from benchmark.lib import trace_reduce as tr

    return (tr.mean_run_ns(ctx["raw"], MODULES)
            / ctx["run"]["segment_steps"] / 1e6)
