"""prefill_device_ms_per_req — layer "Engine".

Device time of the prefill programs in the profiler trace over the number
of their runs (one run admits one request). Moves ``serve_ttft_p90_ms``.
"""
MODULES = ("jit_prefill_one", "jit_prefill_chunk_fn")


def read(ctx):
    from benchmark.lib import trace_reduce as tr

    return tr.mean_run_ns(ctx["raw"], MODULES) / 1e6
