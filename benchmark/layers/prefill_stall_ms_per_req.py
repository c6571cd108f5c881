"""prefill_stall_ms_per_req — layer "Engine".

Device time of one run of the prefill programs, read in a cell whose
prompts are long: there is no chunked prefill by default, so for this
long every request in flight waits each time one is admitted. Moves
``serve_tpot_p50_ms``. (The same quantity as ``prefill_device_ms_per_req``,
which moves ``serve_ttft_p90_ms`` in the cells that report it.)
"""
MODULES = ("jit_prefill_one", "jit_prefill_chunk_fn")


def read(ctx):
    from benchmark.lib import trace_reduce as tr

    return tr.mean_run_ns(ctx["raw"], MODULES) / 1e6
