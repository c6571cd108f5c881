"""moe_experts_time_share.serve — layer "Pallas kernels".

Device time of the routed experts' three products over the device's busy
time, prefill and decode alike. The products are found BY THE KERNEL'S
NAME: they run as the megablox grouped-matmul Pallas kernel
(``paddle_tpu.ops.pallas.grouped_matmul``), which shows in the trace as
the custom call ``gmm`` (``gmm bf16[256,1024]`` and ``gmm f32[256,2048]``
in decode); no other kernel of the program has that name
(``paged_decode``, the flash forward and the norms have theirs). The sort of the rows by
expert, the router and the shared expert are not in it. Raises
``ValueError`` when a trace with device operations holds none of them.
Moves ``serve_tpot_p50_ms``.
"""
KERNEL = "gmm"


def is_expert_product(event) -> bool:
    from benchmark.lib import trace_reduce as tr

    return tr.is_pallas(event) and tr.op_name(event) == KERNEL


def read(ctx):
    from benchmark.lib import trace_reduce as tr

    busy = tr.busy_ns(ctx["raw"])
    if not busy:
        return None
    experts = tr.op_self_ns(ctx["raw"], keep=is_expert_product)
    if not experts:
        raise ValueError(f"no Pallas operation named {KERNEL!r} in the "
                         f"trace: the expert products were not found")
    return 100.0 * experts / busy
