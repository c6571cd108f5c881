"""kernel_time_share.serve — layer "Pallas kernels".

Device time of the operations that are Pallas kernels (a ``custom-call``
whose target is ``tpu_custom_call``: here paged_decode, rms_norm, fused_rope and the flash forward
of prefill) over the device's busy time. Moves ``serve_tpot_p50_ms``.
"""


def read(ctx):
    from benchmark.lib import trace_reduce as tr

    return 100.0 * tr.pallas_share(ctx["raw"])
