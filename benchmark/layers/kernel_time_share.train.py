"""kernel_time_share.train — layer "Pallas kernels".

Device time of the operations that are Pallas kernels (a ``custom-call``
whose target is ``tpu_custom_call``: here flash forward (two sites), both flash backward kernels and
fused_rope) over the device's busy time. Moves ``train_tokens_per_s``.
"""


def read(ctx):
    from benchmark.lib import trace_reduce as tr

    return 100.0 * tr.pallas_share(ctx["raw"])
