"""device_idle_share.serve — layer "Device".

1 - (union of the device-operation intervals) / (traced window: first
operation's start to last operation's end), from the profiler trace.
Moves ``serve_tpot_p50_ms``.
"""


def read(ctx):
    from benchmark.lib import trace_reduce as tr

    return 100.0 * tr.idle_share(ctx["raw"])
