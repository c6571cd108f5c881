"""idle_unattributed_share.serve — layer "Device".

Share of the traced window in which the chip is idle and NO ``pt:*``
span is open on the scheduler's thread: what the program's spans still
do not cover. A loop with nothing active and nothing queued has no
``step`` span and counts here, as do the spans cut by the profiler
session's two ends (a span is written only if the session saw it begin
and end). Read from the profiler's host plane (``lib/host_spans.py``).
Moves ``serve_tpot_p50_ms``.
"""


def read(ctx):
    from benchmark.lib import host_spans as hs

    return hs.idle_share(ctx, lambda sid, tree: sid == 0)
