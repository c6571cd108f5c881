"""segment_cycle_host_ms — layer "Engine".

How long the chip waits for the host between two decode segments: the
median over cycles of (start of ``jit_segment`` run n+1 on the device -
end of run n), a cycle being two consecutive ``engine.segment`` spans
of the scheduler's thread with no admission between them
(``lib/segment_cycle.py``, whose ``segment_cycle`` line splits the wait
into its pieces). The number PERF.md read off a trace by hand before
PR 36. None for a program without the segment's child spans. Moves
``serve_tpot_p50_ms``.
"""


def read(ctx):
    from benchmark.lib import segment_cycle as sc

    v = sc.view(ctx)
    if v is None:
        return None
    return sc.median_ms([c["cycle"] for c in v["cycles"]])
