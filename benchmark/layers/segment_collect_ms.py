"""segment_collect_ms — layer "Engine".

Mean duration of the ``engine.collect`` spans (``paddle_tpu.tracing``,
host clock, the whole window): the host loop that hands a segment's
tokens to their requests and retires the finished, and the monitor's
gauges after it. From ``ctx["spans"]`` alone: it also reads on the CPU
under ``--tiny``. None for a program without the span. Moves
``serve_tpot_p50_ms``.
"""
COLLECT = "engine.collect"


def read(ctx):
    from benchmark.lib import segment_cycle as sc

    evs = sc.ring_events(ctx, COLLECT)
    if evs is None:
        return None
    return sum(ev["dur_ns"] for ev in evs) / len(evs) / 1e6
