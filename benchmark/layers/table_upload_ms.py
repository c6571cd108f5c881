"""table_upload_ms — layer "Engine".

Mean duration of the ``engine.tables`` spans (``paddle_tpu.tracing``,
host clock, the whole window): the upload of the page table(s) a decode
segment begins with, two tables with a model of window layers. From
``ctx["spans"]`` alone. None for a program without the span. Moves
``serve_tpot_p50_ms``.
"""
TABLES = "engine.tables"


def read(ctx):
    from benchmark.lib import segment_cycle as sc

    evs = sc.ring_events(ctx, TABLES)
    if evs is None:
        return None
    return sum(ev["dur_ns"] for ev in evs) / len(evs) / 1e6
