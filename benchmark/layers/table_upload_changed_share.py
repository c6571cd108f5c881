"""table_upload_changed_share — layer "Engine".

The share of the segments' page-table uploads that had to happen: 100 x
the sum of ``changed`` over the count of ``engine.tables`` spans
(``paddle_tpu.tracing``, the whole window). ``changed`` is 1 when a
table differs from what the last upload sent, a segment's or an
admission's: the device does not hold it yet (the host's own
comparison). What is left of 100 is what an upload skipped when nothing
changed would save (ROADMAP S12), at ``table_upload_ms`` each. The
traffic sets the share, not the program: it sizes a lead. From
``ctx["spans"]`` alone. None for a program without the span. Moves
``serve_tpot_p50_ms``.
"""
TABLES = "engine.tables"


def read(ctx):
    from benchmark.lib import segment_cycle as sc

    evs = sc.ring_events(ctx, TABLES)
    if evs is None:
        return None
    return 100.0 * sum(ev["changed"] for ev in evs) / len(evs)
