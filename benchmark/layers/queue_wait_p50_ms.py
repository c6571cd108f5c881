"""queue_wait_p50_ms — layer "HTTP front / queue".

Median over requests of the time between the scheduler's spans
``queue.enqueue`` and ``queue.dequeue`` of one request id
(``paddle_tpu.tracing``, host clock). Moves ``serve_ttft_p90_ms``.
"""
ENQUEUE, DEQUEUE = "queue.enqueue", "queue.dequeue"


def read(ctx):
    from benchmark.lib.stats import percentile

    put = {}
    waits = []
    for ev in ctx["spans"]:
        if ev["phase"] == ENQUEUE:
            put[ev["rid"]] = ev["ts_ns"]
        elif ev["phase"] == DEQUEUE and ev["rid"] in put:
            waits.append((ev["ts_ns"] - put.pop(ev["rid"])) / 1e6)
    return percentile(waits, 50) if waits else None
