"""admit_host_ms_per_req — layer "Engine".

Mean over requests of the host time of their admission: the scheduler's
``admit`` span, or the ``admit.begin`` and ``prefill_chunk`` spans of a
chunked one, summed by request id (``paddle_tpu.tracing``, host clock, the whole window). The span ends
with a read of the first token, so it holds the device's prefill time
too. From ``ctx["spans"]`` alone: it also reads on the CPU under
``--tiny``. Moves ``serve_tpot_p50_ms``.
"""
PHASES = ("admit", "admit.begin", "prefill_chunk")


def read(ctx):
    per_req = {}
    for ev in ctx["spans"]:
        if ev["phase"] in PHASES:
            per_req[ev["rid"]] = per_req.get(ev["rid"], 0) + ev["dur_ns"]
    if not per_req:
        return None
    return sum(per_req.values()) / len(per_req) / 1e6
