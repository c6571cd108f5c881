"""sched_gap_ms — layer "Scheduler".

Mean duration of the scheduler's ``gap`` spans (``paddle_tpu.tracing``,
host clock): what the host does between two decode segments while there
is work (admit, stream, retire). Moves ``serve_tpot_p50_ms``.
"""
GAP = "gap"


def read(ctx):
    gaps = [ev["dur_ns"] / 1e6 for ev in ctx["spans"] if ev["phase"] == GAP]
    return sum(gaps) / len(gaps) if gaps else None
