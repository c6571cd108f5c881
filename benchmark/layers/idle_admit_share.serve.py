"""idle_admit_share.serve — layer "Engine".

Share of the traced window in which the chip is idle while the
scheduler's thread is inside an admission: the ``admit`` span, or the
``admit.begin`` and ``prefill_chunk`` spans of a chunked one, any child
included (``engine.mini_cache``,
``engine.prefill``, ``engine.reserve``, ``engine.install``,
``engine.first_token``). The spans are the program's
(``paddle_tpu.tracing``), read from the profiler's host plane, so they
are on the device's clock (``lib/host_spans.py``). Moves
``serve_tpot_p50_ms``.
"""
ADMISSION = ("admit", "admit.begin", "prefill_chunk")


def read(ctx):
    from benchmark.lib import host_spans as hs

    return hs.idle_share(
        ctx, lambda sid, tree: hs.under(sid, tree, ADMISSION))
