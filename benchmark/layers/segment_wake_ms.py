"""segment_wake_ms — layer "Engine".

Median over the traced segments of (end of the ``engine.wait`` span -
end of its ``jit_segment`` run on the device): the readback's transfer,
the host thread's wake-up and the interpreter lock regained, after the
device has finished (``lib/segment_cycle.py``). None for a program
without the span. Moves ``serve_tpot_p50_ms``.
"""


def read(ctx):
    from benchmark.lib import segment_cycle as sc

    v = sc.view(ctx)
    if v is None:
        return None
    return sc.median_ms(v["wake_ns"])
