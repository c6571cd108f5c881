"""segment_launch_ms — layer "Engine".

Median over the traced segments of (start of the ``jit_segment`` run on
the device - start of its ``engine.dispatch`` span): the arguments
built, the jit call path over the program's ``args`` array leaves, the
runtime's launch (``lib/segment_cycle.py``). None for a program without
the span. Moves ``serve_tpot_p50_ms``.
"""


def read(ctx):
    from benchmark.lib import segment_cycle as sc

    v = sc.view(ctx)
    if v is None:
        return None
    return sc.median_ms(v["launch_ns"])
