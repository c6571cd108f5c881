"""gdn_time_share.serve — layer "Pallas kernels".

How much of the chip's time the linear-attention mechanism is: self time
of the convolution, the chunked scan (``gdn_chunk_prefill``), the
one-token update (``gdn_decode_step``) and the elementwise work around
them (L2 norms, gates, the gated norm, the kernels' layouts), prefill and
decode alike, over the device's busy time (``lib/gated_delta.py`` names
what is recognised how). The projections are weight products and are not
in it. Moves ``serve_tpot_p50_ms``.
"""


def read(ctx):
    from benchmark.lib import gated_delta as gd
    from benchmark.lib import trace_reduce as tr

    by = gd.times(ctx)
    if not by["scan"] and not by["update"]:
        return None            # a program without the mechanism
    return 100.0 * sum(by.values()) / tr.busy_ns(ctx["raw"])
