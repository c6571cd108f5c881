"""hybrid_mixers_time_share.serve — layer "Pallas kernels".

How much of the decode segments' device time the hybrid's two new mixers
take: self time of the dense latent decode (``paged_latent_decode``), the
linear layers' state update (``gdn_decode_step``, state-shaped operations
and the layout copies of q and k for the kernel) and their convolution, over the self time of every operation
inside the ``jit_segment`` runs matched to a traced ``engine.segment``
span (``lib/latent_hybrid.py`` names what is recognised how). The
projections are weight products and are not in it. Whether the new
mechanisms do the work, beside the experts, the projections and the head.
Moves ``serve_tpot_p50_ms``.
"""


def read(ctx):
    from benchmark.lib import latent_hybrid as lh

    runs, by, whole = lh.segment_times(ctx)
    if runs is None or not whole:
        return None
    return 100.0 * (by["latent"] + by["update"] + by["conv"]) / whole
