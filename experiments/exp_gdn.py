"""The two gated-delta-rule kernels alone on the chip at the hybrid cell's
shapes (30 heads x 96 x 192), each against the XLA composition that
computes the same thing. Prints one JSON line a case.

- ``gdn_chunk_prefill`` (the chunked scan of one linear layer's admission):
  buckets of the cell, ``last_idx`` at the bucket's end and at a mean
  prompt's; against ``recurrence`` (a ``lax.scan`` of the rule, one position
  a step) at the smallest bucket only (it is slow). us a call, us a live
  chunk and head, the share of the least time (``benchmark/lib/
  gated_delta.py``: the recurrence's own FLOPs or the bf16 bytes of q, k,
  v, o) and the worst error of outputs and state against the recurrence.
- ``gdn_decode_step`` (the one-token update of ``rows`` states in place)
  against ``decode_step_xla``, the states carried from call to call, at 48
  rows with 48, 40, 24 and 8 live: us a call and the share of the floor
  (one read and one write of each live row's state at 819 GB/s).

    python experiments/exp_gdn.py [--sizes 256,1024,4096] [--rows 48]

``--rehearse`` runs the same control flow at a tiny size on any device; its
times mean nothing.
"""
import argparse
import json
import sys
import time

H, DK, DV = 30, 96, 192
HBM = 819e9
PEAK = 197e12


def timed(fn, args, n=20, carry=None):
    """Seconds a call ON THE DEVICE: ``n`` calls inside one jitted loop
    (a dispatch from the host costs about a millisecond here, more than
    either kernel), each fed a last argument that depends on the call
    before it so that none is hoisted or merged; ``carry``: the index of
    the argument that takes the call's own second result back (a donated
    state). Returns (seconds a call, one call's result)."""
    import jax
    import jax.numpy as jnp

    def loop(*a):
        def body(_, c):
            a_, nudge = c
            a_ = list(a_)
            a_[-2] = a_[-2] + nudge.astype(a_[-2].dtype)
            out = fn(*a_)
            if carry is not None:
                a_[carry] = out[1]
            a_[-2] = a[-2]
            return tuple(a_), 1e-30 * out[0].reshape(-1)[0].astype(
                jnp.float32)
        return jax.lax.fori_loop(0, n, body, (tuple(a), jnp.float32(0)))[1]

    once = jax.jit(fn, donate_argnums=() if carry is None else (carry,))
    many = jax.jit(loop)
    jax.block_until_ready(many(*args))
    ts = []
    for _ in range(3):
        t = time.perf_counter()
        jax.block_until_ready(many(*args))
        ts.append(time.perf_counter() - t)
    t0 = []
    empty = jax.jit(lambda x: x + 1)
    jax.block_until_ready(empty(jnp.float32(0)))
    for _ in range(3):
        t = time.perf_counter()
        jax.block_until_ready(empty(jnp.float32(0)))
        t0.append(time.perf_counter() - t)
    return (min(ts) - min(t0)) / n, once(*args)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sizes", default="256,1024,4096")
    ap.add_argument("--rows", type=int, default=48)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    sys.path.insert(0, ".")
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import gated_delta_rule as G

    h, dk, dv = (3, 8, 16) if args.rehearse else (H, DK, DV)
    sizes = [128] if args.rehearse else [int(s) for s in
                                         args.sizes.split(",")]
    rows = 4 if args.rehearse else args.rows
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}))

    def inputs(seed, lead):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        q = G.l2norm(jax.random.normal(ks[0], lead + (h, dk))) * dk ** -0.5
        k = G.l2norm(jax.random.normal(ks[1], lead + (h, dk)))
        v = jax.random.normal(ks[2], lead + (h, dv)).astype(jnp.bfloat16)
        g = -jnp.exp(jax.random.normal(ks[3], lead + (h,))) * 0.1
        beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], lead + (h,)))
        return q, k, v, g, beta

    least_pos = max(6 * dk * dv * h / PEAK, h * 2 * (dk + dv) * 2 / HBM)
    scan = G.gdn_chunk_prefill

    def plain(q, k, v, g, b, _):
        return G.recurrence(q, k, v.astype(jnp.float32), g, b)
    for i, s in enumerate(sizes):
        q, k, v, g, beta = inputs(s, (1, s))
        for last in sorted({s - 1, (7 * s) // 8 - 1, s // 2}):
            sec, (o, st) = timed(scan, (q, k, v, g, beta, jnp.int32(last)))
            line = {"kernel": "gdn_chunk_prefill", "positions": s,
                    "last_idx": last, "us_call": sec * 1e6,
                    "us_chunk_head": sec * 1e6 / ((last // G.CHUNK + 1) * h),
                    "roofline_pct": 100 * (last + 1) * least_pos / sec}
            if i == 0:
                t, (o_ref, st_ref) = timed(
                    plain, tuple(a[:, :last + 1] for a in (q, k, v, g, beta))
                    + (jnp.int32(0),), n=2)
                line.update(
                    recurrence_us=t * 1e6,
                    err_out=float(jnp.abs(o[:, :last + 1].astype(jnp.float32)
                                          - o_ref).max()),
                    err_state=float(jnp.abs(st - st_ref).max()),
                    out_max=float(jnp.abs(o_ref).max()))
            print(json.dumps(line), flush=True)

    step, step_xla = G.gdn_decode_step, G.decode_step_xla
    q, k, v, g, beta = inputs(7, (rows,))
    v = v.astype(jnp.float32)
    for n_live in sorted({rows, rows * 5 // 6, rows // 2, rows // 6}):
        # live rows spread over the slots, as retirement leaves them
        idx = jnp.round(jnp.linspace(0, rows - 1, n_live)).astype(jnp.int32)
        live = jnp.zeros((rows,), bool).at[idx].set(True)
        floor = n_live * 2 * h * dk * dv * 4 / HBM
        res = {}
        for name, fn in (("gdn_decode_step", step),
                         ("decode_step_xla", step_xla)):
            st = jax.random.normal(jax.random.PRNGKey(3), (rows, h, dk, dv))
            want = G.decode_step_xla(st, q, k, v, g, beta, live)
            sec, once = timed(fn, (st, q, k, v, g, beta, live), n=50,
                              carry=0)
            res[name] = {
                "us_call": sec * 1e6, "roofline_pct": 100 * floor / sec,
                "err_out": float(jnp.abs(jnp.where(
                    live[:, None, None], once[0] - want[0], 0)).max()),
                "err_state": float(jnp.abs(once[1] - want[1]).max())}
        print(json.dumps({"rows": rows, "live": n_live,
                          "floor_us": floor * 1e6, **res}), flush=True)


if __name__ == "__main__":
    main()
