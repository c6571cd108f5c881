"""``paged_decode`` on the chip at the serving cells' tables and live
contexts: us a call and us a live page. Prints one JSON line a variant.

Geometries (table rows x pages, heads, window) are the serving cells'
(PERF.md section 4); the live rows and their lengths are drawn once from a
fixed seed at about the share of the table the cells' traced runs hold
live. Variants:

- ``arith``: ``mxu`` is the kernel as it is; ``vpu`` puts the
  repeat-multiply-reduce arithmetic of the old grid kernel, a page at a
  time on the vector unit, in place of ``_block_update`` (what the loop
  over live pages gives before the products move to the matrix unit);
  ``copies`` puts a pass-through in its place, so that what is left is
  the walk over the pages and their copies.
- ``dead_rows_len``: the length a retired slot hands the kernel: 0 (what
  the models pass now) or ``stale`` (the 300 tokens a retired request left
  in ``lens``, what they passed before).

Each line gives us a call and a live page, and the pages (``cpb``) and
copies (K and V) of a compute block.

``--repo DIR`` times the kernel of another checkout (the parent's grid
kernel) on the same inputs; it has no ``_block_update``, so only ``mxu``
runs there and means "as it is".

    python experiments/exp_paged_decode.py [--repo DIR] [--calls 20] \
        [--cells smallthinker_full,...] [--arith mxu,copies]

``--rehearse`` runs the same control flow at tiny tables on any device; its
times mean nothing.
"""
import argparse
import json
import sys
import time

import numpy as np

CELLS = {
    # name: rows, pages a row, Hq, Hkv, window, live rows, (lo, hi) tokens
    "chat": (32, 64, 32, 8, None, 11, (100, 600)),
    "longprompt": (16, 128, 32, 8, None, 3, (600, 1900)),
    "trinity_full": (32, 544, 32, 4, None, 24, (200, 2400)),
    "trinity_ring": (32, 129, 32, 4, 2048, 24, (200, 2400)),
    "smallthinker_full": (24, 512, 28, 4, None, 13, (2500, 7100)),
    "smallthinker_ring": (24, 257, 28, 4, 4096, 13, (2500, 7100)),
    "olmo": (48, 320, 32, 32, None, 22, (300, 4500)),
}
D, PS, STALE = 128, 16, 300


def _block_update_vpu(carry, q, k, v, pos0, lo, hi, scale):
    """The old kernel's arithmetic over a compute block, a page at a time:
    K repeated over the query heads of a group, multiplied by q and
    reduced over lanes; the same for p x v. Carry as the kernel's."""
    import jax
    import jax.numpy as jnp

    m, l, acc = carry
    m, l = jnp.transpose(m), jnp.transpose(l)          # [1, Hq]
    g = q.shape[0] // k.shape[1]
    qf = q.astype(jnp.float32)
    for t0 in range(0, k.shape[0], PS):
        kf = jnp.repeat(k[t0:t0 + PS].astype(jnp.float32), g, axis=1)
        vf = jnp.repeat(v[t0:t0 + PS].astype(jnp.float32), g, axis=1)
        s = jnp.sum(qf[None] * kf, axis=-1) * scale     # [ps, Hq]
        pos = pos0 + t0 + jax.lax.broadcasted_iota(jnp.int32, (PS, 1), 0)
        mask = (pos >= lo) & (pos < hi)
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
        acc = acc * jnp.transpose(alpha) + jnp.sum(p[:, :, None] * vf, axis=0)
        m = m_new
    return jnp.transpose(m), jnp.transpose(l), acc


def _block_update_none(carry, q, k, v, pos0, lo, hi, scale):
    """No arithmetic: the block's copies are waited for and nothing is
    computed from them."""
    return carry


def inputs(geometry, stale, seed=0):
    """A cell's pool, table and queries, and its lengths for both values
    of ``dead_rows_len``."""
    import jax.numpy as jnp

    rows, cols, hq, hkv, window, live, (lo, hi) = geometry
    rs = np.random.RandomState(seed)
    ctx = np.minimum(rs.randint(lo, hi, size=live), cols * PS)
    if window is not None:      # lengths count from the window's first page
        ctx = np.where(ctx > window, window + ctx % PS, ctx)
    at = rs.permutation(rows)[:live]
    lens = {}
    for dead, ln in (("0", 0), ("stale", stale)):
        lens[dead] = np.full(rows, ln, np.int32)
        lens[dead][at] = ctx
    # a page a (row, column), scattered over the pool as an allocator that
    # has served for a while leaves them
    pages = rows * cols
    table = rs.permutation(pages).reshape(rows, cols).astype(np.int32)
    pool = (rs.randn(pages, PS, hkv, D) * 0.5).astype(np.float32)
    return dict(
        q=jnp.asarray(rs.randn(rows, hq, D), jnp.bfloat16),
        k=jnp.asarray(pool, jnp.bfloat16),
        v=jnp.asarray(pool[::-1].copy(), jnp.bfloat16),
        table=jnp.asarray(table), lens=lens, window=window,
        live_pages=int(sum(-(-int(c) // PS) for c in ctx)),
        table_pages=pages)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=".")
    ap.add_argument("--calls", type=int, default=20,
                    help="kernel calls in one program (a segment's layers)")
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--arith", default="",
                    help="variants to run (default: every one there is)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny tables, any device: the control flow only")
    args = ap.parse_args()
    sys.path.insert(0, args.repo)
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import paged_attention as pa

    cells, stale = CELLS, STALE
    if args.rehearse:
        cells, stale = {
            name: (4, 12, hq, hkv, window and 64, 2, (20, 150))
            for name, (_, _, hq, hkv, window, _, _) in CELLS.items()}, 30
    elif jax.devices()[0].platform != "tpu":
        raise SystemExit(f"times mean something on a TPU only; JAX found "
                         f"{jax.devices()} (--rehearse runs anywhere)")
    variants = {"mxu": getattr(pa, "_block_update", None)}
    if variants["mxu"] is not None:
        variants["vpu"] = _block_update_vpu
        variants["copies"] = _block_update_none
    if args.arith:
        variants = {k: variants[k] for k in args.arith.split(",")}
    for cell in args.cells.split(","):
        a = inputs(cells[cell], stale)
        want = {dead: _ref(a, ln) for dead, ln in a["lens"].items()}
        for arith, update in variants.items():
            if update is not None:
                pa._block_update = update
            # the kernel's own jit would hand back the last variant's body
            jax.clear_caches()

            def layers(q, k, v, table, lens):
                # one program of ``calls`` kernels, each fed by the last,
                # as a segment's layers are
                for _ in range(args.calls):
                    o = pa.paged_decode_mha(q, k, v, table, lens,
                                            window=a["window"])
                    q = (q + o * 1e-3).astype(q.dtype)
                return o

            fn = jax.jit(layers)
            for dead, lens in a["lens"].items():
                ops = (a["q"], a["k"], a["v"], a["table"], jnp.asarray(lens))
                out = jax.block_until_ready(fn(*ops))
                cpb = pa._pages_per_block(PS, a["k"].shape[2])
                n = 2 if args.rehearse else 30
                t = time.perf_counter()
                for _ in range(n):
                    out = fn(*ops)
                jax.block_until_ready(out)
                us = (time.perf_counter() - t) / (n * args.calls) * 1e6
                err = err32 = None
                if arith != "copies":
                    one = pa.paged_decode_mha(*ops, window=a["window"])
                    err = float(np.abs(np.asarray(one.astype(jnp.float32))
                                       - want[dead]).max())
                    # a float32 query gives a float32 output: rounding p
                    # to bf16 anywhere would show as 1e-3 here, float32
                    # as 1e-6
                    f32 = pa.paged_decode_mha(a["q"].astype(jnp.float32),
                                              *ops[1:], window=a["window"])
                    err32 = float(np.abs(np.asarray(f32) - want[dead]).max())
                print(json.dumps({
                    "cell": cell, "arith": arith, "dead_rows_len": dead,
                    "rehearsal": args.rehearse,
                    "us_per_call": round(us, 2),
                    "live_pages": a["live_pages"],
                    "table_pages": a["table_pages"],
                    "us_per_live_page": round(us / a["live_pages"], 4),
                    "pages_per_block": cpb,
                    "copies_per_block": 2 * cpb,
                    "kv_gb_per_s": round(
                        a["live_pages"] * 2 * PS * a["k"].shape[2] * D * 2
                        / us / 1e3, 1),
                    "max_abs_err_vs_ref": err,
                    "max_abs_err_f32_query": err32,
                    "finite": bool(jnp.isfinite(out).all())}), flush=True)


def _ref(a, lens):
    """Plain softmax attention of every row over the positions it
    attends, in float64 on the host."""
    q, k, v = (np.asarray(a[n].astype("float32")) for n in "qkv")
    table = np.asarray(a["table"])
    out = np.zeros(q.shape)
    for r, ln in enumerate(lens):
        if ln == 0:
            continue
        lo = 0 if a["window"] is None else max(ln - a["window"], 0)
        pages = table[r, :-(-ln // PS)]
        kr = k[pages].reshape(-1, *k.shape[2:])[lo:ln].astype(np.float64)
        vr = v[pages].reshape(-1, *v.shape[2:])[lo:ln].astype(np.float64)
        g = q.shape[1] // kr.shape[1]
        kr, vr = np.repeat(kr, g, 1), np.repeat(vr, g, 1)
        s = np.einsum("hd,lhd->hl", q[r].astype(np.float64), kr) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[r] = np.einsum("hl,lhd->hd", p / p.sum(-1, keepdims=True), vr)
    return out


if __name__ == "__main__":
    main()
