"""``selected_attention`` (the kernel ``mla_selected_prefill``) alone on the
chip at the latent cell's shapes: us a call, us a computed block pair and
head, and the share of the matrix unit's peak. Prints one JSON line a case.

A call is one head group of one layer's prefill: q / k ``[16, S, 192]``,
v ``[16, S, 128]`` bf16, ``S`` a bucket of the cell; whatever the callee
does to its operands on the way in (PR 32's kernel pads q and k to 256 in
XLA, 0.8 ms a call at 16,384) is in its time, as it is in the model's.
Cases a bucket: under a random selection's mask (each query keeps itself
and about 2,048 of the positions before it) and causal alone, ``last_idx``
at ``S - 1``; the widest bucket also with ``last_idx`` 8,705 (an 8.7k
prompt in the 16,384 bucket, which the cell's traced window holds twice).
A block pair is 512 queries by 512 keys at or under the diagonal and at or
before ``last_idx``, whatever blocks the kernel itself walks, so two
tilings compare by it; its floor is 2 x 512 x 512 x (Dk + Dv) FLOPs a head
at 197 TFLOP/s: 1.02 us at the executed width (the matrix unit takes keys
of 192 as 256), 0.85 us at the least (192 + 128).

``--repo DIR`` times the kernel of another checkout (the parent's) on the
same inputs. ``--tilings`` sweeps this checkout's kernel over tilings given
as ``block:sub_tile:heads`` (comma separated; the module's own choice
is ``auto``); it has no meaning for a checkout whose module has no
``_tiling``.

    python experiments/exp_mla_prefill.py [--repo DIR] [--sizes 4096,8192,16384]

``--rehearse`` runs the same control flow at a tiny size on any device; its
times mean nothing.
"""
import argparse
import functools
import inspect
import json
import sys
import time

import numpy as np

H, DK, DV, TOPK, PAIR = 16, 192, 128, 2048, 512
PEAK_FLOPS = 197e12                 # benchmark/lib/peaks.py, TPU v5e bf16
SHORT_LAST = 8705                   # an 8,706-token prompt's last position


def block_pairs(last, pair=PAIR):
    """512 x 512 block pairs at or under the diagonal whose queries' block
    starts at or before ``last``."""
    n = last // pair + 1
    return n * (n + 1) // 2


def selection(s, topk, seed=0, rows=512):
    """An int8 [s, s] mask, made on the device a block of rows at a time:
    causal, the diagonal kept, every other position kept with probability
    ``topk / (t + 1)`` (so about ``topk`` of a long row's positions)."""
    import jax
    import jax.numpy as jnp

    rows = min(rows, s)
    cols = jnp.arange(s)[None, :]

    def block(r0):
        t = r0 + jnp.arange(rows)[:, None]
        u = jax.random.uniform(jax.random.fold_in(jax.random.PRNGKey(seed),
                                                  r0), (rows, s))
        keep = (u * (t + 1) < topk) | (cols == t)
        return (keep & (cols <= t)).astype(jnp.int8)

    return jax.jit(lambda: jax.lax.map(
        block, rows * jnp.arange(s // rows)).reshape(s, s))()


def reference(q, k, v, mask, scale):
    """Plain masked softmax attention in float32 (highest precision)."""
    import jax
    import jax.numpy as jnp

    s = q.shape[1]
    keep = jnp.tril(jnp.ones((s, s), bool))
    if mask is not None:
        keep = keep & (mask != 0)
    sc = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                    k.astype(jnp.float32), precision="highest") * scale
    p = jax.nn.softmax(jnp.where(keep[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,hkv->hqv", p, v.astype(jnp.float32),
                      precision="highest")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=".")
    ap.add_argument("--sizes", default="4096,8192,16384")
    ap.add_argument("--calls", type=int, default=4,
                    help="kernel calls in one program (a layer's groups)")
    ap.add_argument("--tilings", default="auto")
    ap.add_argument("--cases", default="selected,causal,short",
                    help="short: the selection with last_idx 8,705 "
                    "(the widest size only)")
    ap.add_argument("--rehearse", action="store_true",
                    help="a tiny size, any device: the control flow only")
    args = ap.parse_args()
    sys.path.insert(0, args.repo)
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import sparse_latent_attention as sla

    h, topk, short_last = H, TOPK, SHORT_LAST
    sizes = [int(x) for x in args.sizes.split(",")]
    if args.rehearse:
        h, topk, sizes, short_last = 4, 256, [1024], 300
    elif jax.devices()[0].platform != "tpu":
        raise SystemExit(f"times mean something on a TPU only; JAX found "
                         f"{jax.devices()} (--rehearse runs anywhere)")
    scale = 0.1147                  # the model's softmax_scale is of this size
    own = getattr(sla, "_tiling", None)
    if "scale" in inspect.signature(
            sla.selected_attention.__wrapped__).parameters:
        attention = functools.partial(sla.selected_attention, scale=scale)
        in_q = 1.0
    else:                           # the scale rides in q
        attention, in_q = sla.selected_attention, scale
    for s in sizes:
        keys = jax.random.split(jax.random.PRNGKey(s), 3)
        q, k = (jax.random.normal(kk, (h, s, DK), jnp.bfloat16)
                for kk in keys[:2])
        q = (q.astype(jnp.float32) * in_q).astype(q.dtype)
        v = jax.random.normal(keys[2], (h, s, DV), jnp.bfloat16)
        mask = selection(s, topk)
        cases = [("selected", mask, s - 1), ("causal", None, s - 1)]
        if s == max(sizes) and short_last < s - PAIR:
            cases.append(("short", mask, short_last))
        cases = [c for c in cases if c[0] in args.cases.split(",")]
        want = {}
        if s <= 4096:               # [h, s, s] float32 scores: small s only
            want = {name: np.asarray(reference(q, k, v, m, scale / in_q))
                    for name, m, last in cases if last == s - 1}
        for tiling in args.tilings.split(","):
            if tiling != "auto":
                if own is None:
                    raise SystemExit(f"{args.repo}'s kernel has no _tiling")
                fixed = tuple(int(x) for x in tiling.split(":"))
                sla._tiling = lambda *a, fixed=fixed: fixed
            elif own is not None:
                sla._tiling = own
            # the kernel's own jit would hand back the last tiling's body
            jax.clear_caches()
            for name, m, last in cases:

                def layer(q, k, v, m, last):
                    # ``calls`` kernels in one program, each fed by the
                    # last, as a layer's head groups follow each other
                    for _ in range(args.calls):
                        o = attention(q, k, v, m, last)
                        v = (v + o * 1e-3).astype(v.dtype)
                    return o

                fn = jax.jit(layer)
                ops = (q, k, v, m, jnp.int32(last))
                line = {"s": s, "case": name, "last": last, "tiling": tiling,
                        "rehearsal": args.rehearse}
                try:
                    out = jax.block_until_ready(fn(*ops))
                except Exception as e:  # noqa: BLE001 - a tiling refused
                    print(json.dumps({**line, "error":
                                      f"{type(e).__name__}: {e}"[:300]}),
                          flush=True)
                    continue
                n = 1 if args.rehearse else max(3, min(20, 2 ** 27 // s ** 2))
                t = time.perf_counter()
                for _ in range(n):
                    out = fn(*ops)
                jax.block_until_ready(out)
                us = (time.perf_counter() - t) / (n * args.calls) * 1e6
                pairs = block_pairs(last)
                us_pair = us / (pairs * h)
                flops = 2 * PAIR * PAIR / (us_pair * 1e-6)
                line.update(
                    us_per_call=round(us, 1), block_pairs=pairs,
                    us_per_pair_head=round(us_pair, 4),
                    peak_share_executed_width_pct=round(
                        100 * flops * (DK + (-DK % 128) + DV) / PEAK_FLOPS, 2),
                    peak_share_least_width_pct=round(
                        100 * flops * (DK + DV) / PEAK_FLOPS, 2),
                    finite=bool(jnp.isfinite(out).all()))
                if name in want and last == s - 1:
                    one = attention(*ops)
                    line["max_abs_err_vs_f32_ref"] = float(np.abs(
                        np.asarray(one.astype(jnp.float32))
                        - want[name]).max())
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
