"""Per-kernel op benchmark (reference analog: tools/test_op_benchmark.sh +
test/cpp/fluid/benchmark/op_tester.cc — the op-perf CI gate's measurement
half).

Runs the framework's hot kernels at bench shapes and writes one JSON
object per op. Pair with ``check_op_benchmark_result.py`` to gate
regressions between two runs.

    python tools/op_benchmark.py --out ops_now.json [--ops rms,rope,...]

Timing: chained loop bodies (no hoisting), a scalar host readback ending
each timed region, two iteration counts differenced to cancel the constant
dispatch cost.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def timed(fn, args, iters=10):
    import jax
    import jax.numpy as jnp

    def loop(args, n):
        def body(_, a):
            out = fn(*a)
            s = jax.tree.map(lambda x: jnp.sum(x).astype(jnp.float32), out)
            tot = jax.tree.reduce(lambda p, q: p + q, s) * 1e-30
            return (a[0] + tot.astype(a[0].dtype),) + tuple(a[1:])

        out = jax.lax.fori_loop(0, n, body, args)
        return jnp.sum(out[0].astype(jnp.float32).ravel()[:128])

    jit = jax.jit(loop, static_argnums=(1,))
    lo, hi = iters, iters * 6
    _ = float(jit(args, lo))
    _ = float(jit(args, hi))
    t0 = time.perf_counter()
    _ = float(jit(args, lo))
    t1 = time.perf_counter()
    _ = float(jit(args, hi))
    t2 = time.perf_counter()
    return max(((t2 - t1) - (t1 - t0)) / (hi - lo), 1e-9)


def build_ops():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.ops.flash_attention_kernel import flash_attention_bhsd

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        B, H, S, D, HID = 8, 8, 2048, 128, 1024
    else:  # CPU smoke: tiny shapes so interpret-mode kernels finish
        B, H, S, D, HID = 1, 2, 128, 32, 64
    key = jax.random.PRNGKey(0)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    q = jax.random.normal(key, (B, H, S, D), dt)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, S, D), dt)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, S, D), dt)
    x = jax.random.normal(key, (B, S, HID), dt)
    g = jnp.ones((HID,), dt)
    qr = jax.random.normal(key, (B, S, H, D), dt)
    cos = jax.random.normal(key, (S, D // 2), dt)
    sin = jax.random.normal(key, (S, D // 2), dt)
    att = 2 * B * H * S * S * D

    ops = {
        "flash_fwd": (lambda q, k, v: flash_attention_bhsd(
            q, k, v, causal=True), (q, k, v), att),
        "flash_fwd_bwd": (lambda q, k, v: jax.grad(
            lambda a, b, c: jnp.sum(flash_attention_bhsd(
                a, b, c, causal=True).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v), (q, k, v), 3 * att),
        "rms_norm": (lambda x, g: pk.rms_norm(x, g), (x, g), None),
        "fused_rope": (lambda a: pk.fused_rope(a, cos, sin), (qr,), None),
        "matmul_hid_4x": (
            lambda a, w: a.reshape(-1, HID) @ w,
            (x, jax.random.normal(key, (HID, 4 * HID), dt)),
            2 * B * S * HID * 4 * HID),
    }
    return ops


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="op_bench.json")
    ap.add_argument("--ops", default=None,
                    help="comma-separated subset (default all)")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    import jax

    ops = build_ops()
    names = args.ops.split(",") if args.ops else list(ops)
    results = {}
    for name in names:
        fn, fargs, flops = ops[name]
        try:
            t = timed(fn, fargs, iters=args.iters)
            rec = {"ms": round(t * 1e3, 4)}
            if flops:
                rec["tflops"] = round(flops / t / 1e12, 2)
            results[name] = rec
        except Exception as e:  # noqa: BLE001 — record, keep going
            results[name] = {"error": str(e)[:200]}
        print(json.dumps({name: results[name]}), flush=True)
    payload = {"platform": jax.devices()[0].platform,
               "device_kind": jax.devices()[0].device_kind,
               "ops": results}
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
