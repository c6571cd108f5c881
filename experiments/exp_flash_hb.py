"""Head-batched (BSHD-native) vs per-head (BHSD) flash kernel on TPU.

Measures, at the 350M bench shapes, the END-TO-END cost each path implies:
kernel fwd / fwd+bwd PLUS the BSHD<->BHSD transposes the per-head path
forces on the caller. Decides FLAGS_flash_head_batched.
(The round-2 fwd-only prototype this file held is superseded by the real
fwd+bwd kernel in paddle_tpu/ops/flash_attention_hb.py.)

Run: python experiments/exp_flash_hb.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.device.compile_cache import use_compile_cache

    use_compile_cache()

    from exp_micro import timed
    from paddle_tpu.ops.flash_attention_hb import flash_attention_bshd_hb
    from paddle_tpu.ops.flash_attention_kernel import flash_attention_bhsd

    B, S, H, D = 8, 2048, 8, 128
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, H, D), jnp.bfloat16)

    def per_head(q, k, v):
        # what ops/pallas.flash_attention does today: transpose around
        # the BHSD kernel — the transposes are PART of this path's cost
        qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
        out = flash_attention_bhsd(qt, kt, vt, causal=True)
        return jnp.swapaxes(out, 1, 2)

    variants = {"per_head_1024": per_head}
    for blk in (256, 512):
        variants[f"hb_{blk}"] = (
            lambda q, k, v, b=blk: flash_attention_bshd_hb(
                q, k, v, causal=True, block_q=b, block_k=b))

    results = {}
    for name, f in variants.items():
        try:
            fwd_ms = timed(jax.jit(f), (q, k, v)) * 1e3

            def loss(q, k, v, _f=f):
                return jnp.sum(_f(q, k, v).astype(jnp.float32))

            g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            bwd_ms = timed(g, (q, k, v)) * 1e3
            results[name] = {"fwd_ms": round(fwd_ms, 3),
                             "fwdbwd_ms": round(bwd_ms, 3)}
        except Exception as e:  # noqa: BLE001 - report per-variant
            results[name] = {"error": f"{type(e).__name__}: {e}"[:200]}
        print(json.dumps({name: results[name]}), flush=True)

    timed_rs = [(r["fwdbwd_ms"], n) for n, r in results.items()
                if "fwdbwd_ms" in r]
    if timed_rs:
        best = min(timed_rs)
        print(json.dumps({"best": best[1], "fwdbwd_ms": best[0],
                          "flip_flag": best[1].startswith("hb_")}))


if __name__ == "__main__":
    main()
