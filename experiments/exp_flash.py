"""Flash-attention kernel block-size sweep at bench shapes on TPU.

Run: python experiments/exp_flash.py
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
    from paddle_tpu.ops.flash_attention_kernel import flash_attention_bhsd

    B, H, S, D = 8, 8, 2048, 128
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, S, D), jnp.bfloat16)
    k = jax.random.normal(key, (B, H, S, D), jnp.bfloat16)
    v = jax.random.normal(key, (B, H, S, D), jnp.bfloat16)
    att_flops = 2 * B * H * S * S * D  # fwd, non-causal count

    for bq, bk in [(512, 512), (256, 512), (512, 256), (1024, 512),
                   (512, 1024), (1024, 1024), (256, 1024), (2048, 512),
                   (512, 2048), (128, 512)]:
        try:
            def f(q, k, v):
                return flash_attention_bhsd(q, k, v, causal=True,
                                            block_q=bq, block_k=bk)

            t = timed(f, (q, k, v), iters=10)

            def fb(q, k, v):
                def g(q, k, v):
                    return jnp.sum(flash_attention_bhsd(
                        q, k, v, causal=True, block_q=bq,
                        block_k=bk).astype(jnp.float32))
                return jax.grad(g, argnums=(0, 1, 2))(q, k, v)

            tb = timed(fb, (q, k, v), iters=10)
            print(json.dumps({
                "bq": bq, "bk": bk,
                "fwd_ms": round(t * 1e3, 3),
                "fwd_mxu_pct": round(100 * att_flops / t / 394e12, 1),
                "fwdbwd_ms": round(tb * 1e3, 3),
                "fwdbwd_mxu_pct": round(100 * 3 * att_flops / tb / 394e12,
                                        1)}), flush=True)
        except Exception as e:
            print(json.dumps({"bq": bq, "bk": bk,
                              "error": str(e)[:120]}), flush=True)


if __name__ == "__main__":
    main()
