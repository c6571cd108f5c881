"""Component microbenchmarks at bench shapes (350M llama, b8 s2048).

Times each building block with a carry-dependent loop (no loop-invariant
hoisting). Run: python experiments/exp_micro.py [name ...]
"""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def timed(fn, args, iters=20):
    import jax
    import jax.numpy as jnp

    # chain: perturb first arg by a tiny nonzero function of the output so
    # XLA can neither hoist the body (loop-variant input) nor simplify the
    # add away (x + 0 would fold; x + s*1e-30 does not)
    def loop(args, n):
        def body(_, a):
            out = fn(*a)
            s = jax.tree.map(lambda x: jnp.sum(x).astype(jnp.float32), out)
            tot = jax.tree.reduce(lambda p, q: p + q, s) * 1e-30
            return (a[0] + tot.astype(a[0].dtype),) + tuple(a[1:])

        out = jax.lax.fori_loop(0, n, body, args)
        # scalar result: the timed region ends on its host readback
        return jnp.sum(out[0].astype(jnp.float32).ravel()[:128])

    jit = jax.jit(loop, static_argnums=(1,))
    # two iteration counts; the difference cancels the constant dispatch +
    # readback cost that otherwise dominates sub-ms ops
    lo, hi = iters, iters * 6
    _ = float(jit(args, lo))
    _ = float(jit(args, hi))
    t0 = time.perf_counter()
    _ = float(jit(args, lo))
    t1 = time.perf_counter()
    _ = float(jit(args, hi))
    t2 = time.perf_counter()
    return ((t2 - t1) - (t1 - t0)) / (hi - lo)


def main(names):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.device.compile_cache import use_compile_cache

    use_compile_cache()

    B, S, H, D, HID, FF, V, L = 8, 2048, 8, 128, 1024, 2816, 32000, 24
    key = jax.random.PRNGKey(0)
    bf = jnp.bfloat16

    results = {}

    def rec(name, t, flops=None):
        r = {"ms": round(t * 1e3, 3)}
        if flops:
            r["tflops"] = round(flops / t / 1e12, 1)
            r["mxu_pct"] = round(100 * flops / t / 394e12, 1)
        results[name] = r
        print(json.dumps({name: r}), flush=True)

    x = jax.random.normal(key, (B * S, HID), bf)
    w1 = jax.random.normal(key, (HID, FF), bf)

    if "matmul" in names:
        t = timed(lambda a, b: a @ b, (x, w1))
        rec("matmul_16k_1024_2816", t, 2 * B * S * HID * FF)

    if "matmul_vocab" in names:
        wv = jax.random.normal(key, (HID, V), bf)
        t = timed(lambda a, b: a @ b, (x, wv))
        rec("matmul_16k_1024_32000", t, 2 * B * S * HID * V)

    q = jax.random.normal(key, (B, S, H, D), bf)
    k = jax.random.normal(key, (B, S, H, D), bf)
    v = jax.random.normal(key, (B, S, H, D), bf)
    # causal attention FLOPs (fwd): 2*2*B*H*S^2*D / 2
    att_flops = 2 * B * H * S * S * D

    if "flash_fwd" in names:
        from paddle_tpu.ops.pallas import flash_attention

        t = timed(lambda q, k, v: flash_attention(q, k, v, causal=True),
                  (q, k, v))
        rec("flash_fwd", t, att_flops)

    if "flash_bwd" in names:
        from paddle_tpu.ops.pallas import flash_attention

        def fb(q, k, v):
            def f(q, k, v):
                return jnp.sum(
                    flash_attention(q, k, v, causal=True).astype(jnp.float32))
            return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        t = timed(fb, (q, k, v))
        rec("flash_fwd+bwd", t, 3 * att_flops)

    if "xla_attn" in names:
        def sdpa(q, k, v):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, v,
                           preferred_element_type=jnp.float32)
            mask = jnp.tril(jnp.ones((S, S), bool))
            s = jnp.where(mask, s / np.sqrt(D), -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(bf)
            return jnp.einsum("bhqk,bkhd->bqhd", p, k)

        t = timed(sdpa, (q, k, v))
        rec("xla_sdpa_fwd", t, att_flops)

    if "rms" in names:
        from paddle_tpu.models.llama_functional import _rms

        xh = jax.random.normal(key, (B, S, HID), bf)
        w = jnp.ones((HID,), bf)
        t = timed(lambda a, b: _rms(a, b, 1e-5), (xh, w))
        rec("rms_norm", t)

    if "rope" in names:
        from paddle_tpu.models.llama import _rope_cos_sin, apply_rotary_emb

        cos, sin = _rope_cos_sin(S, D, 10000.0, bf)
        t = timed(lambda a: apply_rotary_emb(a, cos, sin), (q,))
        rec("rope", t)

    if "loss" in names:
        logits = jax.random.normal(key, (B, S, V), bf)
        lbl = jnp.zeros((B, S), jnp.int32)

        def ce(lg, lb):
            lp = jax.nn.log_softmax(lg.astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(lp, lb[..., None], -1)[..., 0]
            return jnp.mean(nll)

        t = timed(ce, (logits, lbl))
        rec("ce_loss_fwd", t)

    if "layer_fwd" in names:
        from paddle_tpu.models.llama import LlamaConfig
        from paddle_tpu.models.llama_functional import _layer_fwd
        from paddle_tpu.models.llama import _rope_cos_sin

        cfg = LlamaConfig(hidden_size=HID, intermediate_size=FF,
                          num_hidden_layers=1, num_attention_heads=H,
                          num_key_value_heads=H, vocab_size=V,
                          dtype="bfloat16")
        cos, sin = _rope_cos_sin(S, cfg.head_dim, cfg.rope_theta, bf)
        lp = {
            "input_layernorm.weight": jnp.ones((HID,), bf),
            "post_attention_layernorm.weight": jnp.ones((HID,), bf),
            "self_attn.q_proj.weight": jax.random.normal(key, (HID, HID), bf) * 0.02,
            "self_attn.k_proj.weight": jax.random.normal(key, (HID, HID), bf) * 0.02,
            "self_attn.v_proj.weight": jax.random.normal(key, (HID, HID), bf) * 0.02,
            "self_attn.o_proj.weight": jax.random.normal(key, (HID, HID), bf) * 0.02,
            "mlp.gate_proj.weight": jax.random.normal(key, (HID, FF), bf) * 0.02,
            "mlp.up_proj.weight": jax.random.normal(key, (HID, FF), bf) * 0.02,
            "mlp.down_proj.weight": jax.random.normal(key, (FF, HID), bf) * 0.02,
        }
        xh = jax.random.normal(key, (B, S, HID), bf)
        t = timed(lambda a: _layer_fwd(lp, a, cos, sin, cfg), (xh,))
        layer_flops = 2 * B * S * (4 * HID * HID + 3 * HID * FF) + att_flops
        rec("decoder_layer_fwd", t, layer_flops)

    print(json.dumps(results))


ALL = ["matmul", "matmul_vocab", "flash_fwd", "flash_bwd", "xla_attn",
       "rms", "rope", "loss", "layer_fwd"]

if __name__ == "__main__":
    main(sys.argv[1:] or ALL)
