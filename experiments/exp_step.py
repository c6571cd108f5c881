"""Step-level time decomposition for the bench config on TPU.

Times: full train step / grad-only / loss fwd / logits fwd, all chained
(params perturbed by tiny*result each iteration) with scalar readback.
Run: python experiments/exp_step.py [iters]
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.device.compile_cache import use_compile_cache

    use_compile_cache()

    from paddle_tpu.models import LlamaForCausalLM, llama_config
    from paddle_tpu.models.llama_functional import (build_loss_fn,
                                                    build_train_step, forward,
                                                    stack_params)

    iters = int(sys.argv[1]) if len(sys.argv) > 1 else 10
    cfg = llama_config("350m", dtype="bfloat16",
                       num_attention_heads=8, num_key_value_heads=8,
                       max_position_embeddings=2048, recompute="full")
    B, S = 8, 2048
    model = LlamaForCausalLM(cfg)
    params = {k: p.value for k, p in model.named_parameters()}
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    stacked, rest = stack_params(params, cfg)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    labels = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)

    def bench(name, make_loop, flops_per_tok=None):
        jit = jax.jit(make_loop, static_argnums=(1,))
        _ = float(jit((stacked, rest), iters))
        t0 = time.perf_counter()
        _ = float(jit((stacked, rest), iters))
        dt = (time.perf_counter() - t0) / iters
        rec = {"ms_per_iter": round(dt * 1e3, 2)}
        if flops_per_tok:
            rec["mfu"] = round(flops_per_tok * B * S / dt / 394e12, 4)
        print(json.dumps({name: rec}), flush=True)

    loss_fn = build_loss_fn(cfg, remat="full")
    loss_fn_dots = build_loss_fn(cfg, remat="dots")

    def perturb(p, scalar):
        eps = scalar.astype(jnp.float32) * 1e-30
        return jax.tree.map(lambda a: a + eps.astype(a.dtype), p)

    def loop_fwd_logits(p, n):
        def body(_, p):
            lg = forward(p[0], p[1], ids, cfg, remat="full")
            return (perturb(p[0], jnp.sum(lg[..., :64].astype(jnp.float32))),
                    p[1])
        p = jax.lax.fori_loop(0, n, body, p)
        return jnp.sum(p[0]["input_layernorm.weight"].astype(jnp.float32))

    def loop_fwd_loss(p, n):
        def body(_, p):
            l = loss_fn(p[0], p[1], ids, labels)
            return (perturb(p[0], l), p[1])
        p = jax.lax.fori_loop(0, n, body, p)
        return jnp.sum(p[0]["input_layernorm.weight"].astype(jnp.float32))

    def loop_grad(p, n):
        def body(_, p):
            l, g = jax.value_and_grad(
                lambda q: loss_fn(q["s"], q["r"], ids, labels))(
                    {"s": p[0], "r": p[1]})
            return (perturb(p[0], l + jnp.sum(
                g["s"]["input_layernorm.weight"].astype(jnp.float32))), p[1])
        p = jax.lax.fori_loop(0, n, body, p)
        return jnp.sum(p[0]["input_layernorm.weight"].astype(jnp.float32))

    def loop_grad_dots(p, n):
        def body(_, p):
            l, g = jax.value_and_grad(
                lambda q: loss_fn_dots(q["s"], q["r"], ids, labels))(
                    {"s": p[0], "r": p[1]})
            return (perturb(p[0], l + jnp.sum(
                g["s"]["input_layernorm.weight"].astype(jnp.float32))), p[1])
        p = jax.lax.fori_loop(0, n, body, p)
        return jnp.sum(p[0]["input_layernorm.weight"].astype(jnp.float32))

    from paddle_tpu.optimizer.functional import (adamw_init, adamw_update,
                                                 clip_by_global_norm)

    opt0 = adamw_init({"s": stacked, "r": rest})

    def loop_opt_only(p, n):
        grads = jax.tree.map(jnp.ones_like, {"s": p[0], "r": p[1]})

        def body(_, carry):
            pv, st = carry
            g, _ = clip_by_global_norm(grads, 1.0)
            st, pv = adamw_update(g, st, pv, lr=1e-4)
            return pv, st

        pv, st = jax.lax.fori_loop(0, n, body, ({"s": p[0], "r": p[1]}, opt0))
        return jnp.sum(pv["s"]["input_layernorm.weight"].astype(jnp.float32))

    def loop_clip_only(p, n):
        def body(_, carry):
            _, nrm = clip_by_global_norm(carry, 1.0)
            return jax.tree.map(
                lambda a: a + (nrm * 1e-30).astype(a.dtype), carry)

        out = jax.lax.fori_loop(0, n, body, {"s": p[0], "r": p[1]})
        return jnp.sum(out["s"]["input_layernorm.weight"].astype(jnp.float32))

    bench("fwd_logits", loop_fwd_logits, 2 * n_params)
    bench("fwd_loss", loop_fwd_loss, 2 * n_params)
    bench("grad_full_remat", loop_grad, 6 * n_params)
    bench("grad_dots_remat", loop_grad_dots, 6 * n_params)
    bench("opt_clip_update", loop_opt_only)
    bench("clip_only", loop_clip_only)


if __name__ == "__main__":
    main()
