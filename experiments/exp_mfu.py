"""MFU experiment matrix for the bench config (350M llama, v5e).

Run: python experiments/exp_mfu.py [name ...]   (default: all)
Each config prints one JSON line; compare mfu across remat policy / batch.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def run(name, remat, batch, seq=2048, steps=10, fwd_only=False):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.device.compile_cache import use_compile_cache

    use_compile_cache()

    from paddle_tpu.models import LlamaForCausalLM, llama_config
    from paddle_tpu.models.llama_functional import (build_train_step,
                                                    build_loss_fn,
                                                    stack_params)

    cfg = llama_config("350m", dtype="bfloat16",
                       num_attention_heads=8, num_key_value_heads=8,
                       max_position_embeddings=seq, recompute="full")
    model = LlamaForCausalLM(cfg)
    params = {k: p.value for k, p in model.named_parameters()}
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    stacked, rest = stack_params(params, cfg)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)

    if fwd_only:
        loss_fn = build_loss_fn(cfg, remat=remat)

        def multi(stacked, rest, ids, labels, n):
            def body(_, acc):
                return acc + loss_fn(stacked, rest, ids, labels)
            return jax.lax.fori_loop(0, n, body, jnp.zeros((), jnp.float32))

        jitted = jax.jit(multi, static_argnums=(4,))
        args = (stacked, rest, ids, labels, steps)
        out = jitted(*args); _ = float(out)
        t0 = time.perf_counter()
        out = jitted(*args); _ = float(out)
        dt = time.perf_counter() - t0
        flops_per_tok = 2.0 * n_params
    else:
        step, init = build_train_step(cfg, lr=1e-4, remat=remat)
        opt_state = init(stacked, rest)

        def multi(stacked, rest, st, ids, labels, n):
            def body(_, carry):
                stacked, rest, st, _ = carry
                stacked, rest, st, loss = step(stacked, rest, st, ids, labels)
                return stacked, rest, st, loss.astype(jnp.float32)
            return jax.lax.fori_loop(0, n, body,
                                     (stacked, rest, st,
                                      jnp.zeros((), jnp.float32)))

        jitted = jax.jit(multi, static_argnums=(5,), donate_argnums=(0, 1, 2))
        stacked, rest, opt_state, loss = jitted(stacked, rest, opt_state,
                                                ids, labels, steps)
        _ = float(loss)
        t0 = time.perf_counter()
        stacked, rest, opt_state, loss = jitted(stacked, rest, opt_state,
                                                ids, labels, steps)
        _ = float(loss)
        dt = time.perf_counter() - t0
        flops_per_tok = 6.0 * n_params

    tokens = batch * seq * steps
    peak = 394e12
    mfu = flops_per_tok * tokens / dt / peak
    print(json.dumps({"exp": name, "remat": str(remat), "batch": batch,
                      "tps": round(tokens / dt, 1), "mfu": round(mfu, 4),
                      "dt": round(dt, 3)}), flush=True)


CONFIGS = {
    "base": dict(remat="full", batch=8),
    "dots": dict(remat="dots", batch=8),
    "none": dict(remat="none", batch=8),
    "b16_full": dict(remat="full", batch=16),
    "b16_dots": dict(remat="dots", batch=16),
    "fwd_full": dict(remat="full", batch=8, fwd_only=True),
    "fwd_none": dict(remat="none", batch=8, fwd_only=True),
}

if __name__ == "__main__":
    names = sys.argv[1:] or list(CONFIGS)
    for n in names:
        try:
            run(n, **CONFIGS[n])
        except Exception as e:
            print(json.dumps({"exp": n, "error": str(e)[:300]}), flush=True)
