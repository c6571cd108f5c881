"""MFU at larger configs: does wider hidden lift MXU utilization enough
to beat the 350m number? Run: python experiments/exp_big.py [name ...]
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

CONFIGS = {
    # name: (preset, overrides, batch, seq)
    "770m": ("350m", dict(hidden_size=1536, intermediate_size=4096,
                          num_attention_heads=12, num_key_value_heads=12),
             8, 2048),
    "770m_b4": ("350m", dict(hidden_size=1536, intermediate_size=4096,
                             num_attention_heads=12,
                             num_key_value_heads=12), 4, 2048),
    "1b3": ("1b3", dict(num_attention_heads=16, num_key_value_heads=16),
            4, 2048),
}


def run(name):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.device.compile_cache import use_compile_cache

    use_compile_cache()

    from paddle_tpu.models import LlamaForCausalLM, llama_config
    from paddle_tpu.models.llama_functional import (build_train_step,
                                                    stack_params)

    preset, over, B, S = CONFIGS[name]
    cfg = llama_config(preset, dtype="bfloat16",
                       max_position_embeddings=S, recompute="full", **over)
    model = LlamaForCausalLM(cfg)
    params = {k: p.value for k, p in model.named_parameters()}
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    stacked, rest = stack_params(params, cfg)
    step, init = build_train_step(cfg, lr=1e-4, remat=True)
    st = init(stacked, rest)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    lab = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    jitted = jax.jit(step, donate_argnums=(0, 1, 2))
    stacked, rest, st, loss = jitted(stacked, rest, st, ids, lab)
    _ = float(loss)
    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        stacked, rest, st, loss = jitted(stacked, rest, st, ids, lab)
    _ = float(loss)
    dt = (time.perf_counter() - t0) / iters
    toks = B * S
    mfu = 6.0 * n_params * toks / dt / 394e12
    print(json.dumps({"exp": name, "params": n_params,
                      "tps": round(toks / dt, 1),
                      "mfu": round(mfu, 4),
                      "ms_per_step": round(dt * 1e3, 1)}), flush=True)


if __name__ == "__main__":
    names = sys.argv[1:] or list(CONFIGS)
    for n in names:
        try:
            run(n)
        except Exception as e:
            print(json.dumps({"exp": n, "error": str(e)[:200]}), flush=True)
