"""The dots-bucket attack plan (PERF.md Headroom #1): dots sit at ~43%
MXU and dominate the 350M step (223ms). Each experiment here is an
UNTRIED lever (merged-QKV and remat="dots" already measured and
rejected — see PERF.md "did NOT work"); run on TPU, flip a default only
on a >=3% full-step win.

  E1 scan unroll      — lax.scan(unroll=k) exposes k consecutive layers
                        to one XLA fusion scope: boundary relayouts and
                        convert tails can fuse across layers. Measures
                        the FULL 350M loss fwd+bwd at unroll 1/2/4.
  E2 dot form         — [B,S,H]x[H,N] einsum vs reshape-to-2D
                        [B*S,H]@[H,N]: batched-3D vs flat-2D tiling.
  E3 rhs layout       — W[in,out] (ours) vs W[out,in] consumed as
                        dot_general with contracting dim 1 ("transposed
                        weights"): whether XLA inserts a relayout for
                        one of the forms at bf16.
  E4 dot out dtype    — bf16 dot -> f32 output (preferred_element_type)
                        vs bf16 output + later upcast: convert-tail
                        fusion (PERF.md's ~25ms convert bucket).
  E5 remat attn_out   — jax.checkpoint save_only_these_names("attn_out"):
                        keep ONLY flash outputs across the scan; kills
                        the refwd-flash bucket (~22ms/step) for ~800MB
                        (vs remat="dots"'s rejected 8.4GB).

Run: python experiments/exp_dots.py            (TPU; ~2 min)

Each variant runs in its OWN subprocess with a wall-clock budget
(EXP_VARIANT_SECS, default 600): per-variant isolation means one hung
compile costs one variant, not the run. Variants run one at a time and
the parent never touches JAX, so one process at a time wants the chip.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

VARIANTS = ("E1_unroll1", "E1_unroll2", "E1_unroll4", "E5_remat_attn_out",
            "E2_einsum3d", "E2_flat2d", "E3_rhs_transposed", "E4_f32_out")


def run_variants():
    """Parent: one subprocess per variant via the shared budget harness
    (own session, TERM-then-KILL group, SIGTERM forwarded — a hung
    child can never outlive us holding the chip)."""
    from _budget import run_budgeted

    budget = int(os.environ.get("EXP_VARIANT_SECS", "600"))
    lines = []
    for name in VARIANTS:
        r = run_budgeted([sys.executable, "-u", os.path.abspath(__file__),
                          "--variant", name], budget)
        if r.timed_out:
            print(json.dumps({name: f"hung >{budget}s (group killed)"}),
                  flush=True)
        if r.err.strip():
            sys.stderr.write(f"--- {name} stderr tail ---\n"
                             + r.err[-2000:] + "\n")
        got = [ln for ln in r.out.splitlines()
               if ln.strip().startswith("{")]
        for ln in got:
            print(ln, flush=True)
        if got:
            lines.append(name)
    print(json.dumps({"variants_with_output": len(lines),
                      "of": len(VARIANTS)}))


def main(only: str = None):
    import jax

    if os.environ.get("EXP_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from paddle_tpu.device.compile_cache import use_compile_cache

    use_compile_cache()

    from exp_micro import timed

    from paddle_tpu.models import LlamaForCausalLM, llama_config
    from paddle_tpu.models.llama_functional import (build_loss_fn,
                                                    stack_params)

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = llama_config("350m", dtype="bfloat16", num_attention_heads=8,
                           num_key_value_heads=8,
                           max_position_embeddings=2048, recompute="full")
        B, S = 8, 2048
    else:
        cfg = llama_config("tiny")
        B, S = 2, 64
    rng = np.random.RandomState(0)
    results = {}
    full_step = [(f"E1_unroll{u}", dict(remat=True, scan_unroll=u))
                 for u in (1, 2, 4)]
    # E5: selective remat — save ONLY the flash outputs; kills the
    # refwd-flash bucket (~22ms/step) for ~800MB at bench shapes (vs the
    # rejected remat="dots" 8.4GB)
    full_step.append(("E5_remat_attn_out", dict(remat="attn_out")))
    full_step = [fs for fs in full_step
                 if only is None or only == fs[0]]
    if full_step:
        model = LlamaForCausalLM(cfg)
        params = {k: p.value for k, p in model.named_parameters()}
        stacked, rest = stack_params(params, cfg)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
        y = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32)

    # ---- E1/E5: full loss fwd+bwd (scan unroll / remat policy) -------------
    for vname, build_kw in full_step:
        try:
            loss_fn = build_loss_fn(cfg, **build_kw)

            # timed() chains its perturbation through arg 0, which must
            # be a float array: thread the embedding weight through
            def gfn(emb_w, _lf=loss_fn):
                r2 = dict(rest)
                r2["model.embed_tokens.weight"] = emb_w
                return jax.grad(
                    lambda p: _lf(p["s"], p["r"], ids, y))(
                        {"s": stacked, "r": r2})

            ms = timed(jax.jit(gfn),
                       (rest["model.embed_tokens.weight"],)) * 1e3
            results[f"{vname}_fwdbwd_ms"] = round(ms, 2)
        except Exception as e:  # noqa: BLE001
            results[f"{vname}_fwdbwd_ms"] = \
                f"{type(e).__name__}: {e}"[:120]
        print(json.dumps({vname: results[f"{vname}_fwdbwd_ms"]}),
              flush=True)

    # ---- E2/E3/E4: dot micro-forms at layer shapes -------------------------
    H, I = cfg.hidden_size, cfg.intermediate_size
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    x3 = jnp.asarray(rng.randn(B, S, H), dt)
    w = jnp.asarray(rng.randn(H, I) * 0.02, dt)
    wt = jnp.asarray(np.asarray(w).T.copy())

    def e2_einsum(x, w):
        return jnp.einsum("bsh,hi->bsi", x, w)

    def e2_flat(x, w):
        return (x.reshape(-1, H) @ w).reshape(B, S, I)

    def e3_transposed(x, wt):
        return jax.lax.dot_general(x, wt, (((2,), (1,)), ((), ())))

    def e4_f32out(x, w):
        return jax.lax.dot_general(
            x, w, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dt)

    for name, fn, args in (
            ("E2_einsum3d", e2_einsum, (x3, w)),
            ("E2_flat2d", e2_flat, (x3, w)),
            ("E3_rhs_transposed", e3_transposed, (x3, wt)),
            ("E4_f32_out", e4_f32out, (x3, w))):
        if only is not None and only != name:
            continue
        try:
            ms = timed(jax.jit(fn), args) * 1e3
            results[f"{name}_ms"] = round(ms, 3)
        except Exception as e:  # noqa: BLE001
            results[f"{name}_ms"] = f"{type(e).__name__}: {e}"[:120]
        print(json.dumps({name: results[f"{name}_ms"]}), flush=True)

    print(json.dumps({"platform": jax.default_backend(), **results}))


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--variant":
        main(only=sys.argv[2])
    else:
        run_variants()
