"""Benchmark driver: one JSON line for the round record.

Measures flagship-model (Llama-family) training throughput on the chip:
tokens/sec/chip and MFU (model FLOPs 6·N·tokens / peak). North star
(BASELINE.md): ≥50% MFU — `vs_baseline` reports MFU/0.50 so 1.0 == target
(the reference publishes no absolute numbers, BASELINE.json "published": {}).

Run: python bench.py [mode]

Every mode but `hybrid` measures the TPU and FAILS when JAX finds none.
The one exception is asked for from outside: `JAX_PLATFORMS=cpu` runs the
mode's tiny configuration on the CPU as a smoke test, and that record
carries no `mfu` and no `vs_baseline` — a CPU timing is not a result.
One process holds the chip; no mode probes it from a child first.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _platform() -> str:
    """Place the compile cache, then name the platform this run measures:
    "tpu", or "cpu" when JAX_PLATFORMS=cpu asked for the tiny smoke run.
    Anything else is an error — there is no fallback."""
    from paddle_tpu.device.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"bench.py measures a TPU and found platform {platform!r}; "
            "set JAX_PLATFORMS=cpu for a tiny smoke run on the CPU")
    return platform


def _emit(rec: dict) -> None:
    """Print the record; off the TPU it loses the device metrics."""
    if rec.get("platform") != "tpu":
        rec.pop("mfu", None)
        rec.pop("vs_baseline", None)
    print(json.dumps(rec))


def main(model_size: str = "350m"):
    if model_size not in ("350m", "1.3b"):
        raise SystemExit(
            f"unknown model size {model_size!r} (350m|1.3b) — refusing to "
            f"mislabel a benchmark record")

    platform = _platform()
    on_tpu = platform == "tpu"

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM, llama_config
    from paddle_tpu.models.llama_functional import (build_train_step,
                                                    stack_params)

    moment_dtype = None
    if on_tpu:
        # 350M-param Llama with head_dim 128 (8 heads x 128 instead of
        # 16 x 64): same parameter count, full-width MXU lanes on the
        # attention contractions. Full activation recompute bounds live
        # activations to one layer's worth (round-1 bench OOMed without it).
        if model_size == "1.3b":
            # BASELINE config 2 scale on ONE chip: bf16 FIRST moment
            # (v must stay fp32 — 1-beta2 is below the bf16 ulp and the
            # stored v would freeze) + batch 4; fp32 moments alone were
            # the r2 OOM (10.4GB)
            import jax.numpy as jnp

            cfg = llama_config("1b3", dtype="bfloat16",
                               max_position_embeddings=2048,
                               recompute="full")
            batch, seq, steps = 4, 2048, 20
            moment_dtype = jnp.bfloat16
        else:
            cfg = llama_config("350m", dtype="bfloat16",
                               num_attention_heads=8, num_key_value_heads=8,
                               max_position_embeddings=2048,
                               recompute="full")
            # >= 50 steps: the r4 record was a 10-step snapshot; a
            # steady-state window (~20 s at 400 ms/step) makes the
            # tokens/s and MFU numbers robust to warmup/dispatch noise
            batch, seq, steps = 8, 2048, 50
        # the peak table keyed by exact device_kind (device/peaks.py —
        # the same denominator the serving ledger's MFU uses); an
        # unlisted TPU raises there
        from paddle_tpu.device import peaks as _peaks

        peak = _peaks.peaks()["peak_flops"]
    else:
        cfg = llama_config("tiny")
        batch, seq, steps = 4, 128, 3
        peak = None  # no MFU off the TPU

    model = LlamaForCausalLM(cfg)
    params = {k: p.value for k, p in model.named_parameters()}
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    # scan-over-layers functional form: the decoder layer compiles ONCE
    # regardless of depth
    stacked, rest = stack_params(params, cfg)
    del model, params  # the stacked copy trains; 2.4 GB of HBM at 1.3b
    # BENCH_REMAT (full|attn_out|none) / BENCH_SCAN_UNROLL: the exp_dots
    # E1/E5 levers, env-switchable so a TPU session can A/B the full
    # bench without code edits; defaults match the recorded baseline
    remat = os.environ.get("BENCH_REMAT", "full")  # _remat_policy vocab
    step, init = build_train_step(
        cfg, lr=1e-4, remat=remat, moment_dtype=moment_dtype,
        scan_unroll=int(os.environ.get("BENCH_SCAN_UNROLL", "1")))
    opt_state = init(stacked, rest)

    # ONE dispatch for the whole timed loop (lax.fori_loop inside jit), so
    # per-step dispatch is not in the measurement; the timed region ends
    # on a scalar host readback.
    def multi_step(stacked, rest, st, ids, labels, n):
        import jax.numpy as jnp

        def body(_, carry):
            stacked, rest, st, _ = carry
            stacked, rest, st, loss = step(stacked, rest, st, ids, labels)
            return stacked, rest, st, loss.astype(jnp.float32)

        return jax.lax.fori_loop(0, n, body,
                                 (stacked, rest, st,
                                  jnp.zeros((), jnp.float32)))

    jitted = jax.jit(multi_step, static_argnums=(5,),
                     donate_argnums=(0, 1, 2))

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)

    # warmup / compile with the SAME static n as the timed call
    stacked, rest, opt_state, loss = jitted(stacked, rest, opt_state, ids,
                                            labels, steps)
    _ = float(loss)  # host readback barrier

    t0 = time.perf_counter()
    stacked, rest, opt_state, loss = jitted(stacked, rest, opt_state, ids,
                                            labels, steps)
    loss_val = float(loss)  # host readback barrier
    dt = time.perf_counter() - t0

    tokens = batch * seq * steps
    tps = tokens / dt
    rec = {
        "metric": f"llama_{model_size if on_tpu else 'tiny'}"
                  "_train_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
        "params": n_params,
        "platform": platform,
        "final_loss": loss_val,
        "steps": steps,
        "batch": batch,
        "seq": seq,
    }
    if on_tpu:
        model_flops = 6.0 * n_params * tokens  # fwd+bwd ≈ 6·N per token
        mfu = model_flops / dt / peak
        rec["mfu"] = round(mfu, 4)
        rec["vs_baseline"] = round(mfu / 0.50, 4)
    # which flash sub-lane plan this config's head_dim rides: a silent
    # fp32 upcast at hd<128 would not be the same benchmark
    import jax.numpy as jnp

    from paddle_tpu.ops.flash_attention_kernel import _sublane_plan

    hd = cfg.hidden_size // cfg.num_attention_heads
    smode, dpad = _sublane_plan(
        hd, jnp.bfloat16 if on_tpu else jnp.float32, not on_tpu)
    rec["flash_sublane"] = {"head_dim": hd, "mode": smode or "native",
                            "dpad": dpad}
    # provenance header: which machine/backend/rev produced this number —
    # tools/bench_diff.py warns when two compared rounds' env headers
    # disagree (cross-machine MFU is not a comparison)
    from paddle_tpu.monitor.provenance import env_stamp

    rec["env"] = env_stamp()
    _emit(rec)


def spec_bench():
    """Speculative-decode measurement: tokens emitted per model forward
    (lossless n-gram lookup, greedy) and wall tokens/s vs the plain
    decode loop on the same prompt. Run: python bench.py spec.

    The reference has no speculative path; on TPU decode is HBM-bound,
    so tokens_per_forward approximates the end-to-end speedup on
    accepting inputs. A code-like self-repetitive prompt is used — the
    accepting case this path exists for — alongside a random prompt as
    the adversarial floor (ratio ~1)."""
    platform = _platform()
    on_tpu = platform == "tpu"

    from paddle_tpu.inference.generation import (CausalLMEngine,
                                                 GenerationConfig)
    from paddle_tpu.models import LlamaForCausalLM, llama_config

    if on_tpu:
        cfg = llama_config("350m", dtype="bfloat16", num_attention_heads=8,
                           num_key_value_heads=8)
        prompt_unit, reps, new, max_len, k = 16, 16, 256, 1024, 8
    else:
        cfg = llama_config("tiny")
        prompt_unit, reps, new, max_len, k = 4, 8, 32, 256, 6
    model = LlamaForCausalLM(cfg)
    model.eval()
    eng = CausalLMEngine(model, max_batch=1, max_len=max_len)
    rng = np.random.RandomState(0)
    unit = rng.randint(0, cfg.vocab_size, (prompt_unit,))
    rep_prompt = np.tile(unit, reps)[None].astype(np.int32)
    gc = GenerationConfig(max_new_tokens=new, do_sample=False,
                          eos_token_id=None)
    # warm both paths (compiles), then time one run each
    ref = eng.generate(rep_prompt, gc)
    spec = eng.generate_speculative(rep_prompt, gc, draft_k=k)
    exact = bool(np.array_equal(ref, spec))
    t0 = time.perf_counter()
    eng.generate(rep_prompt, gc)
    t_plain = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.generate_speculative(rep_prompt, gc, draft_k=k)
    t_spec = time.perf_counter() - t0
    tpf_rep = eng.last_spec_stats["tokens_per_forward"]
    rand_prompt = rng.randint(0, cfg.vocab_size,
                              (1, prompt_unit * reps)).astype(np.int32)
    # same shapes/draft_k as the repetitive leg: already compiled, and
    # tokens_per_forward is deterministic — one run suffices
    eng.generate_speculative(rand_prompt, gc, draft_k=k)
    tpf_rand = eng.last_spec_stats["tokens_per_forward"]
    _emit({
        "metric": "speculative_tokens_per_forward"
                  + ("" if on_tpu else "_tiny"),
        "value": round(tpf_rep, 3), "unit": "tokens/forward (repetitive)",
        "vs_baseline": round(tpf_rep, 3),   # plain decode is 1.0
        "tokens_per_forward_random": round(tpf_rand, 3),
        "exact_match_vs_generate": exact,
        "wall_speedup_repetitive": round(t_plain / max(t_spec, 1e-9), 3),
        "platform": platform})


def decode_bench():
    """BASELINE config 5: decode throughput over the KV-cache engine
    (reference fused_multi_transformer decode loop). Run: python bench.py
    decode. Prints one JSON line with tokens/s across the decode scan."""
    import jax

    import numpy as np

    platform = _platform()
    on_tpu = platform == "tpu"

    from paddle_tpu.inference.generation import (CausalLMEngine,
                                                 GenerationConfig)
    from paddle_tpu.models import LlamaForCausalLM, llama_config

    if on_tpu:
        cfg = llama_config("350m", dtype="bfloat16", num_attention_heads=8,
                           num_key_value_heads=8)
        batch, prompt, new = 8, 128, 256
        max_len = 512
    else:
        cfg = llama_config("tiny")
        batch, prompt, new = 2, 16, 16
        max_len = 64
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = sum(int(np.prod(p.shape)) for _, p in model.named_parameters())
    eng = CausalLMEngine(model, max_batch=batch, max_len=max_len)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, prompt)).astype(np.int32)
    gc = GenerationConfig(max_new_tokens=new)
    out = eng.generate(ids, gc)          # warm/compile
    t0 = time.perf_counter()
    out = eng.generate(ids, gc)
    dt = time.perf_counter() - t0
    toks = batch * new
    rec = {
        "metric": "llama_350m_decode_tokens_per_sec" if on_tpu
        else "llama_tiny_decode_tokens_per_sec",
        "value": round(toks / dt, 1),
        "unit": "tokens/s",
        "vs_baseline": 0.0,  # no published reference decode number
        "params": n_params,
        "batch": batch,
        "platform": platform,
    }
    _emit(rec)


def resnet_bench():
    """BASELINE config 1: ResNet-50 single-device training imgs/sec.
    Run: python bench.py resnet."""
    import jax
    import jax.numpy as jnp

    platform = _platform()
    on_tpu = platform == "tpu"

    import paddle_tpu as paddle
    from paddle_tpu.nn.functional_call import functional_call
    from paddle_tpu.optimizer.functional import adamw_init, adamw_update
    from paddle_tpu.vision.models import resnet18, resnet50

    if on_tpu:
        model = resnet50()
        batch, steps, hw = 64, 10, 224
    else:
        model = resnet18()
        batch, steps, hw = 2, 2, 32
    model.train()
    params = {k: p.value for k, p in model.named_parameters()}
    n_params = sum(int(np.prod(v.shape)) for v in params.values())

    def loss_fn(pv, x, y):
        out = functional_call(model, pv, paddle.Tensor(x))
        out = out.value if hasattr(out, "value") else out
        logp = jax.nn.log_softmax(out.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))

    # ONE dispatch for the whole timed loop (same pattern as the llama
    # bench): per-call dispatch and the ~38MB image upload stay out of the
    # measurement
    def multi_step(pv, st, x, y, n):
        def body(_, carry):
            pv, st, _ = carry
            loss, g = jax.value_and_grad(loss_fn)(pv, x, y)
            st, pv = adamw_update(g, st, pv, lr=1e-3)
            return pv, st, loss.astype(jnp.float32)

        return jax.lax.fori_loop(0, n, body,
                                 (pv, st, jnp.zeros((), jnp.float32)))

    jitted = jax.jit(multi_step, static_argnums=(4,), donate_argnums=(0, 1))
    st = adamw_init(params)
    rng = np.random.RandomState(0)
    x = rng.randn(batch, 3, hw, hw).astype(np.float32)
    y = rng.randint(0, 1000, (batch,)).astype(np.int32)
    params, st, loss = jitted(params, st, x, y, steps)
    _ = float(loss)
    t0 = time.perf_counter()
    params, st, loss = jitted(params, st, x, y, steps)
    lv = float(loss)
    dt = time.perf_counter() - t0
    _emit({
        "metric": "resnet50_train_imgs_per_sec" if on_tpu
        else "resnet18_train_imgs_per_sec",
        "value": round(batch * steps / dt, 1), "unit": "imgs/s",
        "vs_baseline": 0.0,  # reference publishes no number (BASELINE.md)
        "params": n_params, "platform": platform, "final_loss": lv})


def moe_bench():
    """BASELINE config 4: MoE expert-parallel dispatch throughput.
    Run: python bench.py moe."""
    import jax
    import jax.numpy as jnp

    platform = _platform()
    on_tpu = platform == "tpu"

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.incubate.distributed.models.moe import MoELayer

    d_model, d_hidden = (1024, 4096) if on_tpu else (32, 64)
    n_expert = 8
    b, s = (8, 1024) if on_tpu else (2, 16)
    experts = [nn.Sequential(nn.Linear(d_model, d_hidden), nn.GELU(),
                             nn.Linear(d_hidden, d_model))
               for _ in range(n_expert)]
    layer = MoELayer(d_model=d_model, experts=experts,
                     gate={"type": "gshard", "top_k": 2})
    x = jax.device_put(
        np.random.RandomState(0).randn(b, s, d_model).astype(np.float32))

    from paddle_tpu.nn.functional_call import functional_call

    params = {k: p.value for k, p in layer.named_parameters()}

    def fwd(pv, xv):
        out = functional_call(layer, pv, paddle.Tensor(xv))
        return jnp.sum((out.value if hasattr(out, "value") else out)
                       .astype(jnp.float32))

    def multi(pv, xv, n):
        # chain iterations through the input (tiny nonzero perturbation)
        # so XLA cannot hoist the loop-invariant forward out of the loop
        def body(_, carry):
            acc, xv = carry
            s = fwd(pv, xv)
            return s, xv + (s * 1e-30).astype(xv.dtype)

        acc, _ = jax.lax.fori_loop(
            0, n, body, (jnp.zeros((), jnp.float32), xv))
        return acc

    jitted = jax.jit(multi, static_argnums=(2,))
    steps = 10 if on_tpu else 2
    _ = float(jitted(params, x, steps))  # compile + warm
    t0 = time.perf_counter()
    _ = float(jitted(params, x, steps))  # one dispatch, readback barrier
    dt = time.perf_counter() - t0
    toks = b * s * steps
    _emit({
        "metric": "moe_gshard_fwd_tokens_per_sec", "value": round(toks / dt, 1),
        "unit": "tokens/s", "vs_baseline": 0.0, "n_expert": n_expert,
        "platform": platform})


def vit_bench():
    """BASELINE config 5: ViT-Huge fused-transformer INFERENCE imgs/sec.
    Encoder = patch-embed conv + scan-over-layers pre-LN transformer with
    the framework's flash kernel (non-causal), mean-pool head — the
    fused_multi_transformer inference path at encoder shapes.
    Run: python bench.py vit."""
    import jax
    import jax.numpy as jnp

    platform = _platform()
    on_tpu = platform == "tpu"

    if on_tpu:
        # ViT-H/14 at 224: hidden 1280, 32 layers, 16 heads, mlp 5120;
        # mean-pool (no cls token) keeps 256 tokens — flash-block friendly
        H, L, NH, MLP, P_, IMG, B = 1280, 32, 16, 5120, 14, 224, 32
        dt = jnp.bfloat16
    else:
        H, L, NH, MLP, P_, IMG, B = 64, 2, 4, 128, 16, 64, 2
        dt = jnp.float32
    S = (IMG // P_) ** 2
    hd = H // NH
    rng = np.random.RandomState(0)

    def mk(*s):
        return jnp.asarray(rng.randn(*s).astype(np.float32) * 0.02, dt)

    params = {
        "patch": mk(P_, P_, 3, H), "pos": mk(1, S, H),
        "ln1": jnp.ones((L, H), dt), "qkv": mk(L, H, 3 * H),
        "proj": mk(L, H, H), "ln2": jnp.ones((L, H), dt),
        "fc1": mk(L, H, MLP), "fc2": mk(L, MLP, H),
        "head": mk(H, 1000),
    }

    def ln(x, w):
        xf = x.astype(jnp.float32)
        y = (xf - xf.mean(-1, keepdims=True)) * jax.lax.rsqrt(
            xf.var(-1, keepdims=True) + 1e-6)
        return (y * w.astype(jnp.float32)).astype(x.dtype)

    def encoder_layer(x, lp):
        from paddle_tpu.ops.pallas import flash_attention

        b, s, _ = x.shape
        xn = ln(x, lp["ln1"])
        qkv = (xn @ lp["qkv"]).reshape(b, s, 3, NH, hd)
        ctx = flash_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                              causal=False)
        x = x + ctx.reshape(b, s, H) @ lp["proj"]
        xn = ln(x, lp["ln2"])
        return x + jax.nn.gelu(xn @ lp["fc1"]) @ lp["fc2"]

    def fwd(pv, img):
        x = jax.lax.conv_general_dilated(
            img, pv["patch"], (P_, P_), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = x.reshape(img.shape[0], S, H) + pv["pos"]
        x, _ = jax.lax.scan(
            lambda c, lp: (encoder_layer(c, lp), None), x,
            {k: pv[k] for k in ("ln1", "qkv", "proj", "ln2", "fc1", "fc2")})
        return jnp.mean(x.astype(jnp.float32), axis=1) @ pv[
            "head"].astype(jnp.float32)

    def multi(pv, img, n):
        def body(_, carry):
            acc, img = carry
            out = fwd(pv, img)
            s = jnp.sum(out) * 1e-30
            return acc + jnp.sum(out), img + s.astype(img.dtype)

        acc, _ = jax.lax.fori_loop(0, n, body,
                                   (jnp.zeros((), jnp.float32), img))
        return acc

    jitted = jax.jit(multi, static_argnums=(2,))
    img = jnp.asarray(rng.randn(B, IMG, IMG, 3).astype(np.float32), dt)
    steps = 10 if on_tpu else 2
    _ = float(jitted(params, img, steps))          # compile + warm
    t0 = time.perf_counter()
    _ = float(jitted(params, img, steps))          # one dispatch
    dt_s = time.perf_counter() - t0
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    _emit({
        "metric": "vit_h_infer_imgs_per_sec" if on_tpu
        else "vit_tiny_infer_imgs_per_sec",
        "value": round(B * steps / dt_s, 1), "unit": "imgs/s",
        "vs_baseline": 0.0,  # reference publishes no number (BASELINE.md)
        "params": n_params, "platform": platform})


def hybrid_bench():
    """BASELINE config 3 (Llama-2 13B/65B hybrid TP x PP x sharding):
    COMPILE-ONLY per-device memory feasibility at real dims over virtual
    device meshes — the at-scale proof that stage-local PP + ZeRO
    placement fits a v5p HBM budget, with no hardware needed.

    CPU-only by construction: each config runs in a child that pins the
    CPU backend with that many virtual devices (the count must be fixed
    before jax initializes), and this parent never touches JAX — no
    process here wants a chip. Writes MEMORY_CONFIG3.json and prints the
    one-line summary record (a count of compiles that fit, no rate)."""
    import subprocess

    configs = [
        # (preset, ndev, axes dict, stash, seq, M, budget GiB, zero_stage)
        ("13b", 8, dict(pp=2, mp=2, sharding=2), "input", 4096, 8, 95, 2),
        ("13b", 8, dict(pp=2, mp=2, sharding=2), "residuals", 4096, 8, 95,
         2),
        ("65b", 64, dict(pp=8, mp=4, sharding=2), "input", 4096, 16, 95,
         2),
        ("65b", 64, dict(pp=8, mp=4, sharding=2), "residuals", 4096, 16,
         95, 2),
        # BASELINE config 3 names sharding-stage-3 explicitly
        ("65b", 64, dict(pp=8, mp=4, sharding=2), "residuals", 4096, 16,
         95, 3),
    ]
    runner = r'''
import sys, os, json, time
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count="
                           + sys.argv[2])
import jax
jax.config.update("jax_platforms", "cpu")
from paddle_tpu.distributed.topology import build_mesh, set_mesh
from paddle_tpu.models.llama import llama_config
from paddle_tpu.models.llama_pp import hybrid_memory_analysis

spec = json.loads(sys.argv[1])
cfg = llama_config(spec["preset"])
mesh = build_mesh(**spec["axes"])
set_mesh(mesh)
t0 = time.time()
rep = hybrid_memory_analysis(
    cfg, mesh, accumulate_steps=spec["M"], seq_len=spec["seq"],
    remat=(spec["stash"] == "input"), stash=spec["stash"],
    hbm_budget=spec["budget_gib"] << 30,
    zero_stage=spec.get("zero_stage", 2))
rep["compile_secs"] = round(time.time() - t0, 1)
print("HYBRID_REPORT " + json.dumps(rep))
'''
    reports = []
    for preset, ndev, axes, stash, seq, M, budget, zstage in configs:
        spec = json.dumps({"preset": preset, "axes": axes, "stash": stash,
                           "seq": seq, "M": M, "budget_gib": budget,
                           "zero_stage": zstage})
        try:
            proc = subprocess.run(
                [sys.executable, "-c", runner, spec, str(ndev)],
                capture_output=True, text=True, timeout=1800,
                cwd=os.path.dirname(os.path.abspath(__file__)))
            line = next((ln for ln in proc.stdout.splitlines()
                         if ln.startswith("HYBRID_REPORT ")), None)
            if line:
                reports.append(json.loads(line[len("HYBRID_REPORT "):]))
            else:
                reports.append({
                    "model": preset, "stash": stash, "error":
                    (proc.stderr.strip().splitlines() or ["no output"])
                    [-1][:200]})
        except subprocess.TimeoutExpired:
            reports.append({"model": preset, "stash": stash,
                            "error": "compile timeout 1800s"})
        print(json.dumps({"progress": reports[-1]}), file=sys.stderr)
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "MEMORY_CONFIG3.json")
    with open(out, "w") as f:
        json.dump(reports, f, indent=1)
    fits = [r for r in reports if r.get("fits")]
    print(json.dumps({
        "metric": "config3_memory_fits",
        "value": len(fits), "unit": f"of {len(reports)} configs",
        "platform": "cpu",
        "detail": [{"model": r.get("model"), "stash": r.get("stash"),
                    "peak_gib": r.get("peak_gib"),
                    "fits": r.get("fits", False)} for r in reports]}))


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "train"
    if mode == "decode":
        decode_bench()
    elif mode == "spec":
        spec_bench()
    elif mode == "resnet":
        resnet_bench()
    elif mode == "moe":
        moe_bench()
    elif mode == "vit":
        vit_bench()
    elif mode == "hybrid":
        hybrid_bench()
    elif mode == "train":
        main(sys.argv[2] if len(sys.argv) > 2 else "350m")
    elif mode == "1.3b":
        main("1.3b")
    else:
        raise SystemExit(
            f"unknown bench mode {mode!r} "
            "(train|decode|spec|resnet|moe|vit|1.3b|hybrid)")
