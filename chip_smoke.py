"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            one TPU chip: train + serve
    python3 chip_smoke.py --chips 4  four chips: tensor-parallel serving and
                                     the hybrid train step, nothing else

One process, run from the checkout root, no network, no git. It drives the
main paths once through the entry points a user would call, at the full
width of a model the repo supports (depth cut, random weights from a
seed), checks what comes out by the repo's own means, and FAILS — non-zero
exit, no ``"ok": true`` — unless JAX's first device is a TPU. No phase's
exception is caught. It prints no rate and no utilisation: it is a
bring-up check, not a benchmark.

Default phases:

- **train** — ``models.llama_functional.build_train_step`` as ``bench.py``
  drives it: preset ``1b3`` (hidden 2048, 16 heads x 128, FFN 5504, vocab
  32000), bf16, sequence 2048, batch 4, remat ``full``, bf16 first moment.
  A few steps on one repeated batch, each ended by a host read of the
  loss: finite, and lower at the last step than at the first.
- **serve** — ``LlamaForCausalLM`` at preset ``7b`` width (hidden 4096, 32
  heads x 128, FFN 11008, vocab 32000) in ``PagedContinuousBatchingEngine``
  -> ``serving.Server`` -> ``serving.serve_http``, and ``POST /generate``
  requests over the loopback from threads of this process: mixed prompt
  lengths, several in flight together, one streamed. Every request
  completes; the first one's greedy tokens are held to ``CausalLMEngine``
  on the same weights; an identical second round compiles nothing; then
  two requests on ``kv_dtype="int8"`` pools, which only have to complete.
- **the kernel is really there** — each program's lowered text is searched
  for the Pallas kernels (``tpu_custom_call``) that the code's own routing
  puts in it; ``KERNELS`` below writes that routing down once.

Every line printed is one JSON object; the last is the contract's
``{"ok": true, "device": {...}}`` and nothing else.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# Which Pallas kernels each program must hold ON A TPU, read from the
# routing and written down once (kernel function names, as the lowered
# text's ``kernel_name`` carries them):
#
# - train step (models/llama_functional._layer_fwd): apply_rotary_emb with
#   shared tables -> ops/pallas_kernels.fused_rope (the VJP reuses the
#   kernel); ops/pallas.flash_attention at block-divisible lengths ->
#   flash forward and both backward kernels. Its RMS norm is plain jnp.
# - prefill (LlamaAttention.forward_with_cache, pos == 0): RMSNorm layer
#   -> rms_norm; fused_rope; flash forward where the bucket is a multiple
#   of 128 — a narrower bucket has no usable flash block and takes the
#   chunked XLA recurrence BY DESIGN (ops/flash_attention_kernel.supports),
#   as does every traced-offset prefill (prefix_chunk_attention).
# - decode segment (forward_decode_paged): rms_norm; the paged decode
#   kernel, bf16 and int8 pools alike. Rope there is per-row, in jnp.
# - the reference engine's decode (forward_with_cache, S == 1, MHA):
#   rms_norm; fused_rope (one shared-table row); decode_mha.
# - tensor-parallel serving (tp_*): Mosaic kernels cannot be partitioned
#   automatically, so under the engine's mesh only the attention kernels
#   run — the ones the ops wrap in shard_map (tp=) — and rms_norm / rope
#   take the XLA composition (ops/pallas._kernel_routable).
KERNELS = {
    "train_step": {"_rope_kernel", "_fwd_kernel", "_bwd_dq_kernel",
                   "_bwd_dkv_kernel"},
    "prefill_wide": {"_rms_kernel", "_rope_kernel", "_fwd_kernel"},
    "prefill_narrow": {"_rms_kernel", "_rope_kernel"},
    "decode_segment": {"_rms_kernel", "paged_decode"},
    "reference_decode": {"_rms_kernel", "_rope_kernel", "_decode_kernel"},
    "tp_prefill_wide": {"_fwd_kernel"},
    "tp_prefill_narrow": set(),
    "tp_decode_segment": {"paged_decode"},
}

# A greedy bf16 run that meets a near-tie between two logits diverges for
# good, so agreement with the reference is the length of the common
# prefix. A broken kernel ends it at the first decode step; the floor asks
# for the prefill token and seven decode steps. (Seen on the chip: 20 of
# 32.) Tensor parallelism rounds each row-parallel partial sum to bf16
# before the all-reduce, so tp=4 against tp=1 drifts sooner (3, 19 and 32
# of 32 seen): there the bf16 floor is the prefill token, and the same
# comparison in float32, where the two differ only in summation order,
# must agree on every token.
AGREE_FLOOR = 8


def say(**kv) -> None:
    print(json.dumps(kv), flush=True)


def kernels_in(lowered) -> dict:
    """Pallas kernels in a lowered program: name -> count."""
    text = lowered.as_text()
    names = re.findall(r'kernel_name = "([^"]+)"', text)
    if len(names) != text.count("@tpu_custom_call"):
        raise RuntimeError(
            f"{text.count('@tpu_custom_call')} tpu_custom_call sites but "
            f"{len(names)} kernel names in the lowered text")
    return {n: names.count(n) for n in sorted(set(names))}


def check_kernels(program: str, routing: str, lowered, enforce: bool) -> dict:
    """Report the kernels of one program; with ``enforce`` fail where one
    that ``KERNELS[routing]`` lists is missing."""
    found = kernels_in(lowered)
    missing = sorted(KERNELS[routing] - set(found))
    say(phase="kernels", program=program, routing=routing,
        tpu_custom_calls=sum(found.values()), found=found,
        missing=missing, enforced=enforce)
    if enforce and missing:
        raise RuntimeError(
            f"{program}: the routing puts {missing} in this program and "
            f"the lowered text holds only {sorted(found)}")
    return found


def memory(dev) -> dict:
    ms = dev.memory_stats() or {}
    return {k: ms.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


# -- train -------------------------------------------------------------------
def train_phase(preset="1b3", layers=None, batch=4, seq=2048, steps=4,
                dtype="bfloat16", kernels=True, seed=0) -> dict:
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM, llama_config
    from paddle_tpu.models.llama_functional import (build_train_step,
                                                    stack_params)
    from paddle_tpu.ops.flash_attention_kernel import _sublane_plan

    over = {} if layers is None else {"num_hidden_layers": layers}
    cfg = llama_config(preset, dtype=dtype, max_position_embeddings=seq,
                       recompute="full", **over)
    full_depth = llama_config(preset).num_hidden_layers
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    params = {k: p.value for k, p in model.named_parameters()}
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    stacked, rest = stack_params(params, cfg)
    del model, params   # the stacked copy is the one that trains
    step, init = build_train_step(
        cfg, lr=1e-4, remat="full",
        moment_dtype=jnp.bfloat16 if dtype == "bfloat16" else None)
    opt_state = init(stacked, rest)

    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)

    jitted = jax.jit(step, donate_argnums=(0, 1, 2))
    lowered = jitted.lower(stacked, rest, opt_state, ids, labels)
    found = check_kernels("train_step", "train_step", lowered, kernels)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0

    losses = []
    for _ in range(steps):
        stacked, rest, opt_state, loss = compiled(stacked, rest, opt_state,
                                                  ids, labels)
        losses.append(float(loss))   # host read ends the step
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"train: loss not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(
            f"train: loss did not fall on a repeated batch: {losses}")
    mode, dpad = _sublane_plan(cfg.head_dim, jnp.dtype(dtype), False)
    out = {"phase": "train", "preset": preset, "hidden": cfg.hidden_size,
           "heads": cfg.num_attention_heads, "head_dim": cfg.head_dim,
           "ffn": cfg.intermediate_size, "vocab": cfg.vocab_size,
           "layers": cfg.num_hidden_layers, "layers_of": full_depth,
           "params": n_params, "dtype": dtype, "batch": batch, "seq": seq,
           "remat": "full", "steps": steps, "losses": losses,
           "compile_s": round(compile_s, 2), "kernels": found,
           "flash_sublane": {"mode": mode or "native", "dpad": dpad},
           "memory": memory(jax.devices()[0])}
    say(**out)
    return out


# -- serve -------------------------------------------------------------------
def _post(url: str, body: dict, timeout: float = 900.0):
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        if not body.get("stream"):
            return json.load(r)["tokens"]
        toks, done = [], None
        for line in r:
            rec = json.loads(line)
            if "token" in rec:
                toks.append(rec["token"])
            else:
                done = rec
        if not done or done.get("status") != "finished":
            raise RuntimeError(f"stream ended without finishing: {done}")
        return toks


def _round(url: str, prompts, new_tokens, stream_idx=1):
    """All requests at once from threads of this process; returns their
    generated tokens in request order. A failed request fails the run
    (``map`` re-raises its exception here)."""
    def one(i):
        return _post(url, {"prompt": [int(t) for t in prompts[i]],
                           "max_new_tokens": int(new_tokens[i]),
                           "stream": i == stream_idx})

    with ThreadPoolExecutor(len(prompts)) as pool:
        out = list(pool.map(one, range(len(prompts))))
    for i, toks in enumerate(out):
        if len(toks) != new_tokens[i]:
            raise RuntimeError(
                f"request {i}: {len(toks)} tokens, asked {new_tokens[i]}")
    return out


def _serving(engine, segment_steps, warmup):
    """engine -> Server -> HTTP front; returns (server, httpd, url)."""
    from paddle_tpu import serving

    srv = serving.Server(engine, max_queue=16, segment_steps=segment_steps,
                         warmup=warmup)
    srv.wait_ready()
    if srv.status != "ok":
        raise RuntimeError(f"server is {srv.status!r} after start-up")
    httpd = serving.serve_http(srv)
    return srv, httpd, f"http://127.0.0.1:{httpd.server_address[1]}"


def _stop(srv, httpd, engine) -> None:
    httpd.shutdown()
    httpd.server_close()
    srv.shutdown(drain=True, timeout=60.0)
    engine.close()


def _prompts(lens, vocab, seed):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, (n,)).astype(np.int32) for n in lens]


def _compile_seconds(monitor) -> float:
    m = monitor.snapshot().get("metrics", {}).get(
        "paddle_tpu_jit_compile_seconds_total") or {}
    return sum(rec["value"] for rec in m.get("samples", []))


def _check_serving_kernels(eng, widths, segment_steps, kernels, tag=""):
    """Lower (trace only, no compile) the engine's prefill programs at the
    bucket widths in use and its decode segment, with the arguments the
    engine itself passes, and look for the kernels."""
    tp = "tp_" if eng.tp_degree > 1 else ""
    pools, pt = eng.caches
    for w in widths:
        # the program of a cold admission: mini cache, prefill and page
        # install in one (it is only lowered: nothing is donated)
        lowered = eng._prefill_paged._jitted.lower(
            eng.params, np.zeros((1, w), np.int32), pools, pt,
            np.int32(0), np.int32(w), eng._bank(), np.int32(0))
        check_kernels(
            f"{tag}cb_prefill[{w}]",
            tp + ("prefill_wide" if w % 128 == 0 else "prefill_narrow"),
            lowered, kernels)
    lowered = eng._segment_fn(segment_steps)._jitted.lower(
        eng.params, eng.last, eng.lens, eng.done_dev, eng._active_mask(),
        eng.samp, eng._bank(), eng.caches, np.uint32(0), np.uint32(0))
    check_kernels(f"{tag}cb_segment[{segment_steps}]",
                  tp + "decode_segment", lowered, kernels)


def serve_phase(preset="7b", layers=16, dtype="bfloat16", max_batch=4,
                page_size=16, max_pages=64,
                prompt_lens=(40, 300, 900, 150),
                new_tokens=(32, 48, 64, 40), segment_steps=8,
                kernels=True, floor=AGREE_FLOOR, seed=0) -> dict:
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import monitor
    from paddle_tpu.inference.generation import (
        CausalLMEngine, GenerationConfig, PagedContinuousBatchingEngine,
        _bucket_for)
    from paddle_tpu.models import LlamaForCausalLM, llama_config

    monitor.enable()   # the jit miss counters need it
    cfg = llama_config(preset, dtype=dtype, num_hidden_layers=layers)
    full_depth = llama_config(preset).num_hidden_layers
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    prompts = _prompts(prompt_lens, cfg.vocab_size, seed)
    max_len = page_size * max_pages
    num_pages = max_batch * max_pages

    def engine(kv_dtype):
        return PagedContinuousBatchingEngine(
            model, max_batch=max_batch, num_pages=num_pages,
            page_size=page_size, max_pages=max_pages, kv_dtype=kv_dtype)

    # bf16 pools: warmed server, two identical rounds over HTTP
    eng = engine("bf16")
    widths = sorted({_bucket_for(eng.prefill_buckets, len(p))
                     for p in prompts})
    c0 = _compile_seconds(monitor)
    srv, httpd, url = _serving(eng, segment_steps, warmup=True)
    warm_s = _compile_seconds(monitor) - c0
    first = _round(url, prompts, new_tokens)
    before = monitor.jit_miss_by_fn()
    second = _round(url, prompts, new_tokens)
    new_compiles = {k: v - before.get(k, 0)
                    for k, v in monitor.jit_miss_by_fn().items()
                    if v != before.get(k, 0)}
    if new_compiles:
        raise RuntimeError(
            f"serve: the second identical round compiled {new_compiles}")
    if second != first:
        raise RuntimeError("serve: the second identical round gave other "
                           "greedy tokens than the first")
    mem_serving = memory(jax.devices()[0])
    # after the rounds: lowering first would fill jit's trace cache and
    # hide these programs' compiles from the miss counters
    _check_serving_kernels(eng, widths, segment_steps, kernels)
    _stop(srv, httpd, eng)

    # the repo's reference engine on the same weights, first request
    ref_eng = CausalLMEngine(model, max_batch=1, max_len=max_len)
    ref = ref_eng.generate(
        prompts[0][None], GenerationConfig(max_new_tokens=new_tokens[0]))
    ref = [int(t) for t in ref[0, len(prompts[0]):]]
    agree = next((i for i, (a, b) in enumerate(zip(first[0], ref))
                  if a != b), len(ref))
    if agree < min(floor, len(ref)):
        raise RuntimeError(
            f"serve: request 0 agrees with CausalLMEngine on {agree} "
            f"leading tokens of {len(ref)}, floor {floor}: "
            f"{first[0]} vs {ref}")
    w0 = _bucket_for(ref_eng.prefill_buckets, len(prompts[0]))
    decode = ref_eng._decode_fn(
        new_tokens[0] - 1,
        GenerationConfig(max_new_tokens=new_tokens[0]))._jitted.lower(
            ref_eng.params, jnp.zeros((1,), jnp.int32),
            model.init_cache(1, max_len), jnp.int32(w0),
            jax.random.PRNGKey(0))
    check_kernels("lm_decode", "reference_decode", decode, kernels)

    # int8 pools on the same model: two requests that have to complete
    eng8 = engine("int8")
    srv8, httpd8, url8 = _serving(eng8, segment_steps, warmup=False)
    int8_tokens = _round(url8, prompts[:2], new_tokens[:2])
    _check_serving_kernels(eng8, [], segment_steps, kernels, tag="int8 ")
    _stop(srv8, httpd8, eng8)

    out = {"phase": "serve", "preset": preset, "hidden": cfg.hidden_size,
           "heads": cfg.num_attention_heads, "head_dim": cfg.head_dim,
           "ffn": cfg.intermediate_size, "vocab": cfg.vocab_size,
           "layers": layers, "layers_of": full_depth, "dtype": dtype,
           "max_batch": max_batch, "page_size": page_size,
           "num_pages": num_pages, "max_len": max_len,
           "prompt_lens": list(prompt_lens),
           "new_tokens": list(new_tokens), "prefill_buckets": widths,
           "requests_completed": 2 * len(prompts),
           "streamed": 1, "in_flight_together": len(prompts),
           "warmup_compile_s": round(warm_s, 2),
           "second_round_new_compiles": 0,
           "reference": "CausalLMEngine.generate",
           "agree_leading_tokens": agree, "of": len(ref), "floor": floor,
           "int8_requests_completed": len(int8_tokens),
           "memory_while_serving": mem_serving,
           "memory": memory(jax.devices()[0])}
    say(**out)
    return out


# -- four chips --------------------------------------------------------------
def _spread(name: str, arrays, devices) -> None:
    """Fail unless every array is laid out over all of ``devices`` with
    each holding a proper share (not device 0 alone, not full copies)."""
    for a in arrays:
        held = {s.device for s in a.addressable_shards}
        share = max(s.data.nbytes for s in a.addressable_shards)
        if held != set(devices) or share * len(devices) != a.nbytes:
            raise RuntimeError(
                f"{name}: array {a.shape} sits on {sorted(d.id for d in held)}"
                f" with {share} of {a.nbytes} bytes on one device")


def tp_serve_phase(preset="7b", layers=8, dtype="bfloat16", tp=4,
                   max_batch=4, page_size=16, max_pages=64,
                   prompt_lens=(40, 300, 900), new_tokens=(32, 32, 32),
                   segment_steps=8, kernels=True, floor=AGREE_FLOOR,
                   one_chip="tpu:0", seed=0) -> dict:
    """The paged server with ``tp_degree=tp`` over the first ``tp``
    devices and, on ``one_chip`` of them, ``tp_degree=1``: greedy tokens
    of the same requests compared. The model is built on the HOST, so no
    chip ever holds the unsharded weights beside its share."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.inference.generation import (
        PagedContinuousBatchingEngine, _bucket_for)
    from paddle_tpu.models import LlamaForCausalLM, llama_config

    cfg = llama_config(preset, dtype=dtype, num_hidden_layers=layers)
    devices = jax.devices()[:tp]
    paddle.seed(seed)
    with jax.default_device(jax.devices("cpu")[0]):
        model = LlamaForCausalLM(cfg)
    model.eval()
    prompts = _prompts(prompt_lens, cfg.vocab_size, seed)

    def engine(degree):
        return PagedContinuousBatchingEngine(
            model, max_batch=max_batch, num_pages=max_batch * max_pages,
            page_size=page_size, max_pages=max_pages, tp_degree=degree)

    eng = engine(tp)
    sharded = [v for k, v in eng.params.items() if "proj" in k]
    _spread("weights", sharded, devices)
    pools, _ = eng.caches
    _spread("kv pool", [a for entry in pools for a in entry], devices)
    srv, httpd, url = _serving(eng, segment_steps, warmup=False)
    tokens_tp = _round(url, prompts, new_tokens)
    per_device = [memory(d)["bytes_in_use"] for d in devices]
    if None not in per_device and min(per_device) * 4 < max(per_device):
        raise RuntimeError(
            f"tp serving: bytes in use are lopsided: {per_device}")
    widths = sorted({_bucket_for(eng.prefill_buckets, len(p))
                     for p in prompts})
    _check_serving_kernels(eng, widths, segment_steps, kernels,
                           tag=f"tp{tp} {dtype} ")
    _stop(srv, httpd, eng)
    del srv, httpd, eng, sharded, pools   # its shards go before tp=1 comes

    model.to(device=one_chip)   # now the unsharded model, on one chip
    eng1 = engine(1)
    srv1, httpd1, url1 = _serving(eng1, segment_steps, warmup=False)
    tokens_1 = _round(url1, prompts, new_tokens)
    _stop(srv1, httpd1, eng1)

    agree = [next((i for i, (a, b) in enumerate(zip(x, y)) if a != b),
                  len(x)) for x, y in zip(tokens_tp, tokens_1)]
    if min(agree) < floor:
        raise RuntimeError(
            f"tp serving: tp={tp} and tp=1 agree on {agree} leading "
            f"tokens, floor {floor}")
    out = {"phase": "tp_serve", "preset": preset,
           "hidden": cfg.hidden_size, "heads": cfg.num_attention_heads,
           "layers": layers, "dtype": dtype, "tp_degree": tp,
           "devices": [d.id for d in devices],
           "prompt_lens": list(prompt_lens),
           "new_tokens": list(new_tokens),
           "requests_completed": len(prompts),
           "agree_leading_tokens_vs_tp1": agree, "floor": floor,
           "bytes_in_use_per_device_while_serving": per_device}
    say(**out)
    return out


def hybrid_phase(preset="1b3", layers=4, dtype="bfloat16", n=4, seq=2048,
                 rtol=2e-2, seed=0) -> dict:
    """One hybrid dp×mp×sharding train step (``__graft_entry__``'s, mesh
    from ``jax.devices()``) and the unsharded step on the same batch and
    weights; the two losses compared."""
    import jax

    import __graft_entry__ as graft
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM, llama_config

    cfg = llama_config(preset, dtype=dtype, num_hidden_layers=layers,
                       max_position_embeddings=seq)
    paddle.seed(seed)
    with jax.default_device(jax.devices("cpu")[0]):
        model = LlamaForCausalLM(cfg)
    axes = graft.hybrid_axes(n)
    batch = max(2, axes["dp"] * axes["sharding"])
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    loss_n, gnorm_n = graft.hybrid_train_step(model, ids, labels, n)
    # the step's arrays are gone once it returns; its peak stays (this
    # phase runs first, so the peak so far is the sharded step's)
    per_device = [memory(d)["peak_bytes_in_use"]
                  for d in jax.devices()[:n]]
    if None not in per_device and min(per_device) * 2 < max(per_device):
        raise RuntimeError(
            f"hybrid: peak bytes are lopsided: {per_device}")
    loss_1, gnorm_1 = graft.hybrid_train_step(model, ids, labels, 1)
    if not (np.isfinite(loss_n) and np.isfinite(loss_1)):
        raise RuntimeError(f"hybrid: loss not finite: {loss_n}, {loss_1}")
    if abs(loss_n - loss_1) > rtol * abs(loss_1):
        raise RuntimeError(
            f"hybrid: loss on {n} devices {loss_n} vs unsharded {loss_1} "
            f"differ by more than {rtol} relative")
    out = {"phase": "hybrid_train", "preset": preset,
           "hidden": cfg.hidden_size, "layers": layers, "dtype": dtype,
           "mesh": axes, "batch": batch, "seq": seq,
           "loss_sharded": loss_n, "loss_unsharded": loss_1,
           "grad_norm_sharded": gnorm_n, "grad_norm_unsharded": gnorm_1,
           "rtol": rtol,
           "peak_bytes_per_device_in_sharded_step": per_device}
    say(**out)
    return out


# -- entry -------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the tensor-parallel server and the hybrid "
                         "train step on four chips, and no other phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    set_vars = sorted(k for k in os.environ
                      if k.startswith("PADDLE_TPU_FLASH_SUBLANE"))
    if set_vars:
        raise SystemExit(f"unset {set_vars}: the variable is frozen into "
                         "compiled programs at trace time")

    from paddle_tpu.device.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found {device}")
    if len(devs) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs that many devices; "
                         f"JAX found {device}")
    say(phase="start", device=device, chips=args.chips, seed=args.seed,
        jax=jax.__version__,
        hbm_bytes_limit=(devs[0].memory_stats() or {}).get("bytes_limit"),
        compile_cache=cache_dir,
        compile_cache_entries=(len(os.listdir(cache_dir))
                               if os.path.isdir(cache_dir) else 0))

    t0 = time.perf_counter()
    if args.chips == 4:
        results = [hybrid_phase(seed=args.seed),
                   tp_serve_phase(dtype="float32", layers=4, floor=32,
                                  seed=args.seed),
                   tp_serve_phase(dtype="bfloat16", floor=1,
                                  seed=args.seed)]
    else:
        results = [train_phase(seed=args.seed), serve_phase(seed=args.seed)]
    say(phase="summary", phases=[r["phase"] for r in results],
        wall_s=round(time.perf_counter() - t0, 1), claim=None)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
