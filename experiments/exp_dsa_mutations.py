"""What the chip's check of ``serve-deepseek-v3.2-longctx`` can see: the
benchmark's model (``benchmark/lib/seeded_weights.py``), sound and with one
mechanism broken at a time, against the plain reference of the SOUND
model, at the published widths, on the chip.

    python experiments/exp_dsa_mutations.py [--seed N] [variant ...]
        --forward [--len 4096] [--prompts 2] [--o-gains 1,2] [--routed-also 1.0]
        --serve
        --run <variant> -- <benchmark/run.py's arguments>

``--forward``: one jitted prefill of a whole prompt a variant; every
position past ``index_topk`` is a token the model would serve next, and the
reference's float32 logits give the gap between their maximum and that
token's logit, as ``benchmark/run.py``'s ``check_served`` reads it: some
2,000 tokens a prompt, one compile a variant, any number of prompts and of
``o_proj`` gains without another. The sound variant also prints what share
of the residual stream each layer's attention output is.
``--serve``: a small engine (2 rows) serves two requests greedily (the
decode path: the kernel, the sort, the gather).
``--run``: the variant is patched in and ``benchmark/run.py`` itself runs
the cell: its own traffic, window and comparison.

``check.logit_margin`` has to lie above the sound runs' gaps and under the
mutations'. One JSON line a reading. Fails unless JAX's first device is a
TPU (``--mid``: narrow widths in bf16 on the CPU, for the control flow).
"""
import contextlib
import dataclasses
import json
import os
import sys
import time
from types import SimpleNamespace

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from benchmark.lib import seeded_weights  # noqa: E402
from benchmark.run import (build_config, load_json, load_module,  # noqa: E402
                           overlay, program_seed)
from paddle_tpu.core.autograd import apply_op, no_grad  # noqa: E402
from paddle_tpu.inference.generation import (  # noqa: E402
    GenerationConfig, PagedContinuousBatchingEngine)
from paddle_tpu.models import deepseek_v32 as ds  # noqa: E402
from paddle_tpu.models.llama import LlamaMLP  # noqa: E402
from paddle_tpu.nn.functional_call import substituted_state  # noqa: E402
from paddle_tpu.nn.layer import routed_experts as rx  # noqa: E402
from paddle_tpu.nn.layer.norm import RMSNorm  # noqa: E402

# narrow widths, the published routing (256 experts in 8 groups), bf16
MID = {"vocab_size": 1024, "hidden_size": 256, "intermediate_size": 512,
       "moe_intermediate_size": 64, "num_hidden_layers": 3,
       "num_attention_heads": 8, "q_lora_rank": 128, "kv_lora_rank": 64,
       "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
       "index_n_heads": 16, "index_head_dim": 32, "index_topk": 128,
       "dtype": "bfloat16"}


def say(**kv):
    print(json.dumps(kv), flush=True)


@contextlib.contextmanager
def patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def dense_scores(relu=True, by_position=False):
    """(decode kernel's stand-in, prefill's stand-in): the indexer's scores
    in plain jnp, with the relu left out or the position in their place."""
    def decode(q, w, k_pool, page_table, seq_lens, interpret=None):
        width = page_table.shape[1] * k_pool.shape[1]
        if by_position:
            return jnp.broadcast_to(-jnp.arange(width, dtype=jnp.float32),
                                    (q.shape[0], width))
        keys = k_pool[jnp.maximum(page_table, 0)].reshape(
            q.shape[0], width, -1)
        s = jnp.einsum("bhd,bud->bhu", q, keys,
                       preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) if relu else s
        return jnp.sum(s * w[:, :, None], axis=1)

    def prefill(q, w, keys):
        if by_position:
            return jnp.broadcast_to(
                -jnp.arange(keys.shape[0], dtype=jnp.float32),
                (q.shape[0], keys.shape[0]))
        n, hq, d = q.shape
        s = jnp.matmul(q.reshape(n * hq, d), keys.T,
                       preferred_element_type=jnp.float32)
        s = s.reshape(n, hq, -1)
        s = jnp.maximum(s, 0.0) if relu else s
        return jnp.sum(s * w[:, :, None], axis=1)

    return decode, prefill


def to_fp8(x):
    """x rounded to float8 e4m3, the nearest precision below bf16, and
    handed back in its own dtype."""
    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


def variants(model):
    cfg = model.config
    experts = [layer.mlp.experts for layer in model.model.layers
               if layer.sparse]

    @contextlib.contextmanager
    def scores(**kw):
        decode, prefill = dense_scores(**kw)
        with patched(ds, "dsa_index_scores", decode), \
                patched(ds, "index_scores", prefill):
            yield

    @contextlib.contextmanager
    def on_experts(name, value):
        with contextlib.ExitStack() as stack:
            for e in experts:
                stack.enter_context(patched(e, name, value))
            yield

    def no_head_weights(self, a, cq, cos, sin, wqbi, ww):
        qi, w = INDEX_QUERY(self, a, cq, cos, sin, wqbi, ww)
        return qi, jnp.full_like(w, cfg.index_n_heads ** -0.5
                                 * cfg.index_head_dim ** -0.5)

    def bf16_router(x, router, bias, top_k, route_scale=1.0, route_norm=True,
                    n_group=1, topk_group=1, **kw):
        with patched(jnp, "float32", jnp.bfloat16):
            return ROUTE(x, router, bias, top_k, route_scale, route_norm,
                         n_group, topk_group, **kw)

    def fp8_norm(self, x):
        out = NORM(self, x)
        return type(out)(to_fp8(out.value)) if hasattr(out, "value") \
            else to_fp8(out)

    def fp8_weights(self):
        return tuple(to_fp8(ds._val(w)) if ds._val(w).ndim == 2 else w
                     for w in WEIGHTS(self))

    def fp8_mlp(self, x, lora=None):
        def f(xv, gate, up, down):
            h = jax.nn.silu(jnp.matmul(xv, to_fp8(gate))) \
                * jnp.matmul(xv, to_fp8(up))
            return jnp.matmul(to_fp8(h), to_fp8(down))

        return apply_op(f, x, self.gate_proj.weight, self.up_proj.weight,
                        self.down_proj.weight, op_name="fp8_mlp")

    def fp8_out(method):
        def wrapped(self, *a):
            return tuple(to_fp8(v) for v in method(self, *a))
        return wrapped

    @contextlib.contextmanager
    def fp8_products():
        # the lower-precision control: e4m3 on both sides of the products
        # of the attention, the indexer, the dense FFN and the shared
        # expert. Every normed activation (a, c_q, c, m, the head's input),
        # the queries (attention's and indexer's, after their rope), what
        # a token leaves in the cache (c | k_rope and kI) and the FFNs'
        # hidden state are rounded as they are made, the weight matrices
        # where they are used (the stored weights, which the reference
        # reads, stay bf16). The router, the routed experts and the head
        # stay as they are
        at = ds.DeepseekV32Attention
        with patched(RMSNorm, "forward", fp8_norm), patched(
                ds, "_rms", lambda x, w, eps: to_fp8(RMS(x, w, eps))), \
                patched(at, "_weights", fp8_weights), \
                patched(at, "_cached", fp8_out(at._cached)), \
                patched(at, "_query", fp8_out(at._query)), \
                patched(at, "_index_query", fp8_out(at._index_query)), \
                patched(LlamaMLP, "forward", fp8_mlp):
            yield

    @contextlib.contextmanager
    def selection_off():
        # every position attended. The model's layers get a config of
        # their own (run.py hands ONE config to the model and to the
        # reference); a model built before the patch has its config's
        # field set, and the caller keeps a copy for the reference
        at = ds.DeepseekV32Attention
        init = at.__init__
        with patched(cfg, "index_topk", 1 << 30), patched(
                at, "__init__", lambda self, config: init(
                    self, dataclasses.replace(config, index_topk=1 << 30))):
            yield

    INDEX_QUERY = ds.DeepseekV32Attention._index_query
    ROUTE = rx.route_top_k
    NORM, RMS = RMSNorm.forward, ds._rms
    WEIGHTS = ds.DeepseekV32Attention._weights
    plain_scale = property(lambda self: (self.qk_nope_head_dim
                                         + self.qk_rope_head_dim) ** -0.5)
    return {
        "sound": contextlib.nullcontext,
        "sound_dense_scores": lambda: scores(),      # the stand-ins' own check
        "fp8_products": fp8_products,
        "selection_off": selection_off,
        "selection_by_position": lambda: scores(by_position=True),
        "indexer_relu_dropped": lambda: scores(relu=False),
        "indexer_head_weights_dropped": lambda: patched(
            ds.DeepseekV32Attention, "_index_query", no_head_weights),
        "mscale_out_of_softmax_scale": lambda: patched(
            ds.DeepseekV32Config, "softmax_scale", plain_scale),
        "rope_off_shared_dims": lambda: patched(
            ds, "rope_pairs", lambda x, cos, sin: x),
        "shared_expert_out": lambda: patched(
            ds.DeepseekV32SparseMLP, "forward",
            lambda self, m, valid=None: self.experts(m, valid=valid)),
        "routed_scaling_factor_out": lambda: on_experts("route_scale", 1.0),
        "group_limit_off": lambda: on_experts("n_group", 1),
        "bf16_router": lambda: patched(rx, "route_top_k", bf16_router),
    }


def gaps(logits, served):
    """check_served's reading of one request: reference logits [T, V] at
    the positions that chose ``served`` [T]."""
    gap = logits.max(-1) - jnp.take_along_axis(
        logits, jnp.asarray(served)[:, None], -1)[:, 0]
    top = np.sort(np.asarray(gap))[::-1]
    return {"worst_gap": float(top[0]),
            "next_gaps": [round(float(g), 4) for g in top[1:4]],
            "tokens": int(gap.shape[0]),
            "tokens_at_argmax": int((gap == 0).sum())}


def stream_shares(model, ids):
    """rms of each layer's attention output and FFN output over the rms of
    the stream they are added to (eager, a short prompt)."""
    out = []

    def wrap(layer):
        attend, ffn = layer.self_attn.forward_with_cache, layer._ffn

        def attn(x, cache, last_idx=None):
            o, cache = attend(x, cache, last_idx=last_idx)
            rec["attn"] = float(jnp.sqrt(jnp.mean(
                o.value.astype(jnp.float32) ** 2)))
            return o, cache

        def f(x, valid):
            rec["stream"] = float(jnp.sqrt(jnp.mean(
                x.value.astype(jnp.float32) ** 2)))
            y, stats = ffn(x, valid)
            rec["ffn"] = float(jnp.sqrt(jnp.mean(
                (y.value - x.value).astype(jnp.float32) ** 2)))
            out.append(dict(rec))
            return y, stats

        rec = {}
        return patched(layer.self_attn, "forward_with_cache", attn), \
            patched(layer, "_ffn", f)

    with contextlib.ExitStack() as stack, no_grad():
        for layer in model.model.layers:
            for p in wrap(layer):
                stack.enter_context(p)
        model(paddle.to_tensor(ids))
    return [{k: round(v, 4) for k, v in r.items()} for r in out]


def forward_mode(model, cfg, cfg_ref, ref, rs, args, want):
    """Teacher-forced prefill: see the module's docstring."""
    n = args.len
    prompts = [rs.randint(1, cfg.vocab_size, (1, n)).astype(np.int32)
               for _ in range(args.prompts)]
    base = {k: p.value for k, p in model.named_parameters()}
    last = n - cfg.index_topk

    def with_gains(o_gain, routed):
        out = dict(base)
        for k, v in base.items():
            g = (o_gain if k.endswith("self_attn.o_proj.weight") else
                 routed / own
                 if k.endswith("experts.down_proj") else 1.0)
            if g != 1.0:
                out[k] = (v.astype(jnp.float32) * g).astype(v.dtype)
        return out

    own = seeded_weights.ROUTED_DOWN_GAIN
    settings = [(g, own) for g in args.o_gains] + [
        (args.o_gains[0], r) for r in args.routed_also]
    table = variants(model)
    refs = {}
    for name in want or table:
        with table[name]():
            @jax.jit
            def fwd(params, ids):
                with substituted_state(model, params), no_grad():
                    logits, _ = model.forward_with_cache(
                        ids, model.init_cache(1, n), 0)
                return getattr(logits, "value", logits)[0, -last:]

            for o_gain, routed in settings:
                if name != "sound" and routed != own:
                    continue
                params = with_gains(o_gain, routed)
                for i, ids in enumerate(prompts):
                    key = (o_gain, routed, i)
                    if key not in refs:
                        refs[key] = ref.forward(
                            params.__getitem__, cfg_ref, jnp.asarray(ids),
                            last=last)[0]
                    t = time.time()
                    got = fwd(params, jnp.asarray(ids)).astype(jnp.float32)
                    err = float(jnp.sqrt(jnp.mean((got - refs[key]) ** 2)))
                    say(variant=name, o_proj_gain=o_gain, routed_gain=routed,
                        prompt=i, **gaps(refs[key], jnp.argmax(got, -1)),
                        logits_std=round(float(refs[key].std()), 4),
                        rms_err=round(err, 5),
                        seconds=round(time.time() - t, 1))
                del params


def serve_mode(model, cfg, cfg_ref, ref, rs, args, want):
    lens = (900, 1300) if args.mid else (2900, 3700)
    prompts = [rs.randint(1, cfg.vocab_size, (1, n)).astype(np.int32)
               for n in lens]
    geometry = (dict(max_batch=2, num_pages=200, page_size=16, max_pages=96,
                     prefill_buckets=[1024, 1536]) if args.mid else
                dict(max_batch=2, num_pages=640, page_size=16, max_pages=272,
                     prefill_buckets=[4096]))
    gen = GenerationConfig(max_new_tokens=24 if args.mid else 64,
                           do_sample=False)
    params = {k: p.value for k, p in model.named_parameters()}
    table = variants(model)
    for name in want or table:
        t = time.time()
        with table[name]():
            eng = PagedContinuousBatchingEngine(model, **geometry)
            try:
                rids = [eng.add_request(p, gen) for p in prompts]
                while eng.decode_segment(8):
                    pass
                done = eng.collect_finished()
            finally:
                eng.close()
            del eng
        for rid, prompt in zip(rids, prompts):
            served = [int(t) for t in done[rid]]
            ids = jnp.asarray([list(prompt[0]) + served[:-1]], jnp.int32)
            logits = ref.forward(params.__getitem__, cfg_ref, ids,
                                 last=len(served))[0]
            say(variant=name, prompt_len=prompt.shape[1],
                **gaps(logits, served), seconds=round(time.time() - t, 1))


def main():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mid", action="store_true")
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--forward", action="store_true")
    mode.add_argument("--serve", action="store_true")
    mode.add_argument("--run", metavar="VARIANT")
    ap.add_argument("--len", type=int, default=4096)
    ap.add_argument("--prompts", type=int, default=2)
    ap.add_argument("--o-gains", type=lambda s: [float(v) for v in
                                                 s.split(",")],
                    default=[1.0], help="o_proj gains ON TOP of the "
                    "benchmark's own (--forward)")
    ap.add_argument("--routed-also", type=lambda s: [float(v) for v in
                                                     s.split(",")],
                    default=[], help="further routed gains, sound only")
    ap.add_argument("variants", nargs="*")
    argv = sys.argv[1:]
    cut = argv.index("--") if "--" in argv else len(argv)
    args, rest = ap.parse_args(argv[:cut]), argv[cut + 1:]

    platform = jax.devices()[0].platform
    if not args.run and (platform == "tpu") == args.mid:
        raise SystemExit(f"--mid is for the CPU, the real widths for a "
                         f"TPU; JAX found {platform!r}")
    config = load_json("benchmark", "configs", "deepseek-v3.2.json")
    if args.mid:
        config = overlay(config, MID)
    cfg = build_config(config)
    if args.run:
        from benchmark import run

        # run.py builds its own model: only the patches of classes and
        # modules reach it
        table = variants(SimpleNamespace(
            config=cfg, model=SimpleNamespace(layers=[])))
        if args.run in ("routed_scaling_factor_out", "group_limit_off"):
            raise SystemExit("patched on the built model's layers: "
                             "--forward or --serve")
        with table[args.run]():
            return run.main(rest)
    cfg_ref = dataclasses.replace(cfg)       # the mutations never reach it
    ref = load_module(os.path.join(ROOT, config["reference"]))
    paddle.seed(program_seed(args.seed))
    model = seeded_weights.deepseek_v32(cfg)
    model.eval()
    rs = np.random.RandomState(program_seed(args.seed) % (2 ** 31))
    if args.forward:
        # a whole number of the prefill kernel's 512-key blocks
        short = rs.randint(1, cfg.vocab_size,
                           (1, cfg.index_topk + 512)).astype(np.int32)
        say(stream_shares=stream_shares(model, short),
            device=jax.devices()[0].device_kind)
        forward_mode(model, cfg, cfg_ref, ref, rs, args, args.variants)
    else:
        serve_mode(model, cfg, cfg_ref, ref, rs, args, args.variants)


if __name__ == "__main__":
    sys.exit(main())
