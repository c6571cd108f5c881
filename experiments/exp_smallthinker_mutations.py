"""What the chip's check of ``serve-smallthinker-21b-a3b-longanswer`` can
see: the model sound and with one mechanism broken at a time, against the
plain reference of the SOUND model, at the published widths, on the chip.

    python experiments/exp_smallthinker_mutations.py [--seed N] [variant ...]
        --forward [--len 6144] [--last 2048]
        --run <variant> -- <benchmark/run.py's arguments>

``--forward``: one jitted prefill of a whole prompt a variant; each of the
last ``--last`` positions is a token the model would serve next (its
argmax), and the reference's float32 logits give the gap between their
maximum and that token's logit, as ``benchmark/run.py``'s
``check_served`` reads it. One compile a variant. A prompt of 6,144
positions passes the 4,096 window, so the window layers' mask in the
flash forward is read; the mask in ``paged_decode`` only shows under
``--run`` (``window_mask_out_of_paged_decode``: a window layer's kernel
then reads its whole ring, up to ``page_size - 1`` positions more than
the window).
``--run``: the variant is patched in and ``benchmark/run.py`` itself runs
the cell: its own traffic, window and comparison (``sound`` patches
nothing).

``check.logit_margin`` has to lie above the sound runs' gaps and under the
mutations'. One JSON line a reading. ``--mid``: narrow widths on the CPU,
for the control flow.
"""
import contextlib
import json
import os
import sys
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from benchmark.run import (build_config, load_json, load_module,  # noqa: E402
                           overlay, program_seed, resolve)
from paddle_tpu.core.autograd import no_grad  # noqa: E402
from paddle_tpu.models import llama  # noqa: E402
from paddle_tpu.models import smallthinker as st  # noqa: E402
from paddle_tpu.nn.functional_call import substituted_state  # noqa: E402
from paddle_tpu.nn.layer import routed_experts as rx  # noqa: E402
from paddle_tpu.ops import paged_attention, pallas  # noqa: E402

MID = {"vocab_size": 2048, "hidden_size": 256, "moe_ffn_hidden_size": 128,
       "num_hidden_layers": 4, "num_attention_heads": 7,
       "num_key_value_heads": 1, "head_dim": 64,
       "moe_num_primary_experts": 16, "sliding_window_size": 64}
F32 = jnp.float32


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


@contextlib.contextmanager
def patched_item(table, key, value):
    old = table[key]
    table[key] = value
    try:
        yield
    finally:
        table[key] = old


def variants():
    """{name: a context manager that breaks one mechanism}. Only classes,
    modules and their tables are patched, so a model built inside it
    (``run.py``'s) is broken too."""
    layer = st.SmallThinkerDecoderLayer
    flash, paged, gmm = (pallas.flash_attention,
                         paged_attention.paged_decode_mha,
                         pallas.grouped_matmul)

    def router_on_n2(self, x, attend, valid):
        a = self.input_layernorm(x)
        attn, cache = attend(a)
        x = x + attn
        f, stats = self.mlp(self.post_attention_layernorm(x), valid=valid)
        return x + f, cache, stats

    def rope_everywhere(self, qv, kv, vv, weights, cos, sin):
        b, s, hd = qv.shape[0], qv.shape[1], self.config.head_dim
        qh = llama.apply_rotary_emb(
            qv.reshape(b, s, self.num_heads, hd).astype(F32), cos, sin)
        kh = llama.apply_rotary_emb(
            kv.reshape(b, s, self.kv_heads, hd).astype(F32), cos, sin)
        return (qh.astype(qv.dtype), kh.astype(kv.dtype),
                vv.reshape(b, s, self.kv_heads, hd))

    def softmax_over_all(x, router, bias, top_k, route_scale=1.0, *a, **k):
        z = jnp.matmul(x.astype(F32), router.astype(F32),
                       precision=jax.lax.Precision.HIGHEST)
        w, sel = jax.lax.top_k(jax.nn.softmax(z, -1), top_k)
        return sel.astype(jnp.int32), w * route_scale

    def e4m3(v):
        # float8 e4m3's rounding as an operation XLA keeps: a round trip
        # through the type is a convert pair the chip's compiler drops
        # (it read the sound run's gaps to the last digit)
        return jax.lax.reduce_precision(v, exponent_bits=4, mantissa_bits=3)

    def gmm_fp8(lhs, rhs, sizes, **k):
        return gmm(e4m3(lhs), e4m3(rhs), sizes, **k)

    def both(*cms):
        @contextlib.contextmanager
        def cm():
            with contextlib.ExitStack() as stack:
                for c in cms:
                    stack.enter_context(c())
                yield
        return cm

    def one(obj, name, value):
        return lambda: patched(obj, name, value)

    return {
        "sound": contextlib.nullcontext,
        "silu_experts": lambda: patched_item(rx.ACTIVATIONS, "relu",
                                             jax.nn.silu),
        "router_on_n2": one(layer, "_block", router_on_n2),
        "rope_on_full_layers": one(st.SmallThinkerAttention, "_heads",
                                   rope_everywhere),
        "window_mask_out": both(
            one(pallas, "flash_attention",
                lambda *a, window=None, **k: flash(*a, **k)),
            one(paged_attention, "paged_decode_mha",
                lambda *a, window=None, **k: paged(*a, **k))),
        "window_mask_out_of_paged_decode": one(
            paged_attention, "paged_decode_mha",
            lambda *a, window=None, **k: paged(*a, **k)),
        "softmax_over_all_experts": one(rx, "route_top_k", softmax_over_all),
        "fp8_e4m3_expert_products": one(pallas, "grouped_matmul", gmm_fp8),
    }


def gaps(logits, served):
    """check_served's reading: reference logits [T, V] at the positions
    that chose ``served`` [T]."""
    logits = np.asarray(logits)
    gap = logits.max(-1) - logits[np.arange(len(served)), served]
    top = np.sort(gap)[::-1]
    return {"worst_gap": float(top[0]),
            "next_gaps": [round(float(g), 4) for g in top[1:4]],
            "tokens": int(gap.shape[0]),
            "tokens_at_argmax": int((gap == 0).sum())}


def forward_mode(config, cfg, args, want):
    """Teacher-forced prefill: see the module's docstring."""
    n, last = args.len, min(args.last, args.len)
    ref = load_module(os.path.join(ROOT, config["reference"]))
    rs = np.random.RandomState(program_seed(args.seed) % (2 ** 31))
    ids = rs.randint(1, cfg.vocab_size, (1, n)).astype(np.int32)
    paddle.seed(program_seed(args.seed))
    model = resolve(config["model_class"])(cfg)
    model.eval()
    params = {k: p.value for k, p in model.named_parameters()}
    want_logits = ref.forward(params.__getitem__, cfg, ids, last=last)[0]
    say(phase="reference", positions=n, last=last,
        logits_std=round(float(want_logits.std()), 4))
    for name in want or variants():
        with variants()[name]():
            @jax.jit
            def served(params, ids):
                with substituted_state(model, params), no_grad():
                    hidden, _ = model.model.forward_with_cache(
                        ids, model.init_cache(1, n), 0)
                    logits = model._logits(
                        getattr(hidden, "value", hidden)[:, n - last:])
                return jnp.argmax(getattr(logits, "value", logits)[0], -1)

            t = time.time()
            tokens = np.asarray(served(params, jnp.asarray(ids)))
            say(variant=name, **gaps(want_logits, tokens),
                seconds=round(time.time() - t, 1))


def main():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mid", action="store_true")
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--forward", action="store_true")
    mode.add_argument("--run", metavar="VARIANT")
    ap.add_argument("--len", type=int, default=6144)
    ap.add_argument("--last", type=int, default=2048)
    ap.add_argument("variants", nargs="*")
    argv = sys.argv[1:]
    cut = argv.index("--") if "--" in argv else len(argv)
    args, rest = ap.parse_args(argv[:cut]), argv[cut + 1:]

    if args.run:
        from benchmark import run

        with variants()[args.run]():
            return run.main(rest)
    platform = jax.devices()[0].platform
    if (platform == "tpu") == args.mid:
        raise SystemExit(f"--mid is for the CPU, the real widths for a "
                         f"TPU; JAX found {platform!r}")
    config = load_json("benchmark", "configs", "smallthinker-21b-a3b.json")
    if args.mid:
        config = overlay(config, MID)
    cfg = build_config(config)
    say(device=jax.devices()[0].device_kind, layers=cfg.num_hidden_layers,
        seed=args.seed)
    forward_mode(config, cfg, args, args.variants)


if __name__ == "__main__":
    sys.exit(main())
