"""What the chip's check of ``serve-olmo-hybrid-7b-manyrows`` can see: the
benchmark's model (``benchmark/lib/seeded_gdn_hybrid.py``), sound and with
one mechanism of its linear-attention and full layers broken at a time,
against the plain reference of the SOUND model, at the published widths, on
the chip.

    python experiments/exp_gdn_mutations.py [--seed N] [variant ...]
        --forward [--len 2048] [--slowdowns 1,256] [--embeddings 0,1]
        --run <variant> -- <benchmark/run.py's arguments>

``--forward``: one jitted prefill of a whole prompt a variant; every
position is a token the model would serve next, and the reference's float32
logits give the gap between their maximum and that token's logit, as
``benchmark/run.py``'s ``check_served`` reads it. One compile a variant,
any number of settings of the benchmark's seeding without another
(``--slowdowns``: ``DECAY_SLOWDOWN``; ``--embeddings``: ``EMBEDDING_RMS``,
0 = the model's own draw). The decode-only mutations (the state kept in
bf16 between steps, the convolution's rows not carried over) show only
under ``--run``.
``--run``: the variant is patched in and ``benchmark/run.py`` itself runs
the cell: its own traffic, window and comparison (``sound`` patches
nothing).

``check.logit_margin`` has to lie above the sound runs' gaps and under the
mutations'. One JSON line a reading. ``--mid``: narrow widths in bf16 on
the CPU, for the control flow.
"""
import contextlib
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from benchmark.lib import seeded_gdn_hybrid as seeding  # noqa: E402
from benchmark.run import (build_config, load_json, load_module,  # noqa: E402
                           overlay, program_seed)
from paddle_tpu.core.autograd import no_grad  # noqa: E402
from paddle_tpu.models import olmo_hybrid as oh  # noqa: E402
from paddle_tpu.nn.functional_call import substituted_state  # noqa: E402
from paddle_tpu.ops import gated_delta_rule as gdn  # noqa: E402

MID = {"vocab_size": 2048, "hidden_size": 512, "intermediate_size": 1024,
       "num_hidden_layers": 8, "num_attention_heads": 4,
       "num_key_value_heads": 4, "linear_num_key_heads": 4,
       "linear_num_value_heads": 4, "linear_key_head_dim": 32,
       "linear_value_head_dim": 64, "dtype": "bfloat16"}
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


def variants():
    """{name: a context manager that breaks one mechanism}. Only classes
    and modules are patched, so a model built inside it (``run.py``'s) is
    broken too."""
    net, attn = oh.GatedDeltaNet, oh.OlmoHybridAttention
    gates, out = net._gates, net._out
    scan, step, rows = (gdn.gdn_chunk_prefill, gdn.gdn_decode_step,
                        gdn.conv_rows)

    def no_decay(self, *a):
        g, beta = gates(self, *a)
        return jnp.zeros_like(g), beta

    def beta_not_doubled(self, *a):
        g, beta = gates(self, *a)
        return g, beta / 2

    def no_gate(self, o, z, norm_w):
        # silu(z) = 1 where z = 1.2785 (the gate's place taken by a one)
        return out(self, o, jnp.full_like(z, 1.2784645), norm_w)

    def no_qk_norm(self, qv, kv, vv, qw, kw):
        b, s, hd = qv.shape[0], qv.shape[1], self.config.head_dim
        return (qv.reshape(b, s, self.num_heads, hd),
                kv.reshape(b, s, self.kv_heads, hd),
                vv.reshape(b, s, self.kv_heads, hd))

    def rounded(x):
        return x.astype(jnp.bfloat16).astype(F32)

    def scan_bf16(*a, **k):
        o, state = scan(*a, **k)
        return o, rounded(state)

    def step_bf16(*a, **k):
        o, state = step(*a, **k)
        return o, rounded(state)

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
        "decay_out": one(net, "_gates", no_decay),
        "beta_not_doubled": one(net, "_gates", beta_not_doubled),
        "conv_out": both(
            one(gdn, "causal_conv",
                lambda u, w: jax.nn.silu(u.astype(F32)).astype(u.dtype)),
            one(gdn, "conv_step", lambda r, u, w: (
                jax.nn.silu(u.astype(F32)).astype(u.dtype),
                jnp.concatenate([r, u[:, None].astype(r.dtype)], 1)[:, 1:]))),
        "l2norm_out": one(gdn, "l2norm", lambda x, eps=1e-6: x),
        "gate_out": one(net, "_out", no_gate),
        "state_bf16": both(one(gdn, "gdn_chunk_prefill", scan_bf16),
                           one(gdn, "gdn_decode_step", step_bf16)),
        "conv_rows_zero": one(gdn, "conv_rows", lambda u, last, width:
                              jnp.zeros_like(rows(u, last, width))),
        "qk_norm_out": one(attn, "_heads", no_qk_norm),
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


def forward_mode(cfg, ref, args, want):
    """Teacher-forced prefill: see the module's docstring."""
    n = args.len
    rs = np.random.RandomState(program_seed(args.seed) % (2 ** 31))
    ids = rs.randint(1, cfg.vocab_size, (1, n)).astype(np.int32)
    paddle.seed(program_seed(args.seed))
    model = oh.OlmoHybridForCausalLM(cfg)
    model.eval()
    drawn = {k: p.value for k, p in model.named_parameters()}
    emb = drawn["model.embed_tokens.weight"].astype(F32)
    emb_rms = float(jnp.sqrt(jnp.mean(emb * emb)))
    table, refs = variants(), {}
    for name in want or table:
        with table[name]():
            @jax.jit
            def fwd(params, ids):
                with substituted_state(model, params), no_grad():
                    logits, _ = model.forward_with_cache(
                        ids, model.init_cache(1, n), 0)
                return getattr(logits, "value", logits)[0]

            for slow in args.slowdowns:
                for rms in args.embeddings:
                    if name != "sound" and (slow, rms) != (
                            args.slowdowns[-1], args.embeddings[-1]):
                        continue
                    params = dict(drawn)
                    for k, v in drawn.items():
                        if k.endswith("A_log"):
                            params[k] = v - math.log(slow)
                    if rms:
                        params["model.embed_tokens.weight"] = (
                            emb * (rms / emb_rms)).astype(
                                drawn["model.embed_tokens.weight"].dtype)
                    if (slow, rms) not in refs:
                        refs[slow, rms] = ref.forward(
                            params.__getitem__, cfg, jnp.asarray(ids))[0]
                    want_logits = refs[slow, rms]
                    t = time.time()
                    got = fwd(params, jnp.asarray(ids)).astype(F32)
                    err = float(jnp.sqrt(jnp.mean((got - want_logits) ** 2)))
                    say(variant=name, decay_slowdown=slow, embedding_rms=rms,
                        **gaps(want_logits, jnp.argmax(got, -1)),
                        logits_std=round(float(want_logits.std()), 4),
                        rms_err=round(err, 5),
                        seconds=round(time.time() - t, 1))
                    del params


def main():
    import argparse

    floats = lambda s: [float(v) for v in s.split(",")]      # noqa: E731
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mid", action="store_true")
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--forward", action="store_true")
    mode.add_argument("--run", metavar="VARIANT")
    ap.add_argument("--len", type=int, default=2048)
    ap.add_argument("--slowdowns", type=floats,
                    default=[seeding.DECAY_SLOWDOWN])
    ap.add_argument("--embeddings", type=floats,
                    default=[seeding.EMBEDDING_RMS])
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
    config = load_json("benchmark", "configs", "olmo-hybrid-7b.json")
    if args.mid:
        config = overlay(config, MID)
    cfg = build_config(config)
    ref = load_module(os.path.join(ROOT, config["reference"]))
    say(device=jax.devices()[0].device_kind, layers=cfg.num_hidden_layers)
    forward_mode(dataclasses.replace(cfg), ref, args, args.variants)


if __name__ == "__main__":
    sys.exit(main())
