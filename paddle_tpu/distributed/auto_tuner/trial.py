"""Built-in auto-tuner trial target: one hybrid-parallel llama train step.

Launched by ``runner.run_trial`` as a subprocess with the candidate config
JSON in ``PADDLE_AUTO_TUNER_TRIAL``. Builds the dp×mp×pp×sharding mesh the
candidate describes, jits the training step, times ``steps`` global
batches and prints ONE JSON line with the metrics. TPU-native counterpart
of the reference auto-tuner's launched training job (the reference launches
a user script through the distributed launcher and greps its logs —
python/paddle/distributed/auto_tuner/utils.py:read_metric_log; here the
trial is a process that *reports* its metric instead of being grepped).
"""
from __future__ import annotations

import json
import os
import sys
import time


def main():
    if os.environ.get("PADDLE_AUTO_TUNER_FORCE_CPU"):
        import jax

        jax.config.update("jax_platforms", "cpu")
    cfg = json.loads(os.environ["PADDLE_AUTO_TUNER_TRIAL"])
    try:
        rec = _run(cfg)
    except Exception as e:  # noqa: BLE001 — classify, report, exit clean
        msg = str(e)
        kind = ("oom" if ("RESOURCE_EXHAUSTED" in msg or
                          "Out of memory" in msg or "OOM" in msg)
                else "error")
        rec = {"error": kind, "detail": msg[:400]}
    print(json.dumps(rec), flush=True)


def _run(cfg):
    import jax
    import numpy as np

    import paddle_tpu as paddle  # noqa: F401
    from paddle_tpu.distributed.topology import build_mesh, set_mesh
    from paddle_tpu.models import LlamaForCausalLM, llama_config

    model_cfg = cfg.get("model_cfg", {})
    dp = int(cfg.get("dp_degree", 1))
    mp = int(cfg.get("mp_degree", 1))
    pp = int(cfg.get("pp_degree", 1))
    shd = int(cfg.get("sharding_degree", 1))
    mbs = int(cfg.get("micro_batch_size", 1))
    recompute = cfg.get("use_recompute", "none")
    gbs = int(model_cfg.get("global_batch_size", mbs * dp * shd))
    seq = int(model_cfg.get("seq_len", 64))
    steps = int(cfg.get("steps", 3))
    acc = max(1, gbs // (mbs * dp * shd))

    preset = model_cfg.get("preset", "tiny")
    over = {k: model_cfg[k] for k in
            ("hidden_size", "intermediate_size", "num_hidden_layers",
             "num_attention_heads", "num_key_value_heads", "vocab_size",
             "dtype") if k in model_cfg}
    if recompute not in ("none", "full"):
        # the Layer-model trial has no "dots" checkpoint policy; erroring
        # keeps the record honest instead of measuring full and calling
        # it dots (llama_functional carries the dots policy)
        raise NotImplementedError(
            f"built-in trial supports use_recompute none/full, got "
            f"{recompute!r}")
    if recompute == "full":
        over["recompute"] = "full"
    lcfg = llama_config(preset, **over)

    rng = np.random.RandomState(0)
    t0 = time.perf_counter()
    if pp > 1:
        tps, loss = _run_pp(lcfg, dp * shd, pp, mp, mbs, acc, seq, steps, rng)
    else:
        tps, loss = _run_flat(lcfg, dp, mp, shd, mbs, acc, seq, steps, rng)
    wall = time.perf_counter() - t0
    return {"tokens_per_sec": round(tps, 2), "final_loss": loss,
            "wall_s": round(wall, 2), "acc_steps": acc}


def _run_flat(lcfg, dp, mp, shd, mbs, acc, seq, steps, rng):
    """dp×mp×sharding pjit step (pp folded out); grad-accumulate acc×."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.distributed._spmd import _filter_spec, get_pspec
    from paddle_tpu.distributed.sharding.sharded_optimizer import state_pspec
    from paddle_tpu.distributed.topology import build_mesh, set_mesh
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.nn.functional_call import functional_call
    from paddle_tpu.optimizer.functional import (adamw_init, adamw_update,
                                                 clip_by_global_norm)

    mesh = build_mesh(dp=dp, sharding=shd, mp=mp)
    set_mesh(mesh)
    model = LlamaForCausalLM(lcfg)
    params = {k: p.value for k, p in model.named_parameters()}
    pspecs = {k: _filter_spec(get_pspec(p) or P(), mesh)
              for k, p in model.named_parameters()}
    mspecs = {k: _filter_spec(state_pspec(p, mesh), mesh)
              for k, p in model.named_parameters()}
    params = {k: jax.device_put(v, NamedSharding(mesh, pspecs[k]))
              for k, v in params.items()}
    opt_state = adamw_init(params)
    opt_state = opt_state._replace(
        m={k: jax.device_put(v, NamedSharding(mesh, mspecs[k]))
           for k, v in opt_state.m.items()},
        v={k: jax.device_put(v, NamedSharding(mesh, mspecs[k]))
           for k, v in opt_state.v.items()})

    def loss_fn(pv, ids, labels):
        return functional_call(model, pv, paddle.Tensor(ids),
                               paddle.Tensor(labels))

    batch_sh = NamedSharding(mesh, P(None, ("dp", "sharding"), None))

    def train_step(pv, st, ids, labels):
        # ids/labels: [acc, B, S] — grad-accumulate over the leading axis
        def micro(c, xy):
            g_acc, l_acc = c
            l, g = jax.value_and_grad(loss_fn)(pv, xy[0], xy[1])
            return (jax.tree.map(jnp.add, g_acc, g), l_acc + l), None

        zero = jax.tree.map(jnp.zeros_like, pv)
        (grads, ls), _ = jax.lax.scan(micro, (zero, jnp.zeros(())),
                                      (ids, labels))
        n = ids.shape[0]
        grads = jax.tree.map(lambda g: g / n, grads)
        grads, _ = clip_by_global_norm(grads, 1.0)
        st, pv = adamw_update(grads, st, pv, lr=1e-4)
        return pv, st, ls / n

    # params/state are already committed with their target shardings;
    # jit infers in/out shardings from the args (explicit in_shardings +
    # donation without out_shardings trips the alias-sharding check)
    jitted = jax.jit(train_step, donate_argnums=(0, 1))

    b = mbs * dp * shd
    ids = rng.randint(0, lcfg.vocab_size, (acc, b, seq)).astype(np.int32)
    labels = rng.randint(0, lcfg.vocab_size, (acc, b, seq)).astype(np.int32)
    ids = jax.device_put(ids, batch_sh)
    labels = jax.device_put(labels, batch_sh)
    params, opt_state, loss = jitted(params, opt_state, ids, labels)
    _ = float(loss)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = jitted(params, opt_state, ids, labels)
    lv = float(loss)
    dt = time.perf_counter() - t0
    return acc * b * seq * steps / dt, lv


def _run_pp(lcfg, dp, pp, mp, mbs, acc, seq, steps, rng):
    """pp×dp compiled 1F1B pipeline over llama decoder stages."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.fleet.meta_parallel import (LayerDesc,
                                                            PipelineLayer)
    from paddle_tpu.distributed.fleet.meta_parallel.pp_compiled import (
        build_pipeline_train_step)
    from paddle_tpu.distributed.topology import build_mesh, set_mesh
    from paddle_tpu.models.llama import (LlamaDecoderLayer, _rope_cos_sin)

    mesh = build_mesh(pp=pp, dp=dp, mp=mp)
    set_mesh(mesh)

    cos, sin = _rope_cos_sin(seq, lcfg.head_dim, lcfg.rope_theta,
                             paddle.float32)

    class Embed(nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = nn.Embedding(lcfg.vocab_size, lcfg.hidden_size)

        def forward(self, ids):
            return self.emb(ids)

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.layer = LlamaDecoderLayer(lcfg)

        def forward(self, x):
            return self.layer(x, paddle.Tensor(cos), paddle.Tensor(sin))

    class Head(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(lcfg.hidden_size, lcfg.vocab_size,
                                bias_attr=False)

        def forward(self, x):
            return self.fc(x)

    def loss_fn(out, y):
        return nn.functional.cross_entropy(
            out.reshape([-1, lcfg.vocab_size]), y.reshape([-1]))

    descs = ([LayerDesc(Embed)]
             + [LayerDesc(Block) for _ in range(lcfg.num_hidden_layers)]
             + [LayerDesc(Head)])
    pipe = PipelineLayer(descs, num_stages=pp, loss_fn=loss_fn)
    params = {k: p.value for k, p in pipe.named_parameters()}
    step, init = build_pipeline_train_step(pipe, accumulate_steps=acc,
                                           mesh=mesh, lr=1e-4)
    st = init(params)
    b = mbs * acc * dp
    ids = rng.randint(0, lcfg.vocab_size, (b, seq)).astype(np.int32)
    y = rng.randint(0, lcfg.vocab_size, (b, seq)).astype(np.int32)
    params, st, loss = step(params, st, ids, y)
    _ = float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, st, loss = step(params, st, ids, y)
    lv = float(loss)
    dt = time.perf_counter() - t0
    return b * seq * steps / dt, lv


if __name__ == "__main__":
    main()
