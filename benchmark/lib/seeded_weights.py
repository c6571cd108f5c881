"""Seeded weights that a check on the chip can hold a model to.

A cell's weights are random draws from ``--seed`` through the model's own
initialisers. For most models that is enough. For a model that SELECTS —
which 2,048 positions a query attends, which 8 of 256 experts take a token,
of which this chip holds 16 — fresh draws make the float32 reference and the
bf16 program disagree by far more than rounding, for reasons a trained
checkpoint does not have, and ``check.logit_margin`` would have to sit above
everything the model's new mechanisms can do to the logits. What this file
changes, after the model class has drawn its weights and before anything
is compiled, is therefore part of the benchmark's data (as a mix's
``shape_seed`` is) and is stated here and nowhere in the model:

``deepseek_v32(cfg)`` builds ``models.deepseek_v32.DeepseekV32ForCausalLM``
and then rescales three groups of its parameters (each draw keeps its
shape; only its size changes):

- the embedding to rms ``EMBEDDING_RMS``. The block does not scale its
  embedding; a Xavier table over [vocab, hidden] (rms 0.009) leaves the
  residual stream of the first layer to its own attention output, and the
  errors of a top-2048 selection over random scores then feed the next
  layer's selection and compound.
- the attention's and the indexer's matrices to rms ``ATTENTION_RMS``, the
  published ``initializer_range`` (what the family's modelling code draws
  every matrix with). At the published widths the attention's logits then
  spread by about 1.5 (a softmax that prefers some positions; Xavier draws
  give 0.5, a near-uniform average that neither rope nor the softmax scale
  can move) and an attention output is 16-18 % of the residual stream
  (0.5 % with Xavier draws): large enough that a wrong selection, scale or
  rotation moves the logits past the limit, small enough that the
  selection's own rounding noise (two indexer scores closer than bf16
  resolves: a chosen position swapped for its neighbour in rank) stays
  under it. ``PERF.md`` sections 2 and 6 give the readings, at half and
  twice that share too.
- the held routed experts' ``down_proj`` by ``ROUTED_DOWN_GAIN``. This chip
  adds the terms of the 16 experts it holds, here and in the reference. A
  token whose 8th and 9th router scores lie closer than bf16 activations
  resolve, one of the two experts held and the other not, gains or loses a
  WHOLE routed term: the same event as a row sent to a wrong expert, in
  every sound run. No limit on the worst gap can tell the two apart, so the
  routed terms are made small against the limit and routing is held to the
  reference on the CPU (``tests/test_mla_dsa_decoder.py``), in float32,
  with unrelated experts at full size; the shared expert stays whole.
"""
from __future__ import annotations

import jax.numpy as jnp

EMBEDDING_RMS = 1.0
ATTENTION_RMS = 0.02
ROUTED_DOWN_GAIN = 0.05


def _scale(param, gain=None, rms=None):
    v = param.value.astype(jnp.float32)
    if rms is not None:
        gain = rms / float(jnp.sqrt(jnp.mean(v * v)))
    param.set_value((v * gain).astype(param.value.dtype))


def condition(model):
    """Rescale ``model``'s freshly drawn parameters in place (see the
    module's docstring). Returns ``model``."""
    _scale(model.model.embed_tokens.weight, rms=EMBEDDING_RMS)
    for layer in model.model.layers:
        at = layer.self_attn
        for lin in (at.q_a_proj, at.q_b_proj, at.kv_a_proj_with_mqa,
                    at.kv_b_proj, at.o_proj, at.indexer.wq_b, at.indexer.wk,
                    at.indexer.weights_proj):
            _scale(lin.weight, rms=ATTENTION_RMS)
        if layer.sparse:
            _scale(layer.mlp.experts.down_proj, gain=ROUTED_DOWN_GAIN)
    return model


def deepseek_v32(cfg):
    """``model_class`` of ``benchmark/configs/deepseek-v3.2.json``."""
    from paddle_tpu.models.deepseek_v32 import DeepseekV32ForCausalLM

    return condition(DeepseekV32ForCausalLM(cfg))
