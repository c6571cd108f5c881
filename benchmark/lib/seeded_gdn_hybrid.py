"""Seeded weights that a check on the chip can hold a model of
linear-attention layers to.

A cell's weights are random draws from ``--seed`` through the model's own
initialisers. A gated-delta-rule layer is a product of several projections
of its input (``q . k`` weights the values it reads back, a norm over each
head's output removes its size, a gate multiplies it), so it amplifies a
small difference of its input (bf16 against the float32 reference) by
about its degree; twelve of them in a row, with nothing but their own
outputs in the residual stream, compound. Fresh draws make it worse in two
ways that a trained checkpoint does not have, and both are cured here, in
the benchmark's data (as a mix's ``shape_seed`` is) and nowhere in the
model, after the model class has drawn its weights and before anything is
compiled:

``olmo_hybrid(cfg)`` builds ``models.olmo_hybrid.OlmoHybridForCausalLM``
and then

- shifts every linear layer's ``A_log`` by ``-log(DECAY_SLOWDOWN)``. The
  published initialiser draws the decay rates ``A`` uniform in (0, 16): a
  state then forgets all but the current token (``exp(-1.3 A)`` a
  position), a head's output is ONE term ``beta (q_t . k_t) v_t``, the
  gated norm keeps its direction alone, and the SIGN of a dot product of
  two random unit vectors (near 0 as often as not) decides it: a flipped
  sign is a whole head's output reversed, which no limit on the logits can
  tell from a wrong mechanism. With ``A`` in (0, 16 / DECAY_SLOWDOWN) a
  state remembers tens to hundreds of positions, as trained models of this
  family do, a head's output is a sum over them, and the state, the decay
  and the delta rule's correction ``k . S`` carry weight in the result (so
  the check sees them);
- rescales the embedding to rms ``EMBEDDING_RMS``. The block adds a
  unit-rms vector a sublayer (a norm follows each) to a Xavier table of rms
  0.004: the stream is then the layers' own outputs and nothing else, and
  each layer's input is the compounded error of those before it. At
  ``EMBEDDING_RMS`` the token's own embedding is a part of every layer's
  input that no earlier layer has touched.

``PERF.md`` sections 2 and 6 give the readings with and without.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

DECAY_SLOWDOWN = 256.0
EMBEDDING_RMS = 1.0


def condition(model, decay_slowdown=DECAY_SLOWDOWN,
              embedding_rms=EMBEDDING_RMS):
    """Shift and rescale ``model``'s freshly drawn parameters in place
    (see the module's docstring). Returns ``model``."""
    emb = model.model.embed_tokens.weight
    v = emb.value.astype(jnp.float32)
    emb.set_value((v * (embedding_rms / jnp.sqrt(jnp.mean(v * v)))
                   ).astype(emb.value.dtype))
    for layer in model.model.layers:
        if layer.linear:
            a_log = layer.linear_attn.A_log
            a_log.set_value(a_log.value - math.log(decay_slowdown))
    return model


def olmo_hybrid(cfg):
    """``model_class`` of ``benchmark/configs/olmo-hybrid-7b.json``."""
    from paddle_tpu.models.olmo_hybrid import OlmoHybridForCausalLM

    return condition(OlmoHybridForCausalLM(cfg))
