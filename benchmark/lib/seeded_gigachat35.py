"""Seeded weights that a check on the chip can hold the hybrid of latent
attention and gated-delta-rule layers with sparse experts to.

A cell's weights are random draws from ``--seed`` through the model's own
initialisers. The hybrid has the other hybrids' two troubles and the
expert share's one, each cured here, in the benchmark's data (as a mix's
``shape_seed`` is) and nowhere in the model, after the model class has
drawn its weights and before anything is compiled:

``gigachat35(cfg)`` builds ``models.gigachat35.GigaChat35ForCausalLM`` and
then

- shifts every linear layer's ``A_log`` by ``-log(DECAY_SLOWDOWN)``
  (``lib/seeded_gdn_hybrid.py`` gives the reason at length): with the
  published initialiser's decay rates in (0, 16) a state forgets all but
  the current token, a head's output is one term whose sign a random dot
  product decides, and a flipped sign reverses a whole head, which no
  limit on the logits can tell from a wrong mechanism. Slowed, a state
  remembers tens to hundreds of positions and its recurrence, grouping and
  decay carry weight in the result;
- rescales the embedding to rms ``EMBEDDING_RMS``: the block does not scale
  its embedding, and a Xavier table over [vocab, hidden] leaves the stream
  to the sublayers' own outputs (each normed after it, ``pre_post``), so
  that every layer's input would be the compounded error of those before
  it;
- scales the held routed experts' ``down_proj`` by ``ROUTED_DOWN_GAIN``
  (``lib/seeded_weights.py`` gives the reason): a near-tie between the 8th
  and 9th router score, one expert held here and the other not, gains or
  loses a whole routed term in sound runs; small routed terms keep that
  under the limit, and routing is held to the reference on the CPU
  (``tests/test_gigachat35.py``) with experts at full size. The shared
  expert stays whole.

``PERF.md`` section 4 gives the readings.
"""
from __future__ import annotations

import math

from benchmark.lib.seeded_gdn_hybrid import DECAY_SLOWDOWN, EMBEDDING_RMS
from benchmark.lib.seeded_weights import ROUTED_DOWN_GAIN, _scale


def condition(model):
    """Shift and rescale ``model``'s freshly drawn parameters in place (see
    the module's docstring). Returns ``model``."""
    _scale(model.model.embed_tokens.weight, rms=EMBEDDING_RMS)
    for layer in model.model.layers:
        if layer.linear:
            a_log = layer.linear_attn.A_log
            a_log.set_value(a_log.value - math.log(DECAY_SLOWDOWN))
        if layer.sparse:
            _scale(layer.mlp.experts.down_proj, gain=ROUTED_DOWN_GAIN)
    return model


def gigachat35(cfg):
    """``model_class`` of ``benchmark/configs/gigachat3.5-432b-a28b.json``."""
    from paddle_tpu.models.gigachat35 import GigaChat35ForCausalLM

    return condition(GigaChat35ForCausalLM(cfg))
