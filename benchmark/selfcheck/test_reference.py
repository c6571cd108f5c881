"""The plain float32 reference against the program's own model, on the CPU
at a tiny width with grouped-query heads, both through the Layer API
(``LlamaForCausalLM``) and through the scan-over-layers forward."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


@pytest.fixture(scope="module")
def tiny():
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=320, hidden_size=64, intermediate_size=160,
                      num_hidden_layers=3, num_attention_heads=4,
                      num_key_value_heads=2, rope_theta=1e6,
                      rms_norm_eps=1e-5, dtype="float32")
    paddle.seed(4)
    model = LlamaForCausalLM(cfg)
    model.eval()
    ids = np.random.RandomState(0).randint(0, 320, (2, 24)).astype(np.int32)
    return cfg, model, ids


def test_reference_agrees_with_the_layer_api_model(tiny):
    import paddle_tpu as paddle
    from benchmark.reference import dense_decoder as ref

    cfg, model, ids = tiny
    params = {k: p.value for k, p in model.named_parameters()}
    want = ref.forward(params.__getitem__, cfg, ids)
    got = model(paddle.to_tensor(ids))
    got = np.asarray(getattr(got, "value", got))
    assert got.shape == want.shape == (2, 24, 320)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-5)


def test_reference_agrees_with_the_scan_forward_and_keeps_last(tiny):
    from benchmark.reference import dense_decoder as ref
    from paddle_tpu.models import llama_functional as lf

    cfg, model, ids = tiny
    params = {k: p.value for k, p in model.named_parameters()}
    stacked, rest = lf.stack_params(params, cfg)
    got = np.asarray(lf.forward(stacked, rest, ids, cfg, remat=False))
    want = ref.forward(ref.stacked_getter(stacked, rest), cfg, ids, last=5)
    assert want.shape == (2, 5, 320)
    np.testing.assert_allclose(got[:, -5:], np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_reference_is_causal_and_uses_grouped_heads(tiny):
    from benchmark.reference import dense_decoder as ref

    cfg, model, ids = tiny
    params = {k: p.value for k, p in model.named_parameters()}
    base = np.asarray(ref.forward(params.__getitem__, cfg, ids))
    later = ids.copy()
    later[:, -1] = (later[:, -1] + 1) % 320      # change the last token
    moved = np.asarray(ref.forward(params.__getitem__, cfg, later))
    np.testing.assert_array_equal(base[:, :-1], moved[:, :-1])
    assert np.abs(base[:, -1] - moved[:, -1]).max() > 0
    assert params["model.layers.0.self_attn.k_proj.weight"].shape == (64, 32)
