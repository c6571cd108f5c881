"""The sparse-expert decoder whose router reads the attention's input, with
window and full attention layers (models/smallthinker.py), against its
plain reference, at small widths in float32: the model's forward, the
served path (fused admission, then paged decode through the two cache
geometries across the window's edge, 7 query heads a KV head), which
tensor the router and the experts read, softmax-over-top-k routing and
the ReLU-GLU expert against per-token loops, and what the configuration
and the engine refuse."""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.autograd import no_grad
from paddle_tpu.inference.generation import (GenerationConfig,
                                             PagedContinuousBatchingEngine,
                                             _pad_ids)
from paddle_tpu.models._windowed import ring_pages
from paddle_tpu.models.smallthinker import (SmallThinkerConfig,
                                            SmallThinkerForCausalLM)
from paddle_tpu.nn.layer import routed_experts as rx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_PATH = os.path.join(ROOT, "tests", "reference_smallthinker_decoder.py")
WINDOW, PAGE = 8, 4


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(REF_PATH, "reference_smallthinker_decoder")


def tiny_config(**over):
    """Four layers, F S S S as published; 7 query heads over 1 KV head
    (the published 28 over 4 is 7 a group)."""
    kw = dict(vocab_size=256, hidden_size=64, moe_ffn_hidden_size=32,
              num_hidden_layers=4, num_attention_heads=7,
              num_key_value_heads=1, head_dim=16,
              sliding_window_size=WINDOW, moe_num_primary_experts=8,
              moe_num_active_primary_experts=3)
    kw.update(over)
    return SmallThinkerConfig(**kw)


def tiny_model(seed=3, **over):
    cfg = tiny_config(**over)
    paddle.seed(seed)
    model = SmallThinkerForCausalLM(cfg)
    model.eval()
    # the layer's own experts start EXPERT_SPREAD apart; a test of the
    # routing wants experts that have nothing in common
    rs = np.random.RandomState(seed)
    for name, p in model.named_parameters():
        if name.endswith(("mlp.gate_proj", "mlp.up_proj", "mlp.down_proj")):
            a = np.sqrt(6.0 / sum(p.shape[1:]))
            p.set_value(jnp.asarray(rs.uniform(-a, a, p.shape),
                                    p.value.dtype))
    return cfg, model, {k: p.value for k, p in model.named_parameters()}


def tiny_engine(model, **over):
    kw = dict(max_batch=2, num_pages=64, page_size=PAGE, max_pages=16,
              prefill_buckets=[8, 16, 32, 64])
    kw.update(over)
    return PagedContinuousBatchingEngine(model, **kw)


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(1, 256, (1, n)).astype(
        np.int32)


# -- the model's forward ------------------------------------------------------
@pytest.mark.parametrize("seq", [6, 21])
def test_forward_matches_reference(seq):
    """Shorter than the window and well past it; one full layer and three
    window layers. Float32 on both sides, one at the highest matmul
    precision: 2e-5 is float32 rounding over four layers (logits of std
    about 0.6), far under any change of mechanism."""
    cfg, model, params = tiny_model()
    ids = _ids(seq, seed=seq)
    want = ref.forward(params.__getitem__, cfg, ids)
    got = model(paddle.to_tensor(ids)).value
    assert got.shape == want.shape == (1, seq, cfg.vocab_size)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_rope_on_the_full_layers_is_a_different_model():
    """What the forward test holds: the same weights with rope on the
    full layers as well move the logits by far more than its tolerance."""
    cfg, model, params = tiny_model()
    ids = _ids(21, seed=21)
    want = ref.forward(params.__getitem__, cfg, ids)
    roped = dataclasses.replace(cfg, sliding_window_layout=[1, 1, 1, 1],
                                rope_layout=[1, 1, 1, 1],
                                sliding_window_size=1 << 20)
    other = ref.forward(params.__getitem__, roped, ids)
    assert float(jnp.abs(other - want).max()) > 1e-2


# -- the served path ----------------------------------------------------------
@pytest.mark.parametrize("plen", [WINDOW - 3, WINDOW, WINDOW + 5])
def test_fused_admission_then_paged_decode_matches_reference(plen):
    """A prompt shorter than, as long as and longer than the window goes
    through the ONE fused admission program (bucket padding included) into
    the two geometries; then 11 teacher-forced decode steps through the
    engine's step, across the window's edge (every row passes it during
    decode) and several pages' edges. The logits of every position are
    the reference's full forward's, to 5e-5: float32 rounding of the
    kernels' blocked softmax against the reference's."""
    cfg, model, params = tiny_model()
    eng = tiny_engine(model)
    steps = 11
    ids = _ids(plen + steps, seed=plen)
    want = ref.forward(params.__getitem__, cfg, ids)[0]

    eng.alloc.ensure(0, plen + steps)
    width = eng._prefill_width(plen)
    assert width > plen or plen == WINDOW       # padding is exercised
    got = eng._prefill_install(0, _pad_ids(ids[:, :plen], width), plen, 0)
    np.testing.assert_allclose(got[0], want[plen - 1], atol=5e-5)

    live = jnp.asarray([True, False])
    for i in range(steps):
        tok = jnp.asarray([[ids[0, plen + i]], [0]], jnp.int32)
        lens = jnp.asarray([plen + i, 0], jnp.int32)
        logits, caches, aux = eng._fwd_ragged(eng.params, tok, eng.caches,
                                              lens, live)
        eng.caches = caches
        np.testing.assert_allclose(logits[0, 0], want[plen + i], atol=5e-5,
                                   err_msg=f"decode step {i}")
        # one live row: every layer is an expert layer with top_k experts
        # hit, each by one row
        k = cfg.moe_num_active_primary_experts
        assert int(aux["experts_hit"]) == cfg.num_hidden_layers * k
        assert int(aux["expert_rows_max"]) == cfg.num_hidden_layers
    eng.close()


def test_engine_serves_two_rows_and_counts():
    """Through add_request / decode_segment with two rows of different
    lengths in flight: every served token is the reference's argmax, and
    the segment's span carries the window and routing counters as it does
    for the other windowed model."""
    from paddle_tpu import tracing

    cfg, model, params = tiny_model()
    eng = tiny_engine(model)
    prompts = [_ids(WINDOW + 6, seed=1), _ids(5, seed=2)]
    gen = GenerationConfig(max_new_tokens=12, do_sample=False)
    tracing.enable()
    tracing.clear()
    try:
        rids = [eng.add_request(p, gen) for p in prompts]
        while eng.decode_segment(4):
            pass
        events = tracing.events()
    finally:
        tracing.disable()
    done = eng.collect_finished()
    for rid, prompt in zip(rids, prompts):
        toks = done[rid]
        assert len(toks) == 12
        full = np.concatenate([prompt[0], toks[:-1]])[None]
        logits = ref.forward(params.__getitem__, cfg, full, last=12)[0]
        gap = logits.max(-1) - logits[np.arange(12), toks]
        assert float(gap.max()) <= 1e-4
    seg = [e for e in events if e["phase"] == "engine.segment"]
    first = seg[0]
    assert first["rows"] == 2
    assert first["ctx_tokens"] == WINDOW + 6 + 1 + 5 + 1
    assert first["ctx_tokens_window"] == WINDOW + 5 + 1
    # reserved admission: both rows' worst cases (26 and 17 positions) pass
    # the ring's, so each holds a whole ring in a window layer
    ring = ring_pages(WINDOW, PAGE)
    assert first["pages_window"] == 2 * ring
    assert first["pages_full"] == -(-(WINDOW + 18) // PAGE) + -(-17 // PAGE)
    # one compute block a row and a layer: 256 pages of 4 tokens, 1 KV head
    assert first["kv_pages_copied"] == cfg.num_hidden_layers * 2 * 256
    k, layers = cfg.moe_num_active_primary_experts, cfg.num_hidden_layers
    for e in seg:
        # summed over the segment's steps and the four expert layers
        assert layers * e["steps"] <= e["experts_hit"] \
            <= layers * e["steps"] * min(2 * k, cfg.moe_num_primary_experts)
        assert layers * e["steps"] <= e["expert_rows_max"] \
            <= layers * e["steps"] * 2
    assert eng.alloc.used_pages == 0 and eng.alloc.window.used_pages == 0
    eng.close()


def test_router_reads_the_attention_input_and_experts_the_ffn_input(
        monkeypatch):
    """Hooks on the two norms and the two routing functions: the router's
    product is taken of N1(x), the experts' of N2(x + attention)."""
    cfg, model, _ = tiny_model()
    layer = model.model.layers[1]
    seen = {}

    def record(name, fn):
        def wrapped(*a, **k):
            out = fn(*a, **k)
            seen[name] = out
            return out
        return wrapped

    monkeypatch.setattr(layer.input_layernorm, "forward",
                        record("n1", layer.input_layernorm.forward))
    monkeypatch.setattr(layer.post_attention_layernorm, "forward",
                        record("n2", layer.post_attention_layernorm.forward))
    route, ffn = rx.route_top_k, rx.routed_experts_ffn
    calls = []

    def route_spy(x, *a, **k):
        calls.append(("route", x))
        return route(x, *a, **k)

    def ffn_spy(x, *a, **k):
        calls.append(("ffn", x))
        return ffn(x, *a, **k)

    monkeypatch.setattr(rx, "route_top_k", route_spy)
    monkeypatch.setattr(rx, "routed_experts_ffn", ffn_spy)
    with no_grad():
        model(paddle.to_tensor(_ids(10)))
    n1 = np.asarray(seen["n1"].value).reshape(-1, cfg.hidden_size)
    n2 = np.asarray(seen["n2"].value).reshape(-1, cfg.hidden_size)
    (_, routed), (_, computed) = calls[2:4]         # layer 1's calls
    np.testing.assert_array_equal(np.asarray(routed), n1)
    np.testing.assert_array_equal(np.asarray(computed), n2)
    assert not np.allclose(n1, n2, atol=1e-3)


# -- the expert layer ---------------------------------------------------------
def _loop_route(x, router, k):
    """Per token: the k largest of x Wr, a softmax over them."""
    z = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    sel, w = [], []
    for row in z:
        top = np.argsort(-row, kind="stable")[:k]
        e = np.exp(row[top] - row[top].max())
        sel.append(top)
        w.append(e / e.sum())
    return np.array(sel), np.array(w)


def test_softmax_over_the_top_k_against_a_per_token_loop():
    """64 experts, 6 chosen: the chosen set and the weights of a loop that
    sorts each token's logits; the weights sum to 1 (a softmax over all 64
    without renormalising over the six sums to far less)."""
    t, h, e, k = 40, 32, 64, 6
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(t, h), jnp.float32)
    # logits of std about 0.6, as a router at initialisation gives
    router = jnp.asarray(rs.randn(h, e) * 0.1, jnp.float32)
    sel, w = rx.route_top_k(x, router, None, k, score="softmax")
    want_sel, want_w = _loop_route(x, router, k)
    np.testing.assert_array_equal(np.sort(np.asarray(sel), -1),
                                  np.sort(want_sel, -1))
    order = np.argsort(np.asarray(sel), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, -1),
        np.take_along_axis(want_w, np.argsort(want_sel, -1), -1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    everyone = jax.nn.softmax(x @ router, -1)
    assert float(jnp.take_along_axis(everyone, sel, -1).sum(-1).max()) < 0.5


def _loop_experts(x, sel, w, gate, up, down, act):
    out = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for e, we in zip(np.asarray(sel[t]), np.asarray(w[t])):
            g = np.asarray(x[t], np.float64) @ np.asarray(gate[e], np.float64)
            u = np.asarray(x[t], np.float64) @ np.asarray(up[e], np.float64)
            h = (np.maximum(g, 0) if act == "relu"
                 else g / (1 + np.exp(-g))) * u
            out[t] += we * (h @ np.asarray(down[e], np.float64))
    return out


def test_reglu_experts_against_a_per_token_loop():
    """The ReLU-GLU through the grouped products against a loop, to 2e-4
    (float32 sums of 32- and 16-term products of O(1) values); SiLU in its
    place misses the loop by orders more."""
    t, h, m, e, k = 24, 32, 16, 8, 3
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(t, h), jnp.float32)
    gate = jnp.asarray(rs.randn(e, h, m) * 0.2, jnp.float32)
    up = jnp.asarray(rs.randn(e, h, m) * 0.2, jnp.float32)
    down = jnp.asarray(rs.randn(e, m, h) * 0.2, jnp.float32)
    router = jnp.asarray(rs.randn(h, e), jnp.float32)
    sel, w = rx.route_top_k(x, router, None, k, score="softmax")
    out, stats = rx.routed_experts_ffn(x, sel, w, gate, up, down,
                                       act="relu")
    want = _loop_experts(x, sel, w, gate, up, down, "relu")
    np.testing.assert_allclose(out, want, atol=2e-4)
    silu, _ = rx.routed_experts_ffn(x, sel, w, gate, up, down)
    assert float(np.abs(np.asarray(silu) - want).max()) > 1e-2
    counts = np.bincount(np.asarray(sel).ravel(), minlength=e)
    assert int(stats["experts_hit"]) == (counts > 0).sum()
    assert int(stats["expert_rows_max"]) == counts.max()


def test_router_input_in_token_blocks():
    """A call longer than the layer's token block routes each block from
    the router input's own rows: the same as one pass."""
    paddle.seed(1)
    layer = rx.RoutedExperts(32, 16, 8, 2, score="softmax", act="relu")
    assert layer.expert_bias is None
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(2, 9, 32).astype(np.float32))
    r = paddle.to_tensor(rs.randn(2, 9, 32).astype(np.float32))
    whole, stats = layer(x, router_input=r)
    layer.token_block = 4
    blocks, bstats = layer(x, router_input=r)
    np.testing.assert_allclose(blocks.value, whole.value, atol=1e-6)
    assert int(bstats["experts_hit"]) >= int(stats["experts_hit"])
    own, _ = layer(x)
    assert not np.allclose(own.value, whole.value, atol=1e-3)


def test_logits_are_float32_whatever_the_weights():
    cfg, model, _ = tiny_model(dtype="bfloat16")
    logits = model(paddle.to_tensor(_ids(6)))
    assert logits.value.dtype == jnp.float32
    assert model.lm_head.weight.value.dtype == jnp.bfloat16


# -- what the configuration and the engine refuse -----------------------------
@pytest.mark.parametrize("over, named", [
    (dict(moe_primary_router_apply_softmax=False),
     "moe_primary_router_apply_softmax"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(rope_scaling={"type": "linear", "factor": 2.0}), "rope_scaling"),
    (dict(moe_num_secondary_experts=4), "moe_num_secondary_experts"),
    (dict(rope_layout=[0, 1, 1, 0]), "rope_layout and sliding_window_layout"),
    (dict(sliding_window_layout=[0, 1, 2, 1]), "sliding_window_layout"),
    (dict(rope_layout=[0, 1], sliding_window_layout=[0, 1]), "rope_layout"),
])
def test_config_refuses_by_name_what_it_does_not_compute(over, named):
    with pytest.raises(ValueError, match=named):
        tiny_config(**over)


@pytest.mark.parametrize("kwargs, named", [
    (dict(tp_degree=2), "tp_degree"),
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(draft_k=2), "draft_k"),
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(prefill_chunk=16), "prefill_chunk"),
    (dict(lora_capacity=2), "lora_capacity"),
])
def test_engine_refuses_by_name_what_it_cannot_do(kwargs, named):
    _, model, _ = tiny_model()
    with pytest.raises(ValueError, match=named.replace("(", r"\(")):
        tiny_engine(model, **kwargs)


# the keys of the model catalog row's ``config`` (architectures.jsonl,
# "SmallThinker-21BA3B-Instruct"); checked against the catalog where
# MODEL_CATALOG names its file
CATALOG_KEYS = (
    "head_dim", "hidden_size", "max_position_embeddings", "model_name",
    "moe_ffn_hidden_size", "moe_num_active_primary_experts",
    "moe_num_primary_experts", "moe_primary_router_apply_softmax",
    "norm_topk_prob", "num_attention_heads", "num_hidden_layers",
    "num_key_value_heads", "rms_norm_eps", "rope_layout", "rope_scaling",
    "rope_theta", "sliding_window_layout", "sliding_window_size",
    "tie_word_embeddings", "vocab_size")


def test_config_file_is_the_catalog_row_and_every_key_a_field():
    """benchmark/run.py:build_config passes config_class only the keys it
    has fields for and drops the rest in silence: every shape key of the
    file must be a field and read back unchanged; the cut layouts are the
    published ones' first layers."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21b-a3b.json")) as f:
        cfg_file = json.load(f)
    names = {f.name for f in dataclasses.fields(SmallThinkerConfig)}
    cfg = SmallThinkerConfig(**{k: v for k, v in cfg_file.items()
                                if k in names})
    catalog = os.environ.get("MODEL_CATALOG", "")
    if catalog and os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert set(CATALOG_KEYS) == set(row["config"])
        for key, value in row["config"].items():
            if key in cfg_file["reduced"]:
                assert cfg_file["published"][key] == value
            else:
                assert cfg_file[key] == value, key
    for key in CATALOG_KEYS:
        assert key in names, f"{key} is not a field of SmallThinkerConfig"
        assert getattr(cfg, key) == cfg_file[key], key
    n = cfg_file["num_hidden_layers"]
    for key in ("rope_layout", "sliding_window_layout"):
        assert cfg_file[key] == cfg_file["published"][key][:n]


def test_the_two_copies_of_the_reference_are_identical():
    with open(REF_PATH, "rb") as a, open(os.path.join(
            ROOT, "benchmark", "reference", "smallthinker_decoder.py"),
            "rb") as b:
        assert a.read() == b.read()
