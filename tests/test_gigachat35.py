"""The hybrid of latent attention and grouped gated-delta-rule layers with
sparse experts (models/gigachat35.py) against its plain reference
(benchmark/reference/gigachat35_decoder.py), at small widths in float32:
the model's forward, the served path (fused admission, then paged decode
through latent pages beside row states), the dense latent decode kernel,
the causal prefill kernel, the grouped heads of both linear-attention
kernels, the SwiGLU limit, the expert share, the benchmark's seeding and
configuration file, and what the configuration and the engine refuse."""
import dataclasses
import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.generation import (GenerationConfig,
                                             PagedContinuousBatchingEngine,
                                             _pad_ids)
from paddle_tpu.models.gigachat35 import (GigaChat35Config,
                                          GigaChat35ForCausalLM)
from paddle_tpu.nn.layer.routed_experts import RoutedExperts, glu
from paddle_tpu.ops import gated_delta_rule as gdn
from paddle_tpu.ops import sparse_latent_attention as sla

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
REF_PATH = os.path.join(ROOT, "benchmark", "reference",
                        "gigachat35_decoder.py")
CONFIG_PATH = os.path.join(ROOT, "benchmark", "configs",
                           "gigachat3.5-432b-a28b.json")
PAGE = 4


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load(REF_PATH, "reference_gigachat35_decoder")


@pytest.fixture(autouse=True)
def _small_reference_blocks(monkeypatch):
    """Blocks of 8 positions and keys padded to 16: the carry of a linear
    layer's state and convolution from block to block, and the padding of
    the attention's keys, are exercised at test lengths."""
    monkeypatch.setattr(ref, "ROW_BLOCK", 8)
    monkeypatch.setattr(ref, "KEY_PAD", 16)


def tiny_config(**over):
    """Four layers: linear (dense FFN), linear, full, linear (experts); 4
    latent heads; 4 value heads over 2 key heads (2 a group, as
    published); 16 experts of which this chip holds 4 (rank 1 of 4). A
    SwiGLU limit of 0.5 that the tiny widths' products pass, so the clamps
    are exercised; YaRN over an original context of 16."""
    kw = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
              moe_intermediate_size=32, num_hidden_layers=4,
              num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
              kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
              qk_head_dim=24, v_head_dim=16, n_routed_experts=16,
              ep_size=4, ep_rank=1, num_experts_per_tok=4,
              first_k_dense_replace=1, full_attention_layers=[2],
              linear_key_head_dim=16, linear_value_head_dim=8,
              linear_num_key_heads=2, linear_num_value_heads=4,
              swiglu_limit=0.5,
              rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 8,
                            "mscale": 1, "mscale_all_dim": 1,
                            "original_max_position_embeddings": 16,
                            "type": "yarn"})
    kw.update(over)
    return GigaChat35Config(**kw)


def tiny_model(seed=3, **over):
    cfg = tiny_config(**over)
    paddle.seed(seed)
    model = GigaChat35ForCausalLM(cfg)
    model.eval()
    # zero-centred norm weights off 0, slow decays among fast ones, a
    # dt_bias off its constant, and held experts that have nothing in
    # common (they start 1 % apart): a test must see each
    rs = np.random.RandomState(seed)
    for name, p in model.named_parameters():
        if name.endswith("A_log"):
            p.set_value(jnp.log(jnp.asarray(
                rs.uniform(0.02, 2.0, p.shape), p.value.dtype)))
        elif name.endswith(("norm.weight", "layernorm.weight", "dt_bias")):
            p.set_value(jnp.asarray(rs.uniform(-1.0, 1.0, p.shape),
                                    p.value.dtype))
        elif name.endswith(("experts.gate_proj", "experts.up_proj",
                            "experts.down_proj")):
            a = np.sqrt(6.0 / sum(p.shape[1:]))
            p.set_value(jnp.asarray(rs.uniform(-a, a, p.shape),
                                    p.value.dtype))
        elif name.endswith("expert_bias"):
            p.set_value(jnp.asarray(rs.uniform(-0.1, 0.1, p.shape),
                                    p.value.dtype))
    return cfg, model, {k: p.value for k, p in model.named_parameters()}


def tiny_engine(model, **over):
    kw = dict(max_batch=3, num_pages=64, page_size=PAGE, max_pages=16,
              prefill_buckets=[8, 16, 32, 64])
    kw.update(over)
    return PagedContinuousBatchingEngine(model, **kw)


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(1, 256, (1, n)).astype(
        np.int32)


# -- the model's forward ---------------------------------------------------------
@pytest.mark.parametrize("seq", [3, 40])
def test_forward_matches_reference(seq):
    """float32 logits of every position of two rows. 1e-4: the chunked
    scan (WY form, blocks of 64) and the blocked softmax sum in another
    order than the reference's one-position recurrence and whole softmax;
    the logits are O(1)."""
    cfg, model, params = tiny_model()
    ids = np.concatenate([_ids(seq, seed=seq), _ids(seq, seed=seq + 1)])
    want = ref.forward(params.__getitem__, cfg, ids)
    got = model.forward(paddle.to_tensor(ids)).value
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_reference_keeps_the_last_positions():
    cfg, _, params = tiny_model()
    ids = _ids(21, seed=5)
    whole = ref.forward(params.__getitem__, cfg, ids)
    np.testing.assert_allclose(ref.forward(params.__getitem__, cfg, ids,
                                           last=6), whole[:, -6:], atol=1e-6)


# -- the served path --------------------------------------------------------------
@pytest.mark.parametrize("plen", [1, 13])
def test_fused_admission_then_paged_decode_matches_reference(plen):
    """A prompt goes through the ONE fused admission program (bucket
    padding included) into the slot's row states and the full layer's
    latent pages; then 9 teacher-forced decode steps through the engine's
    step, across pages' edges. Every position's logits are the reference's
    full forward's (1e-4, as the forward's), and the step's counters are
    the rows it attended and updated."""
    cfg, model, params = tiny_model()
    eng = tiny_engine(model)
    steps, slot = 9, 1
    ids = _ids(plen + steps, seed=plen)
    want = ref.forward(params.__getitem__, cfg, ids)[0]

    eng.alloc.ensure(slot, plen + steps)
    width = eng._prefill_width(plen)
    assert width > plen                       # padding is exercised
    got = eng._prefill_install(slot, _pad_ids(ids[:, :plen], width), plen, 0)
    np.testing.assert_allclose(got[0], want[plen - 1], atol=1e-4)
    pools, _ = eng.caches
    assert float(jnp.abs(pools[0][0][slot]).max()) > 0     # a row's state
    assert float(jnp.abs(pools[0][0][0]).max()) == 0       # another slot's

    live = jnp.asarray([False, True, False])
    for i in range(steps):
        tok = jnp.zeros((3, 1), jnp.int32).at[slot, 0].set(ids[0, plen + i])
        lens = jnp.zeros((3,), jnp.int32).at[slot].set(plen + i)
        logits, caches, aux = eng._fwd_ragged(eng.params, tok, eng.caches,
                                              lens, live)
        eng.caches = caches
        np.testing.assert_allclose(logits[slot, 0], want[plen + i],
                                   atol=1e-4, err_msg=f"decode step {i}")
        assert int(aux["state_rows"]) == 1
        assert int(aux["latent_rows_attended"]) == plen + i + 1
    eng.close()


def test_engine_serves_rows_admitted_at_different_steps_and_counts():
    """Through add_request / decode_segment with rows of different lengths
    admitted at different steps: every served token is the reference's
    argmax, and the segments' spans count the rows updated and the latent
    rows attended."""
    from paddle_tpu import tracing

    cfg, model, params = tiny_model()
    eng = tiny_engine(model)
    prompts = [_ids(19, seed=1), _ids(2, seed=2), _ids(30, seed=3)]
    budgets = [10, 9, 5]
    tracing.enable()
    tracing.clear()
    try:
        rids = [eng.add_request(prompts[0], GenerationConfig(
            max_new_tokens=budgets[0], do_sample=False))]
        eng.decode_segment(4)
        rids.append(eng.add_request(prompts[1], GenerationConfig(
            max_new_tokens=budgets[1], do_sample=False)))
        eng.decode_segment(4)
        rids.append(eng.add_request(prompts[2], GenerationConfig(
            max_new_tokens=budgets[2], do_sample=False)))
        while eng.decode_segment(4):
            pass
        events = tracing.events()
    finally:
        tracing.disable()
    done = eng.collect_finished()
    for rid, prompt, n in zip(rids, prompts, budgets):
        toks = done[rid]
        assert len(toks) == n
        full = np.concatenate([prompt[0], toks[:-1]])[None]
        logits = ref.forward(params.__getitem__, cfg, full, last=n)[0]
        gap = logits.max(-1) - logits[np.arange(n), toks]
        assert float(gap.max()) <= 1e-4
    seg = [e for e in events if e["phase"] == "engine.segment"]
    # segment 1: row 0 alone, contexts 20..23 (its prompt and first token)
    assert seg[0]["state_rows"] == 4
    assert seg[0]["latent_rows_attended"] == 20 + 21 + 22 + 23
    assert all(e["state_rows"] == e["rows"] * e["steps"] for e in seg)
    assert eng.alloc.used_pages == 0
    eng.close()


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
    with pytest.raises(ValueError, match=named.replace("(", r"\(")) as e:
        tiny_engine(model, **kwargs)
    assert "latent rows" in str(e.value) and "recurrent state" in str(e.value)


def test_one_pool_description_holds_latent_pages_and_row_states():
    """``paged_layout`` names the layers that keep a state a row; the
    others' pools are one latent row a token: the engine builds both from
    it and prices a page by the full layer alone."""
    cfg, model, _ = tiny_model()
    layout = model.paged_layout(PAGE)
    assert layout["state_layers"] == (True, True, False, True)
    eng = tiny_engine(model)
    pools, table = eng.caches
    assert [len(p) for p in pools] == [2, 2, 1, 2]
    assert pools[0][0].shape == (3, 4, 16, 8)       # rows, value heads
    assert pools[2][0].shape == (64, PAGE, cfg.cache_row)
    assert eng.kv_page_cost()["bytes_per_page"] == PAGE * cfg.cache_row * 4
    eng.close()


# -- the kernels ------------------------------------------------------------------
@pytest.mark.parametrize("block", [8, 1024])
def test_paged_latent_decode_is_attention_over_the_rows(block, monkeypatch):
    """Rows of length 0 (no page copied, zeros out), 1, a page's edge (4),
    past it, and a compute block's edge (8 positions in blocks of 8) and
    past it, against a softmax over the rows gathered through the table.
    2e-6: float32 products in another order."""
    monkeypatch.setattr(sla, "BLOCK_POSITIONS", block)
    rs = np.random.RandomState(0)
    lens = jnp.asarray([0, 1, 4, 5, 8, 11], jnp.int32)
    b, h, c, r, w, pages = 6, 4, 16, 8, 32, 48
    pool = jnp.asarray(rs.randn(pages, PAGE, w), jnp.float32)
    pool = pool.at[..., c + r:].set(0.0)
    table = jnp.asarray(rs.permutation(pages)[:b * 4].reshape(b, 4),
                        jnp.int32)
    q_lat = jnp.asarray(rs.randn(b, h, c), jnp.float32)
    q_rope = jnp.asarray(rs.randn(b, h, r), jnp.float32)
    # the function itself, not its jit: the block is read when it traces
    got = sla.paged_latent_decode.__wrapped__(q_lat, q_rope, pool, table,
                                              lens, 0.3)
    assert float(jnp.abs(got[0]).max()) == 0.0
    for i in range(1, b):
        n = int(lens[i])
        rows = pool[table[i]].reshape(-1, w)[:n]
        s = 0.3 * (q_lat[i] @ rows[:, :c].T + q_rope[i] @ rows[:, c:c + r].T)
        want = jax.nn.softmax(s, axis=-1) @ rows[:, :c]
        np.testing.assert_allclose(got[i], want, atol=2e-6)


@pytest.mark.parametrize("s, last", [(64, 63), (64, 37), (128, 100)])
def test_causal_selected_attention_is_its_masked_form(s, last):
    """``selected_attention`` with no mask (the causal compare in the
    diagonal block only) against the same kernel under a full causal mask,
    queries up to the last one's block."""
    rs = np.random.RandomState(s + last)
    q, k = (jnp.asarray(rs.randn(4, s, 24), jnp.float32) for _ in range(2))
    v = jnp.asarray(rs.randn(4, s, 16), jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), jnp.int8))
    got = sla.selected_attention(q, k, v, None, last)
    want = sla.selected_attention(q, k, v, causal, last)
    upto = (last // 64 + 1) * 64 if s % 64 == 0 else s
    np.testing.assert_allclose(got[:, :upto], want[:, :upto], atol=1e-6)


def _grouped_rule_inputs(seed, b, s, hk=2, hv=4, dk=8, dv=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = gdn.l2norm(jax.random.normal(ks[0], (b, s, hk, dk))) * dk ** -0.5
    k = gdn.l2norm(jax.random.normal(ks[1], (b, s, hk, dk)))
    v = jax.random.normal(ks[2], (b, s, hv, dv))
    g = -0.1 * jnp.exp(jax.random.normal(ks[3], (b, s, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hv)))
    return q, k, v, g, beta


@pytest.mark.parametrize("s, last", [(100, 70), (130, 129)])
def test_grouped_chunked_scan_is_the_recurrence(s, last):
    """4 value heads over 2 key heads: value head j reads key head j // 2,
    against the recurrence with each key head repeated for its group.
    2e-6 / 4e-6: the chunk's triangular solve in float32."""
    q, k, v, g, beta = _grouped_rule_inputs(s, 2, s)
    o, state = gdn.gdn_chunk_prefill(q, k, v, g, beta, last)
    cut = slice(0, last + 1)
    rq, rk = (jnp.repeat(a[:, cut], 2, axis=2) for a in (q, k))
    o_ref, state_ref = gdn.recurrence(rq, rk, v[:, cut], g[:, cut],
                                      beta[:, cut])
    np.testing.assert_allclose(o[:, cut], o_ref, atol=2e-6)
    np.testing.assert_allclose(state, state_ref, atol=4e-6)
    # the grouping is the published one: key head j mod Hk is another model
    wrong = tuple(jnp.tile(a[:, cut], (1, 1, 2, 1)) for a in (q, k))
    o_wrong, _ = gdn.recurrence(*wrong, v[:, cut], g[:, cut], beta[:, cut])
    assert float(jnp.abs(o_wrong - o_ref).max()) > 1e-2


@pytest.mark.parametrize("live", [(True, True, False), (False, True, True)])
def test_grouped_update_is_one_step_of_the_recurrence(live):
    q, k, v, g, beta = _grouped_rule_inputs(7, 3, 1)
    live = jnp.asarray(live)
    state = jax.random.normal(jax.random.PRNGKey(8), (3, 4, 8, 16))
    o, new = gdn.gdn_decode_step(state + 0.0, q[:, 0], k[:, 0], v[:, 0],
                                 g[:, 0], beta[:, 0], live)
    o_ref, s_ref = gdn.recurrence(
        jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), v, g, beta,
        state)
    alive = np.asarray(live)
    np.testing.assert_allclose(new[alive], s_ref[alive], atol=1e-6)
    np.testing.assert_allclose(o[alive], o_ref[alive, 0], atol=1e-6)
    np.testing.assert_array_equal(new[~alive], state[~alive])
    assert float(jnp.abs(o[~alive]).max()) == 0.0


# -- the experts -------------------------------------------------------------------
def test_swiglu_limit_clamps_the_gate_above_and_the_linear_half_both_ways():
    g = jnp.asarray([-20.0, -3.0, 0.5, 3.0, 20.0])
    u = jnp.asarray([-20.0, -3.0, 0.5, 3.0, 20.0])
    want = jax.nn.silu(jnp.minimum(g, 2.0)) * jnp.clip(u, -2.0, 2.0)
    np.testing.assert_array_equal(glu(g, u, "silu", 2.0), want)
    np.testing.assert_array_equal(glu(g, u), jax.nn.silu(g) * u)


def test_the_shares_add_up():
    """The routed parts of the 4 ranks, each holding its 4 of 16 experts,
    plus the shared expert once, are the reference's uncut layer (every
    expert held), SwiGLU limit and sigmoid routing with its bias
    included."""
    cfg = tiny_config(ep_size=1, ep_rank=0)
    st = ref._Static.of(cfg)
    h, m, e, k = 64, 32, 16, 4
    rs = np.random.RandomState(13)
    x = jnp.asarray(rs.randn(24, h), jnp.float32)
    full = {"router": rs.randn(h, e) * 0.3, "bias": rs.randn(e) * 0.1,
            "gate": rs.randn(e, h, m) * 0.2, "up": rs.randn(e, h, m) * 0.2,
            "down": rs.randn(e, m, h) * 0.2}
    full = {n: jnp.asarray(v, jnp.float32) for n, v in full.items()}
    shared = [jnp.asarray(rs.randn(*s) * 0.2, jnp.float32)
              for s in ((h, m), (h, m), (m, h))]
    total, rows_here = 0, 0
    for rank in range(4):
        paddle.seed(0)
        layer = RoutedExperts(h, m, e, k, route_scale=2.5, held=(rank * 4, 4),
                              limit=st.limit)
        hold = slice(rank * 4, rank * 4 + 4)
        layer.router.set_value(full["router"])
        layer.expert_bias.set_value(full["bias"])
        layer.gate_proj.set_value(full["gate"][hold])
        layer.up_proj.set_value(full["up"][hold])
        layer.down_proj.set_value(full["down"][hold])
        out, stats = layer(paddle.to_tensor(x))
        total = total + out.value
        rows_here += int(stats["expert_rows_here"])
    assert rows_here == 24 * k           # every choice landed on one rank
    weights = ref.route(x, full["router"], full["bias"], st)
    routed = sum(weights[:, i][:, None] * ref.swiglu(
        x, full["gate"][i], full["up"][i], full["down"][i], st.limit)
        for i in range(e))
    shared_out = ref.swiglu(x, *shared, st.limit)
    assert float(jnp.abs(routed).max()) > 0.0
    np.testing.assert_allclose(shared_out + total, shared_out + routed,
                               atol=2e-5)


# -- the defaults of the options this model added ------------------------------------
def test_the_new_options_leave_the_other_models_calls_as_they_were():
    """No limit: the experts' middle is act(g) * u, with no clamp in its
    program; equal head counts: both linear-attention kernels take one
    head a value head (no group axis in their blocks); the GDN mixer's
    default output is N_o(o) * silu(z) with an RMS norm of weight 1."""
    from paddle_tpu.models.olmo_hybrid import (GatedDeltaNet,
                                               OlmoHybridConfig)

    g = jnp.linspace(-30.0, 30.0, 64).reshape(8, 8)
    text = str(jax.make_jaxpr(lambda a, b: glu(a, b))(g, g))
    assert "min" not in text and "clamp" not in text
    q, k, v, gg, beta = _grouped_rule_inputs(3, 1, 64, hk=2, hv=2)
    text = str(jax.make_jaxpr(lambda *a: gdn.gdn_chunk_prefill(*a, 63))(
        q, k, v, gg, beta))
    assert "(2, 64, 16)" in text.replace("[", "(").replace("]", ")") or \
        "f32[2,64,16]" in text
    assert gdn._head_group(30) == 10 and gdn._head_group(64, 2) == 8
    paddle.seed(0)
    mixer = GatedDeltaNet(OlmoHybridConfig(
        hidden_size=32, linear_num_key_heads=2, linear_num_value_heads=2,
        linear_key_head_dim=8, linear_value_head_dim=8))
    assert mixer.gating == "silu"
    assert float(jnp.abs(mixer.norm.weight.value - 1.0).max()) == 0.0
    o = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 8))
    z = jax.random.normal(jax.random.PRNGKey(1), (3, 16))
    var = jnp.mean(o * o, -1, keepdims=True)
    want = (o * jax.lax.rsqrt(var + 1e-6)
            * jax.nn.silu(z).reshape(3, 2, 8)).reshape(3, 16)
    np.testing.assert_allclose(mixer._out(o, z, mixer.norm.weight.value),
                               want, atol=1e-6)


# -- the configuration --------------------------------------------------------------
@pytest.mark.parametrize("over", [
    dict(nextn_is_sparse=True), dict(n_group=2),
    dict(use_shared_expert_sigmoid=True), dict(hidden_act="gelu"),
    dict(gated_attention=False), dict(rope_interleave=False),
    dict(layernorm_type="pre"), dict(norm_type="RMSNorm"),
    dict(linear_gating_type="silu"), dict(linear_sigmoid_gate_scale=1),
    dict(linear_num_value_heads=3), dict(num_key_value_heads=2),
    dict(qk_head_dim=32), dict(full_attention_layers=[7]),
    dict(tie_word_embeddings=True), dict(ep_rank=4)],
    ids=lambda d: next(iter(d)))
def test_unimplemented_settings_are_refused_by_name(over):
    key = next(iter(over))
    with pytest.raises(ValueError, match=key):
        tiny_config(**over)


def test_config_file_is_the_catalog_row_and_every_key_a_field():
    """benchmark/run.py:build_config passes config_class only the keys it
    has fields for: every published key of the file must be a field and
    read back unchanged; what the cut changes is in ``reduced`` with its
    published value."""
    with open(CONFIG_PATH) as f:
        cfg_file = json.load(f)
    names = {f.name for f in dataclasses.fields(GigaChat35Config)}
    cfg = GigaChat35Config(**{k: v for k, v in cfg_file.items()
                              if k in names})
    harness = {"name", "source", "config_class", "model_class", "reference",
               "reduced", "published", "cut", "deployment", "assumed",
               "departures"}
    for key, value in cfg_file.items():
        if key not in harness:
            assert key in names, key
            assert getattr(cfg, key) == value, key
    assert set(cfg_file["published"]) == set(cfg_file["reduced"])
    assert set(cfg_file["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace",
        "full_attention_layers", "ep_size", "vocab_size"}
    assert [cfg.is_linear(i) for i in range(5)] == [True] * 4 + [False]
    assert cfg.experts_held == 16 and cfg.cache_row == 640
    assert math.isclose(cfg.softmax_scale, 0.105304, rel_tol=1e-5)
    for key in ("assumed", "departures", "cut", "deployment"):
        assert cfg_file[key], key
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == cfg_file["name"])
    assert entry["reduced"] == cfg_file["reduced"]
    assert entry["source"] == cfg_file["source"]


def test_the_benchmarks_seeding_changes_what_it_says():
    """``benchmark/lib/seeded_gigachat35.py``: the decay rates divided by
    ``DECAY_SLOWDOWN``, the embedding at ``EMBEDDING_RMS``, the held
    experts' down projection by ``ROUTED_DOWN_GAIN``, every other
    parameter as the model class drew it; and the cell's ``model_class``
    is that function."""
    from benchmark.lib import seeded_gigachat35 as seeding

    cfg = tiny_config()
    paddle.seed(9)
    drawn = {k: p.value for k, p in
             GigaChat35ForCausalLM(cfg).named_parameters()}
    paddle.seed(9)
    model = seeding.gigachat35(cfg)
    assert type(model) is GigaChat35ForCausalLM
    changed = set()
    for name, p in model.named_parameters():
        if not np.array_equal(p.value, drawn[name]):
            changed.add(name)
        if name.endswith("A_log"):
            np.testing.assert_allclose(
                jnp.exp(p.value), jnp.exp(drawn[name])
                / seeding.DECAY_SLOWDOWN, rtol=1e-5)
        if name.endswith("experts.down_proj"):
            np.testing.assert_allclose(
                p.value, drawn[name] * seeding.ROUTED_DOWN_GAIN, rtol=1e-6)
    emb = model.model.embed_tokens.weight.value
    assert math.isclose(float(jnp.sqrt(jnp.mean(emb * emb))),
                        seeding.EMBEDDING_RMS, rel_tol=1e-4)
    assert changed == {"model.embed_tokens.weight"} | {
        f"model.layers.{i}.linear_attn.A_log" for i in (0, 1, 3)} | {
        f"model.layers.{i}.mlp.experts.down_proj" for i in (1, 2, 3)}
    with open(CONFIG_PATH) as f:
        assert json.load(f)["model_class"] == \
            "benchmark.lib.seeded_gigachat35:gigachat35"


def test_the_model_is_imported_lazily():
    """``import paddle_tpu`` does not import the model's module (no cell
    pays for it at set-up); the package hands it out by name."""
    import subprocess

    code = ("import sys, paddle_tpu, paddle_tpu.models as m; "
            "assert 'paddle_tpu.models.gigachat35' not in sys.modules; "
            "assert m.GigaChat35ForCausalLM.__name__ == "
            "'GigaChat35ForCausalLM'; print('ok')")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.stdout.strip() == "ok", out.stderr[-2000:]
