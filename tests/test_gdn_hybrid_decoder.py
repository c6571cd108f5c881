"""The decoder of gated-delta-rule linear-attention layers with full
attention among them (models/olmo_hybrid.py) against its plain reference,
at small widths in float32: the model's forward, the chunked scan against
the recurrence as written, the served path (fused admission, then decode
through the rows' states and the full layers' pages), the engine's handling
of a state that belongs to a row (slots reused, preemption and replay, what
it refuses), the published recurrence, the configuration's file, and the
shared attention kernels at as many KV heads as query heads."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.generation import (GenerationConfig,
                                             PagedContinuousBatchingEngine,
                                             _pad_ids)
from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                           OlmoHybridForCausalLM)
from paddle_tpu.ops import gated_delta_rule as gdn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_PATH = os.path.join(ROOT, "tests", "reference_gdn_hybrid_decoder.py")
PAGE = 4


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(REF_PATH, "reference_gdn_hybrid_decoder")


def tiny_config(**over):
    kw = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
              num_hidden_layers=4, num_attention_heads=4,
              num_key_value_heads=2, linear_num_key_heads=3,
              linear_num_value_heads=3, linear_key_head_dim=8,
              linear_value_head_dim=16)
    kw.update(over)
    return OlmoHybridConfig(**kw)


def tiny_model(seed=3, **over):
    cfg = tiny_config(**over)
    paddle.seed(seed)
    model = OlmoHybridForCausalLM(cfg)
    model.eval()
    # slow decays among the fast ones the initialiser draws, norm weights
    # off 1 and a dt_bias off its constant: a test must see each
    rs = np.random.RandomState(seed)
    for name, p in model.named_parameters():
        if name.endswith("A_log"):
            p.set_value(jnp.log(jnp.asarray(
                rs.uniform(0.02, 2.0, p.shape), p.value.dtype)))
        if name.endswith(("norm.weight", "layernorm.weight", "dt_bias")):
            p.set_value(jnp.asarray(rs.uniform(0.5, 1.5, p.shape),
                                    p.value.dtype))
    return cfg, model, {k: p.value for k, p in model.named_parameters()}


def tiny_engine(model, **over):
    kw = dict(max_batch=3, num_pages=64, page_size=PAGE, max_pages=24,
              prefill_buckets=[8, 16, 32, 64])
    kw.update(over)
    return PagedContinuousBatchingEngine(model, **kw)


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(1, 256, (1, n)).astype(
        np.int32)


def _rule_inputs(seed, b, s, h=3, dk=8, dv=16, alike=0.0):
    """q, k (L2-normed), v, g, beta of the rule; ``alike`` pulls every key
    towards one direction (a chunk's triangular system is then far from the
    identity)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gdn.l2norm(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = gdn.l2norm((1 - alike) * jax.random.normal(ks[1], (b, s, h, dk))
                   + alike * jax.random.normal(ks[5], (b, 1, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = -0.1 * jnp.exp(jax.random.normal(ks[3], (b, s, h)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return q, k, v, g, beta


# -- the model's forward ---------------------------------------------------------
@pytest.mark.parametrize("seq", [3, 64, 90])
def test_forward_matches_reference(seq):
    cfg, model, params = tiny_model()
    ids = np.concatenate([_ids(seq, seed=seq), _ids(seq, seed=seq + 1)])
    want = ref.forward(params.__getitem__, cfg, ids)
    got = model(paddle.to_tensor(ids)).value
    assert got.dtype == jnp.float32 and got.shape == (2, seq, 256)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_logits_are_float32_and_a_linear_layer_holds_no_pages():
    cfg, model, _ = tiny_model(dtype="bfloat16")
    assert model(paddle.to_tensor(_ids(6))).value.dtype == jnp.float32
    pools = model.init_paged_cache(8, PAGE, state_rows=5)
    kinds = [cfg.is_linear(i) for i in range(4)]
    assert kinds == [True, True, True, False]
    state, rows = pools[0]
    assert state.shape == (5, 3, 8, 16) and state.dtype == jnp.float32
    assert rows.shape == (5, 3, cfg.conv_dim) and rows.dtype == jnp.bfloat16
    assert pools[3][0].shape == (8, PAGE, 2, 16)
    # the published widths: 2,211,840 + 69,120 B a row a linear layer; 30 KV
    # heads are stored as 32 (whole tiles of the pool's head axis)
    wide = OlmoHybridConfig()
    assert (wide.conv_dim, wide.head_dim, wide.cache_kv_heads) == (
        11520, 128, 32)
    assert 30 * 96 * 192 * 4 == 2211840 and 3 * wide.conv_dim * 2 == 69120
    assert sum(wide.is_linear(i) for i in range(32)) == 24


# -- the chunked scan against the recurrence as written -----------------------------
@pytest.mark.parametrize("s, last", [
    (40, 39), (64, 63), (65, 64), (130, 129), (200, 150), (256, 63),
    (256, 64), (192, 0)])
def test_chunked_scan_is_the_recurrence(s, last):
    """Lengths under, at and over one chunk and not a multiple of it, and
    ``last_idx`` short of the bucket: outputs up to ``last_idx`` and the
    state equal the recurrence's over the first ``last_idx + 1`` positions
    (the state is S_{last_idx}), and what lies past it changes nothing."""
    q, k, v, g, beta = _rule_inputs(s, 2, s)
    o, state = gdn.gdn_chunk_prefill(q, k, v, g, beta, last)
    cut = tuple(a[:, :last + 1] for a in (q, k, v, g, beta))
    o_ref, state_ref = gdn.recurrence(*cut)
    np.testing.assert_allclose(o[:, :last + 1], o_ref, atol=2e-6)
    np.testing.assert_allclose(state, state_ref, atol=4e-6)
    assert bool(jnp.isfinite(o).all())
    # other padding, the same result
    noise = tuple(a.at[:, last + 1:].set(7.0) for a in (q, k, v)) + (
        g.at[:, last + 1:].set(-3.0), beta.at[:, last + 1:].set(1.5))
    o2, state2 = gdn.gdn_chunk_prefill(*noise, last)
    np.testing.assert_array_equal(o2[:, :last + 1], o[:, :last + 1])
    np.testing.assert_array_equal(state2, state)


@pytest.mark.parametrize("alike", [0.7, 0.95])
def test_chunked_scan_with_keys_that_are_alike(alike):
    """Keys pulled towards one direction: the chunk's triangular system is
    far from the identity, and its inverse (a finite product of powers of
    its nilpotent part, in float32) still gives the recurrence."""
    q, k, v, g, beta = _rule_inputs(5, 1, 192, alike=alike)
    o, state = gdn.gdn_chunk_prefill(q, k, v, g, beta, 191)
    o_ref, state_ref = gdn.recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(o, o_ref, atol=1e-4)
    np.testing.assert_allclose(state, state_ref, atol=5e-4)


@pytest.mark.parametrize("live", [
    (True, False, True, True, False, False), (False,) * 6, (True,) * 6])
def test_one_token_update_is_one_step_of_the_scan(live):
    """The decode kernel and the XLA composition both equal one position
    of the recurrence from the rows' states; a dead row's state stays."""
    q, k, v, g, beta = _rule_inputs(7, 6, 1)
    state = jax.random.normal(jax.random.PRNGKey(9), (6, 3, 8, 16))
    alive = jnp.asarray(live)
    o_ref, s_ref = gdn.recurrence(q, k, v, g, beta, state)
    one = tuple(a[:, 0] for a in (q, k, v, g, beta))
    for step in (gdn.gdn_decode_step, gdn.decode_step_xla):
        o, new = step(state, *one, alive)
        np.testing.assert_allclose(new[alive], s_ref[alive], atol=1e-6)
        np.testing.assert_array_equal(new[~alive], state[~alive])
        np.testing.assert_allclose(o[alive], o_ref[alive, 0], atol=1e-6)


@pytest.mark.parametrize("s, last", [(9, 8), (9, 0), (9, 1), (9, 2), (9, 5)])
def test_convolution_in_both_forms(s, last):
    """The prefill's convolution, its rows at ``last_idx`` (zeros before
    position 0) and the decode's one position continue each other."""
    rs = np.random.RandomState(s + last)
    u = jnp.asarray(rs.randn(2, s + 1, 12), jnp.float32)
    w = jnp.asarray(rs.randn(12, 4), jnp.float32)
    whole = gdn.causal_conv(u, w)
    rows = gdn.conv_rows(u[:, :s], last, 4)
    assert rows.shape == (2, 3, 12)
    want = np.zeros((2, 3, 12), np.float32)
    for j in range(3):
        if last - 2 + j >= 0:
            want[:, j] = u[:, last - 2 + j]
    np.testing.assert_array_equal(rows, want)
    c, shifted = gdn.conv_step(rows, u[:, last + 1], w)
    np.testing.assert_allclose(c, whole[:, last + 1], atol=1e-6)
    np.testing.assert_array_equal(shifted[:, -1], u[:, last + 1])
    np.testing.assert_array_equal(shifted[:, :-1], rows[:, 1:])


def test_reference_recurrence_is_the_published_one():
    """The reference's recurrence against the published modelling file of
    the ``linear_*`` key family (the package is on this machine; nothing is
    fetched)."""
    torch = pytest.importorskip("torch")
    published = pytest.importorskip(
        "transformers.models.qwen3_next.modeling_qwen3_next")
    rs = np.random.RandomState(0)
    s, h, dk, dv = 37, 3, 8, 16
    q, k = rs.randn(s, h, dk), rs.randn(s, h, dk)
    v = rs.randn(s, h, dv)
    g = -np.exp(rs.randn(s, h)) * 0.3
    beta = 2 / (1 + np.exp(-rs.randn(s, h)))
    t = lambda a: torch.tensor(a[None], dtype=torch.float32)  # noqa: E731
    want, _ = published.torch_recurrent_gated_delta_rule(
        t(q), t(k), t(v), t(g), t(beta), None, False,
        use_qk_l2norm_in_kernel=True)
    f = lambda a: jnp.asarray(a, jnp.float32)                 # noqa: E731
    got = ref.delta_rule(ref.l2norm(f(q)) * dk ** -0.5, ref.l2norm(f(k)),
                         f(v), f(g), f(beta))
    np.testing.assert_allclose(got, want[0].numpy(), atol=2e-5)
    # and the program's own form of it
    mine, _ = gdn.recurrence(*(a[None] for a in (
        gdn.l2norm(f(q)) * dk ** -0.5, gdn.l2norm(f(k)), f(v), f(g),
        f(beta))))
    np.testing.assert_allclose(mine[0], want[0].numpy(), atol=2e-5)


# -- the served path --------------------------------------------------------------
@pytest.mark.parametrize("plen", [1, 2, 3, 7, 21])
def test_fused_admission_then_paged_decode_matches_reference(plen):
    """A prompt goes through the ONE fused admission program (bucket
    padding included: the state is the state after the prompt's last token,
    the convolution's rows are zeros before position 0 for prompts of 1, 2
    and 3 tokens) into the slot's rows and the full layer's pages; then 11
    teacher-forced decode steps through the engine's step, across pages'
    edges. The logits of every position are the reference's full
    forward's."""
    cfg, model, params = tiny_model()
    eng = tiny_engine(model)
    steps, slot = 11, 1
    ids = _ids(plen + steps, seed=plen)
    want = ref.forward(params.__getitem__, cfg, ids)[0]

    eng.alloc.ensure(slot, plen + steps)
    width = eng._prefill_width(plen)
    assert width > plen                       # padding is exercised
    got = eng._prefill_install(slot, _pad_ids(ids[:, :plen], width), plen, 0)
    np.testing.assert_allclose(got[0], want[plen - 1], atol=1e-4)
    pools, _ = eng.caches
    assert float(jnp.abs(pools[0][0][slot]).max()) > 0
    assert float(jnp.abs(pools[0][0][0]).max()) == 0      # another slot's

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
    eng.close()


def test_engine_serves_rows_admitted_at_different_steps_and_counts():
    """Through add_request / decode_segment with rows of different lengths
    admitted at different steps: every served token is the reference's
    argmax, and ``state_rows`` on the segments' spans adds up to the tokens
    the segments emitted."""
    from paddle_tpu import tracing

    cfg, model, params = tiny_model()
    eng = tiny_engine(model)
    prompts = [_ids(19, seed=1), _ids(2, seed=2), _ids(33, seed=3)]
    budgets = [12, 9, 6]
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
    assert seg[0]["rows"] == 1 and seg[0]["state_rows"] == 4
    assert seg[1]["rows"] == 2 and seg[1]["state_rows"] == 8
    # the (row, step) pairs the program updated: every row it held, every
    # step (no eos; a row whose budget ends inside a segment is stepped to
    # the segment's end, as the program computes it), so at least the
    # tokens emitted, whose first came from the admissions
    assert all(e["state_rows"] == e["rows"] * e["steps"] for e in seg)
    assert all(e["state_rows"] >= e["emitted"] for e in seg)
    assert sum(e["emitted"] for e in seg) == sum(budgets) - 3
    pre = [e for e in events if e["phase"] == "engine.prefill"]
    assert [(p["plen"], p["bucket"], p["fused"]) for p in pre] == [
        (19, 32, 1), (2, 8, 1), (33, 64, 1)]
    assert eng.alloc.used_pages == 0
    eng.close()


def test_a_reused_slot_carries_nothing_of_its_last_tenant():
    """The same request served in a fresh engine and in a slot another
    request just left gives the same tokens: admission overwrites the
    slot's rows, and nothing else of the old state is read."""
    cfg, model, _ = tiny_model()
    gen = GenerationConfig(max_new_tokens=10, do_sample=False)
    prompt = _ids(5, seed=11)

    eng = tiny_engine(model, max_batch=1)
    rid = eng.add_request(prompt, gen)
    while eng.decode_segment(4):
        pass
    alone = eng.collect_finished()[rid]
    eng.close()

    eng = tiny_engine(model, max_batch=1)
    first = eng.add_request(_ids(40, seed=12), gen)
    while eng.decode_segment(4):
        pass
    eng.collect_finished()
    pools, _ = eng.caches
    assert float(jnp.abs(pools[0][0][0]).max()) > 0       # the state is left
    rid = eng.add_request(prompt, gen)
    assert rid != first
    while eng.decode_segment(4):
        pass
    np.testing.assert_array_equal(eng.collect_finished()[rid], alone)
    eng.close()


def test_preempted_and_replayed_row_gives_the_same_tokens():
    """A row preempted mid-decode and re-admitted as prompt + generated
    (the scheduler's replay) continues exactly where an undisturbed run
    goes: the state is recomputed by the admission's scan, no snapshot."""
    cfg, model, _ = tiny_model()
    gen = GenerationConfig(max_new_tokens=14, do_sample=False)
    prompt = _ids(11, seed=7)

    eng = tiny_engine(model)
    rid = eng.add_request(prompt, gen)
    while eng.decode_segment(4):
        pass
    straight = eng.collect_finished()[rid]

    rid = eng.add_request(prompt, gen)
    eng.decode_segment(4)
    part = eng.preempt_request(rid)
    assert len(part) == 5 and eng.alloc.used_pages == 0
    rest = GenerationConfig(max_new_tokens=14 - len(part), do_sample=False)
    rid = eng.add_request(np.concatenate([prompt[0], part])[None], rest)
    while eng.decode_segment(4):
        pass
    replayed = np.concatenate([part, eng.collect_finished()[rid]])
    np.testing.assert_array_equal(replayed, straight)
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
    assert "recurrent state" in str(e.value)


def test_the_engine_reads_the_cache_description_in_one_place():
    """``paged_layout`` says which layers keep a state a row: the engine
    keeps one table and the plain allocator, builds the states
    ``[max_batch, ...]`` and prices a page by the full layers alone."""
    from paddle_tpu.inference.paged_cache import PageAllocator

    cfg, model, _ = tiny_model()
    layout = model.paged_layout(PAGE)
    assert layout["ring"] is None and layout["last_idx"] \
        and layout["counters"]
    assert layout["state_layers"] == (True, True, True, False)
    eng = tiny_engine(model)
    assert type(eng.alloc) is PageAllocator
    assert eng._state_layers == layout["state_layers"]
    pools, table = eng.caches
    assert pools[0][0].shape[0] == eng.max_batch == 3
    assert pools[3][0].shape[:2] == (64, PAGE) and table.shape == (3, 24)
    # one full layer: K and V of 2 heads x 16, float32
    assert eng.kv_page_cost()["bytes_per_page"] == 2 * PAGE * 2 * 16 * 4
    eng.close()


# -- the configuration's file ----------------------------------------------------
def test_config_file_is_the_catalog_row_and_every_key_a_field():
    """benchmark/run.py:build_config passes config_class only the keys it
    has fields for and drops the rest in silence: every key of the catalog
    row must be a field and read back unchanged."""
    import dataclasses

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        cfg_file = json.load(f)
    names = {f.name for f in dataclasses.fields(OlmoHybridConfig)}
    cfg = OlmoHybridConfig(
        **{k: v for k, v in cfg_file.items() if k in names})
    shape_keys = {
        "model_type", "vocab_size", "hidden_size", "intermediate_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "hidden_act", "max_position_embeddings", "attention_bias",
        "rms_norm_eps", "tie_word_embeddings", "layer_types",
        "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_value_head_dim",
        "linear_conv_kernel_dim", "linear_allow_neg_eigval",
        "rope_parameters"}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Olmo-Hybrid-7B")
        assert shape_keys == set(row["config"])
        assert cfg_file["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in cfg_file["reduced"]:
                assert cfg_file["published"][key] == value, key
            else:
                assert cfg_file[key] == value, key
    for key in shape_keys | {"dtype"}:
        assert key in names, f"{key} is not a field of OlmoHybridConfig"
        assert getattr(cfg, key) == cfg_file[key], key
    # depth alone is reduced: four whole periods of the published eight
    assert set(cfg_file["reduced"]) == {"num_hidden_layers", "layer_types"}
    assert cfg.num_hidden_layers == 16 and cfg.layer_types == \
        cfg_file["published"]["layer_types"][:16]
    assert [cfg.is_linear(i) for i in range(4)] == [True, True, True, False]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "olmo-hybrid-7b")
    assert entry["reduced"] == cfg_file["reduced"]
    assert entry["source"] == cfg_file["source"]


def test_the_benchmarks_seeding_changes_what_it_says():
    """``benchmark/lib/seeded_gdn_hybrid.py``: the decay rates divided by
    ``DECAY_SLOWDOWN``, the embedding at ``EMBEDDING_RMS``, every other
    parameter as the model class drew it; and the cell's ``model_class`` is
    that function."""
    import math

    from benchmark.lib import seeded_gdn_hybrid as seeding

    cfg = tiny_config()
    paddle.seed(9)
    drawn = {k: p.value for k, p in
             OlmoHybridForCausalLM(cfg).named_parameters()}
    paddle.seed(9)
    model = seeding.olmo_hybrid(cfg)
    assert type(model) is OlmoHybridForCausalLM
    changed = set()
    for name, p in model.named_parameters():
        if not np.array_equal(p.value, drawn[name]):
            changed.add(name.rsplit(".", 1)[-1])
        if name.endswith("A_log"):
            np.testing.assert_allclose(
                jnp.exp(p.value), jnp.exp(drawn[name])
                / seeding.DECAY_SLOWDOWN, rtol=1e-5)
            assert float(jnp.exp(p.value).max()) <= 16 / seeding.DECAY_SLOWDOWN
    emb = model.model.embed_tokens.weight.value
    assert math.isclose(float(jnp.sqrt(jnp.mean(emb * emb))),
                        seeding.EMBEDDING_RMS, rel_tol=1e-4)
    assert changed == {"A_log", "weight"}      # embed_tokens.weight alone
    assert sum(not np.array_equal(p.value, drawn[n])
               for n, p in model.named_parameters()) == 3 + 1
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        assert json.load(f)["model_class"] == \
            "benchmark.lib.seeded_gdn_hybrid:olmo_hybrid"


def test_unimplemented_settings_are_refused_not_ignored():
    for over in (dict(hidden_act="gelu"), dict(attention_bias=True),
                 dict(tie_word_embeddings=True),
                 dict(linear_num_value_heads=6),
                 dict(rope_parameters={"rope_theta": 10000.0}),
                 dict(layer_types=["sliding_attention"] * 4)):
        with pytest.raises(ValueError):
            tiny_config(**over)


def test_the_two_copies_of_the_reference_are_identical():
    with open(REF_PATH, "rb") as a, open(os.path.join(
            ROOT, "benchmark", "reference", "gdn_hybrid_decoder.py"),
            "rb") as b:
        assert a.read() == b.read()


# -- the shared attention kernels at 30 = 30 heads -----------------------------------
def test_paged_decode_with_as_many_kv_heads_as_query_heads():
    """30 query heads over 30 KV heads (stored as 32: the model's
    ``cache_kv_heads``), a count that is no multiple of 8, against the dense
    composition."""
    from paddle_tpu.ops.paged_attention import (_paged_decode_ref,
                                                paged_decode_mha)

    rs = np.random.RandomState(0)
    heads, stored, d, ps = 30, 32, 16, 4
    lens = jnp.asarray([9, 0, 23], jnp.int32)
    table = jnp.asarray(rs.permutation(24).reshape(3, 8), jnp.int32)
    kp = jnp.asarray(rs.randn(24, ps, stored, d), jnp.float32)
    vp = jnp.asarray(rs.randn(24, ps, stored, d), jnp.float32)
    q = jnp.asarray(rs.randn(3, stored, d), jnp.float32)
    got = paged_decode_mha(q, kp, vp, table, lens)
    want = _paged_decode_ref(q, kp, vp, table, lens)
    np.testing.assert_allclose(got[:, :heads][lens > 0],
                               want[:, :heads][lens > 0], atol=2e-5)
    # and unpadded, as the kernel's interpreter takes it
    got = paged_decode_mha(q[:, :heads], kp[:, :, :heads], vp[:, :, :heads],
                           table, lens)
    np.testing.assert_allclose(got[lens > 0], want[:, :heads][lens > 0],
                               atol=2e-5)


def test_flash_forward_with_as_many_kv_heads_as_query_heads():
    """The flash forward at 30 = 30 heads against softmax(q k^T) v."""
    from paddle_tpu.ops.flash_attention_kernel import flash_attention_bhsd

    rs = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rs.randn(1, 30, 128, 16), jnp.float32)
               for _ in range(3))
    got = flash_attention_bhsd(q, k, v, causal=True, interpret=True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / 4.0
    s = jnp.where(jnp.tril(jnp.ones((128, 128), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- the benchmark's readers ---------------------------------------------------
def test_the_readers_tell_the_mechanisms_operations_apart():
    """``benchmark/lib/gated_delta.py`` sorts a traced device operation by
    its compiled text (instructions as the chip's profiler wrote them, PR
    34, shortened), and counts bytes and FLOPs from the configuration."""
    from benchmark.lib import gated_delta as gd

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        config = json.load(f)
    geo = gd.geometry({"config": config,
                       "mix": {"engine": {"max_batch": 48}}})
    assert (geo["linear_layers"], geo["full_layers"], geo["conv_dim"]) == (
        12, 4, 11520)
    call = ', custom_call_target="tpu_custom_call", operand_layout'
    texts = {
        "update": [
            "%gdn_decode_step.84 = (f32[48,3,10,192]{3,2,1,0}, f32[48,30,96,"
            "192]{3,2,1,0}) custom-call(s32[48]{0} %compare.24)" + call,
            "%fusion.9 = f32[48,30,96,192]{3,2,1,0} fusion(f32[48,30,96,192]"
            "{3,2,1,0} %p.1), kind=kLoop"],
        "scan": [
            "%gdn_chunk_prefill.14 = (bf16[30,1024,192]{2,1,0}, f32[30,96,"
            "192]{2,1,0}) custom-call(s32[1]{0} %bitcast.2)" + call],
        "conv": [
            "%multiply_convert_fusion.3 = bf16[1,1024,11520]{2,1,0} fusion("
            "bf16[1,1027,11520]{2,1,0} %pad.1, f32[11520]{0} %slice.4), "
            "kind=kLoop",
            "%fusion.77 = f32[11520,48]{1,0} fusion(f32[11520,4]{1,0} "
            "%convert.5, bf16[48,3,11520]{2,1,0} %gte.9), kind=kLoop"],
        "elementwise": [
            "%multiply_bitcast_fusion.22 = f32[30,1024,96]{2,1,0} fusion("
            "bf16[1,1024,11520]{2,1,0} %fusion.5), kind=kLoop",
            "%fusion.31 = bf16[1,2048,30,192]{3,2,1,0} fusion(f32[30,2048,"
            "192]{2,1,0} %gte.3), kind=kLoop"],
        "": [
            "%paged_decode.28 = bf16[48,32,128]{2,1,0} custom-call(s32[48,"
            "320]{1,0} %gte.7)" + call,
            "%multiply_convert_fusion.17 = bf16[48,30,128]{2,1,0} fusion("
            "f32[48,30,128]{2,1,0} %reshape.2524), kind=kLoop",
            "%convolution_bitcast_fusion.24 = bf16[48,1,11520]{2,0,1} "
            "fusion(bf16[3840,11520]{1,0} %gte.7080, bf16[48,3840]{1,0} "
            "%fusion.1443), kind=kOutput"]}
    for want, events in texts.items():
        for text in events:
            assert gd.kind([text, 0, 1, {}], geo) == want, text
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    assert gd.state_bytes(geo) == 2211840
    assert gd.row_state_bytes(config, geo) == 2280960
    assert gd.full_kv_bytes_per_token(config, geo) == 61440
    # 40 rows a step: 2.12 GB of states read and written, 2.6 ms
    assert abs(gd.step_least_s(geo, 40, peaks) - 2.593e-3) < 1e-5
    # a position and layer: the bytes of q, k, v, o bind (42 ns, 17 by FLOPs)
    assert abs(gd.scan_least_s_per_position(geo, peaks) - 42.2e-9) < 1e-10
