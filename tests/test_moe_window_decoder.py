"""The sparse-expert decoder with window and full attention layers
(models/afmoe.py) against its plain reference, at small widths in
float32: the model's forward, the served path (fused admission, then
paged decode through the two cache geometries), the routed expert layer,
the window in the two attention kernels, the allocator, and what the
engine refuses with such a model."""
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
from paddle_tpu.inference.paged_cache import WindowedPageAllocator
from paddle_tpu.models.afmoe import (AfmoeConfig, AfmoeForCausalLM,
                                     ring_pages)
from paddle_tpu.nn.layer.routed_experts import (EXPERT_SPREAD,
                                                RoutedExperts, route_top_k,
                                                routed_experts_ffn)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_PATH = os.path.join(ROOT, "tests", "reference_moe_window_decoder.py")
WINDOW, PAGE = 16, 4


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(REF_PATH, "reference_moe_window_decoder")


def tiny_config(**over):
    kw = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
              moe_intermediate_size=32, num_hidden_layers=4,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              sliding_window=WINDOW, num_dense_layers=1, num_experts=8,
              num_experts_per_tok=2,
              layer_types=["sliding_attention", "full_attention",
                           "sliding_attention", "full_attention"])
    kw.update(over)
    return AfmoeConfig(**kw)


def tiny_model(seed=3, **over):
    cfg = tiny_config(**over)
    paddle.seed(seed)
    model = AfmoeForCausalLM(cfg)
    model.eval()
    # the model's own experts start EXPERT_SPREAD apart; a test of the
    # routing wants experts that have nothing in common
    rs = np.random.RandomState(seed)
    for name, p in model.named_parameters():
        if ".experts." in name and name.endswith("_proj"):
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


# -- the model's forward ---------------------------------------------------------
@pytest.mark.parametrize("seq", [12, 40])
def test_forward_matches_reference(seq):
    """Shorter than the window and well past it; two window layers, two
    full layers, one dense and three expert layers."""
    cfg, model, params = tiny_model()
    ids = _ids(seq, seed=seq)
    want = ref.forward(params.__getitem__, cfg, ids)
    got = model(paddle.to_tensor(ids)).value
    assert got.shape == want.shape == (1, seq, cfg.vocab_size)
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- the served path --------------------------------------------------------------
@pytest.mark.parametrize("plen", [WINDOW - 5, WINDOW, WINDOW + 9])
def test_fused_admission_then_paged_decode_matches_reference(plen):
    """A prompt shorter than, as long as and longer than the window goes
    through the ONE fused admission program (bucket padding included) into
    the two geometries; then 11 teacher-forced decode steps through the
    engine's step, across the window's edge and several pages' edges. The
    logits of every position are the reference's full forward's."""
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
        # one live row: each expert layer has top_k experts hit, each by
        # one row
        assert int(aux["experts_hit"]) == 3 * cfg.num_experts_per_tok
        assert int(aux["expert_rows_max"]) == 3
    eng.close()


def test_engine_serves_two_rows_and_counts():
    """Through add_request / decode_segment with two rows of different
    lengths in flight: every served token is the reference's argmax, and
    the segment's span carries the new counters."""
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
    assert seg
    first = seg[0]
    assert first["rows"] == 2
    assert first["ctx_tokens"] == WINDOW + 6 + 1 + 5 + 1
    assert first["ctx_tokens_window"] == WINDOW + 5 + 1
    # reserved admission: plen + 12 tokens of pages in a full layer, the
    # ring's at most in a window layer
    ring = ring_pages(WINDOW, PAGE)
    assert first["pages_full"] == -(-(WINDOW + 18) // PAGE) + -(-17 // PAGE)
    assert first["pages_window"] == ring + -(-17 // PAGE)
    assert first["experts_hit"] > 0 and first["expert_rows_max"] > 0
    pre = [e for e in events if e["phase"] == "engine.prefill"]
    assert [p["window_rows"] for p in pre] == [
        WINDOW + 6 - ((WINDOW + 5) // PAGE - ring + 1) * PAGE, 5]
    assert eng.alloc.used_pages == 0 and eng.alloc.window.used_pages == 0
    eng.close()


def test_preempted_and_replayed_row_gives_the_same_tokens():
    """A row preempted mid-decode and re-admitted as prompt + generated
    (the scheduler's replay) continues exactly where an undisturbed run
    goes: its window ring is rebuilt by the admission alone."""
    cfg, model, _ = tiny_model()
    gen = GenerationConfig(max_new_tokens=14, do_sample=False)
    prompt = _ids(WINDOW + 3, seed=7)

    eng = tiny_engine(model)
    rid = eng.add_request(prompt, gen)
    while eng.decode_segment(4):
        pass
    straight = eng.collect_finished()[rid]

    rid = eng.add_request(prompt, gen)
    eng.decode_segment(4)
    part = eng.preempt_request(rid)
    assert len(part) == 5 and eng.alloc.window.used_pages == 0
    rest = GenerationConfig(max_new_tokens=14 - len(part), do_sample=False)
    rid = eng.add_request(np.concatenate([prompt[0], part])[None], rest)
    while eng.decode_segment(4):
        pass
    replayed = np.concatenate([part, eng.collect_finished()[rid]])
    np.testing.assert_array_equal(replayed, straight)
    eng.close()


# -- the allocator ----------------------------------------------------------------
def test_window_layer_pages_are_bounded_and_return_to_the_pool():
    ring = ring_pages(WINDOW, PAGE)
    alloc = WindowedPageAllocator(num_pages=64, page_size=PAGE, max_batch=2,
                                  max_pages=16, ring_pages=ring, debug=True)
    segment_steps = 4
    bound = -(-(WINDOW + segment_steps) // PAGE) + 1
    for n in range(1, 16 * PAGE + 1):          # grow token by token
        alloc.ensure(0, n)
        full, win = alloc.held_pages(0)
        assert full == -(-n // PAGE)
        assert win == min(full, ring) <= bound
    assert alloc.window.num_pages == 2 * ring
    alloc.ensure(1, 3 * PAGE)
    assert alloc.used_pages == 16 + 3 and alloc.window.used_pages == ring + 3
    alloc.free_slot(0)
    assert alloc.held_pages(0) == (0, 0)
    assert alloc.used_pages == 3 and alloc.window.used_pages == 3
    assert (alloc.window.page_table[0] == -1).all()
    alloc.free_slot(1)
    assert alloc.free_pages == 64 and alloc.window.free_pages == 2 * ring
    alloc.close()


# -- the expert layer ---------------------------------------------------------------
def _loop_experts(x, sel, w, gate, up, down):
    out = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for e, we in zip(np.asarray(sel[t]), np.asarray(w[t])):
            g = np.asarray(x[t], np.float64) @ np.asarray(gate[e], np.float64)
            u = np.asarray(x[t], np.float64) @ np.asarray(up[e], np.float64)
            h = g / (1 + np.exp(-g)) * u
            out[t] += we * (h @ np.asarray(down[e], np.float64))
    return out


@pytest.mark.parametrize("routing", ["learned", "one_expert_for_all",
                                     "half_the_experts_unused"])
def test_routed_experts_against_per_token_loop(routing):
    t, h, m, e, k = 24, 32, 16, 8, 2
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(t, h), jnp.float32)
    gate = jnp.asarray(rs.randn(e, h, m) * 0.2, jnp.float32)
    up = jnp.asarray(rs.randn(e, h, m) * 0.2, jnp.float32)
    down = jnp.asarray(rs.randn(e, m, h) * 0.2, jnp.float32)
    router = jnp.asarray(rs.randn(h, e), jnp.float32)
    bias = np.zeros((e,), np.float32)
    if routing == "one_expert_for_all":
        bias[3] = 10.0                  # every row's first choice
    if routing == "half_the_experts_unused":
        bias[e // 2:] = -10.0           # chosen by none
    sel, w = route_top_k(x, router, jnp.asarray(bias), k, 2.5, True)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    out, stats = routed_experts_ffn(x, sel, w, gate, up, down)
    np.testing.assert_allclose(out, _loop_experts(x, sel, w, gate, up, down),
                               atol=2e-4)
    counts = np.bincount(np.asarray(sel).ravel(), minlength=e)
    assert int(stats["experts_hit"]) == (counts > 0).sum()
    assert int(stats["expert_rows_max"]) == counts.max()
    if routing == "one_expert_for_all":
        assert counts[3] == t
    if routing == "half_the_experts_unused":
        assert counts[e // 2:].sum() == 0

    # rows that are padding take no expert and give 0
    valid = jnp.arange(t) < t - 7
    out_v, stats_v = routed_experts_ffn(x, sel, w, gate, up, down,
                                        valid=valid)
    np.testing.assert_allclose(out_v[:t - 7], out[:t - 7], atol=1e-5)
    assert float(jnp.abs(out_v[t - 7:]).max()) == 0.0
    assert int(stats_v["expert_rows_max"]) <= int(stats["expert_rows_max"])


def test_experts_start_as_one_ffn_and_a_spread_of_their_own():
    """The layer's initial experts: the Xavier variance an element, and
    any two of them sqrt(2) x EXPERT_SPREAD apart by norm (one shared draw
    + EXPERT_SPREAD of each expert's own); gate, up and down share
    nothing."""
    paddle.seed(5)
    layer = RoutedExperts(128, 64, 8, 2)
    gate = np.asarray(layer.gate_proj.value, np.float64)
    up = np.asarray(layer.up_proj.value, np.float64)
    np.testing.assert_allclose(gate.std(), np.sqrt(2.0 / (128 + 64)),
                               rtol=0.02)
    for a, b in ((0, 1), (2, 7)):
        rel = np.linalg.norm(gate[a] - gate[b]) / np.linalg.norm(gate[a])
        np.testing.assert_allclose(rel, np.sqrt(2) * EXPERT_SPREAD,
                                   rtol=0.05)
    assert abs(np.corrcoef(gate[0].ravel(), up[0].ravel())[0, 1]) < 0.05


def test_logits_are_float32_whatever_the_weights():
    """bf16 weights, float32 logits: the top of a wide vocabulary's
    logits lie closer than bf16 resolves."""
    cfg, model, _ = tiny_model(dtype="bfloat16")
    logits = model(paddle.to_tensor(_ids(6)))
    assert logits.value.dtype == jnp.float32
    assert model.lm_head.weight.value.dtype == jnp.bfloat16


def test_grouped_matmul_kernel_and_ragged_dot_agree():
    """The megablox kernel that ``grouped_matmul`` calls on the TPU
    (interpret mode here) and the path it takes on the CPU give the same
    rows for the groups that have rows; rows past the last group are no
    path's to define."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from paddle_tpu.ops.pallas import grouped_matmul

    rs = np.random.RandomState(1)
    sizes = jnp.asarray([5, 0, 130, 1, 0, 60], jnp.int32)   # 196 of 256
    lhs = jnp.asarray(rs.randn(256, 128), jnp.float32)
    rhs = jnp.asarray(rs.randn(6, 128, 256), jnp.float32)
    a = gmm(lhs, rhs, sizes, preferred_element_type=jnp.float32,
            tiling=(128, 128, 128), interpret=True)
    b = grouped_matmul(lhs, rhs, sizes)
    n = int(sizes.sum())
    np.testing.assert_allclose(a[:n], b[:n], rtol=1e-4, atol=1e-3)
    seg = np.repeat(np.arange(6), np.asarray(sizes))
    want = np.einsum("rk,rkn->rn", np.asarray(lhs[:n]),
                     np.asarray(rhs)[seg])
    np.testing.assert_allclose(b[:n], want, rtol=1e-4, atol=1e-3)


# -- the window in the two kernels ---------------------------------------------------
def _masked_attention(q, k, v, window):
    """[B, H, S, D] plain softmax attention, causal within ``window``."""
    s = q.shape[2]
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    sc = jnp.where((j <= i) & (j > i - window), sc, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v)


@pytest.mark.parametrize("window", [128, 200, 384])
def test_window_flash_against_masked_attention(window):
    from paddle_tpu.ops.flash_attention_kernel import flash_attention_bhsd

    rs = np.random.RandomState(window)
    q = jnp.asarray(rs.randn(1, 4, 512, 32), jnp.float32)
    k = jnp.asarray(rs.randn(1, 2, 512, 32), jnp.float32)
    v = jnp.asarray(rs.randn(1, 2, 512, 32), jnp.float32)
    got = flash_attention_bhsd(q, k, v, causal=True, window=window,
                               block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(got, _masked_attention(q, k, v, window),
                               atol=2e-5)


def test_window_flash_skips_blocks_outside_the_window():
    """Which blocks the kernel visits, not how long it takes: a key block
    wholly outside every window of a query block is never computed, so a
    NaN there (which a block that is computed and then masked would carry
    into 0 * NaN) leaves the result clean."""
    from paddle_tpu.ops.flash_attention_kernel import (_window_blocks,
                                                       flash_attention_bhsd)

    rs = np.random.RandomState(0)
    s, blk, window = 1024, 128, 128
    q = jnp.asarray(rs.randn(1, 2, s, 32), jnp.float32)
    k = jnp.asarray(rs.randn(1, 2, s, 32), jnp.float32)
    v = jnp.asarray(rs.randn(1, 2, s, 32), jnp.float32)
    want = _masked_attention(q, k, v, window)
    # query block 6 (rows 768..895) sees keys 641..895: blocks 5 and 6
    lo, hi = _window_blocks(6, blk, blk, 0, window, s // blk)
    assert (int(lo), int(hi)) == (5, 6)
    rows = slice(6 * blk, 7 * blk)
    outside = np.ones((s,), bool)
    outside[5 * blk:7 * blk] = False
    poison = jnp.where(jnp.asarray(outside)[None, None, :, None], jnp.nan, v)
    # bottom-right alignment: the queries are the LAST rows of the keys
    # they are given, so the keys end at row 895
    got = flash_attention_bhsd(q[:, :, rows], k[:, :, :7 * blk],
                               poison[:, :, :7 * blk], causal=True,
                               window=window, block_q=blk, block_k=blk,
                               interpret=True)
    assert not bool(jnp.isnan(got).any())
    np.testing.assert_allclose(got, want[:, :, rows], atol=2e-5)


def _paged_setup(lens, ps=4, maxp=12, hkv=2, d=16, seed=0):
    rs = np.random.RandomState(seed)
    b = len(lens)
    pool = rs.randn(b * maxp + 1, ps, hkv, d).astype(np.float32)
    vool = rs.randn(b * maxp + 1, ps, hkv, d).astype(np.float32)
    table = np.arange(b * maxp, dtype=np.int32).reshape(b, maxp) + 1
    q = rs.randn(b, 4, d).astype(np.float32)
    return q, pool, vool, table


def _dense_window_decode(q, pool, vool, table, lens, window):
    out = []
    for r, ln in enumerate(lens):
        k = pool[table[r]].reshape(-1, *pool.shape[2:])[:ln]
        v = vool[table[r]].reshape(-1, *pool.shape[2:])[:ln]
        k, v = k[max(ln - window, 0):], v[max(ln - window, 0):]
        g = q.shape[1] // k.shape[1]
        k, v = np.repeat(k, g, 1), np.repeat(v, g, 1)
        sc = np.einsum("hd,lhd->hl", q[r], k) / np.sqrt(q.shape[-1])
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out.append(np.einsum("hl,lhd->hd", p, v))
    return np.stack(out)


@pytest.mark.parametrize("window", [WINDOW, 10 ** 6], ids=["window", "full"])
@pytest.mark.parametrize("ps", [4, 8])
def test_window_paged_decode_against_masked_attention(window, ps):
    """Rows shorter than, as long as and longer than the window, ending
    on and off a page's edge."""
    from paddle_tpu.ops.paged_attention import paged_decode_mha

    lens = [3, 16, 17, 30, 48]
    q, pool, vool, table = _paged_setup(lens, ps=ps)
    got = paged_decode_mha(jnp.asarray(q), jnp.asarray(pool),
                           jnp.asarray(vool), jnp.asarray(table),
                           jnp.asarray(lens, jnp.int32),
                           window=None if window > 48 else window)
    np.testing.assert_allclose(
        got, _dense_window_decode(q, pool, vool, table, lens, window),
        atol=2e-5)


def test_window_paged_decode_visits_only_the_windows_pages():
    """Pages wholly under a row's window are unmapped (-1, which the
    kernel aims at page 0) and page 0 is NaN: a kernel that computed them
    and masked afterwards would return NaN."""
    from paddle_tpu.ops.paged_attention import paged_decode_mha

    lens = [30, 48, 9]
    q, pool, vool, table = _paged_setup(lens)
    want = _dense_window_decode(q, pool, vool, table, lens, WINDOW)
    pool[0], vool[0] = np.nan, np.nan
    for r, ln in enumerate(lens):
        table[r, :max(ln - WINDOW, 0) // 4] = -1
        table[r, -(-ln // 4):] = -1
    got = paged_decode_mha(jnp.asarray(q), jnp.asarray(pool),
                           jnp.asarray(vool), jnp.asarray(table),
                           jnp.asarray(lens, jnp.int32), window=WINDOW)
    assert not bool(jnp.isnan(got).any())
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- what the engine refuses, and the configuration's file ---------------------------
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


# the keys of the catalog row's ``config`` (model-configs guide,
# architectures.jsonl, "Trinity-Mini"); checked against the catalog where
# it is installed
CATALOG_KEYS = (
    "global_attn_every_n_layers", "head_dim", "hidden_act", "hidden_size",
    "intermediate_size", "layer_types", "load_balance_coeff",
    "max_position_embeddings", "model_type", "moe_intermediate_size",
    "mup_enabled", "n_group", "num_attention_heads", "num_dense_layers",
    "num_expert_groups", "num_experts", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "num_limited_groups",
    "num_shared_experts", "rms_norm_eps", "rope_scaling", "rope_theta",
    "route_norm", "route_scale", "score_func", "sliding_window",
    "tie_word_embeddings", "topk_group", "use_grouped_mm", "vocab_size")


def test_config_file_is_the_catalog_row_and_every_key_a_field():
    """benchmark/run.py:build_config passes config_class only the keys it
    has fields for and drops the rest in silence: every shape key of the
    file must be a field and read back unchanged."""
    import dataclasses

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini.json")) as f:
        cfg_file = json.load(f)
    names = {f.name for f in dataclasses.fields(AfmoeConfig)}
    cfg = AfmoeConfig(**{k: v for k, v in cfg_file.items() if k in names})
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    shape_keys = set(CATALOG_KEYS)
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Trinity-Mini")
        assert shape_keys == set(row["config"])
        for key, value in row["config"].items():
            if key in cfg_file["reduced"]:
                assert cfg_file["published"][key] == value
            else:
                assert cfg_file[key] == value, key
    for key in shape_keys:
        assert key in names, f"{key} is not a field of AfmoeConfig"
        assert getattr(cfg, key) == cfg_file[key], key
    assert cfg_file["layer_types"] == \
        cfg_file["published"]["layer_types"][:cfg_file["num_hidden_layers"]]


def test_the_two_copies_of_the_reference_are_identical():
    with open(REF_PATH, "rb") as a, open(os.path.join(
            ROOT, "benchmark", "reference", "moe_window_decoder.py"),
            "rb") as b:
        assert a.read() == b.read()
