"""The latent-attention decoder with the learned sparse-attention indexer
and group-limited routed experts (models/deepseek_v32.py) against its plain
reference, at small widths in float32 with unrelated experts: the model's
forward, the served path (fused admission, then paged decode over latent
pages), the indexer's kernel and the exact selection, absorbed against
expanded attention, grouped routing, the share of an expert-parallel
layer, YaRN, and what the engine refuses with such a model."""
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.generation import (GenerationConfig,
                                             PagedContinuousBatchingEngine,
                                             _pad_ids)
from paddle_tpu.models.deepseek_v32 import (DeepseekV32Config,
                                            DeepseekV32ForCausalLM,
                                            yarn_inv_freq, yarn_mscale)
from paddle_tpu.nn.layer.routed_experts import RoutedExperts, route_top_k
from paddle_tpu.ops import sparse_latent_attention as sla
from paddle_tpu.ops.sparse_latent_attention import (dsa_index_scores,
                                                    index_scores,
                                                    selected_attention,
                                                    top_k_mask)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_PATH = os.path.join(ROOT, "tests", "reference_mla_dsa_decoder.py")
TOPK, PAGE = 8, 4
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "yarn"}
PUBLISHED_YARN = dict(YARN, original_max_position_embeddings=4096)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(REF_PATH, "reference_mla_dsa_decoder")


def tiny_config(**over):
    kw = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
              moe_intermediate_size=32, num_hidden_layers=3,
              num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
              kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
              v_head_dim=16, index_n_heads=16, index_head_dim=16,
              index_topk=TOPK, first_k_dense_replace=1, n_routed_experts=16,
              ep_size=4, ep_rank=1, num_experts_per_tok=4, n_group=4,
              topk_group=2, rope_scaling=YARN)
    kw.update(over)
    return DeepseekV32Config(**kw)


def tiny_model(seed=3, **over):
    cfg = tiny_config(**over)
    paddle.seed(seed)
    model = DeepseekV32ForCausalLM(cfg)
    model.eval()
    # the model's own experts start EXPERT_SPREAD apart; a test of the
    # routing wants experts that have nothing in common, a bias that
    # moves the choice, and a LayerNorm whose bias is not 0
    rs = np.random.RandomState(seed)
    for name, p in model.named_parameters():
        if ".experts." in name and name.endswith("_proj"):
            a = np.sqrt(6.0 / sum(p.shape[1:]))
            p.set_value(jnp.asarray(rs.uniform(-a, a, p.shape),
                                    p.value.dtype))
        if name.endswith(("expert_bias", "k_norm.bias")):
            p.set_value(jnp.asarray(rs.uniform(-0.1, 0.1, p.shape),
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
@pytest.mark.parametrize("seq", [6, TOPK, 40])
def test_forward_matches_reference(seq):
    """Shorter than ``index_topk`` (every position attended), as long, and
    well past it (the selection acts on 32 of 40 queries); one dense and
    two expert layers, this chip holding experts 4-7 of 16."""
    cfg, model, params = tiny_model()
    ids = _ids(seq, seed=seq)
    want = ref.forward(params.__getitem__, cfg, ids)
    got = model(paddle.to_tensor(ids)).value
    assert got.shape == want.shape == (1, seq, cfg.vocab_size)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_selection_changes_the_logits():
    """The test above would pass with the indexer ignored if the selection
    chose nothing away: with ``index_topk`` past the sequence the logits
    of the late positions differ."""
    cfg, model, _ = tiny_model()
    _, whole, _ = tiny_model(index_topk=64)
    ids = paddle.to_tensor(_ids(40, seed=40))
    a, b = model(ids).value[0], whole(ids).value[0]
    np.testing.assert_allclose(a[:TOPK], b[:TOPK], atol=1e-6)
    assert float(jnp.abs(a[TOPK:] - b[TOPK:]).max()) > 1e-3


# -- the served path --------------------------------------------------------------
@pytest.mark.parametrize("plen", [TOPK - 5, TOPK, TOPK + 9])
def test_fused_admission_then_paged_decode_matches_reference(plen):
    """A prompt under, at and over ``index_topk`` goes through the ONE
    fused admission program (bucket padding included) into the latent
    pages; then 11 teacher-forced decode steps through the engine's step,
    across ``index_topk`` and several pages' edges. The logits of every
    position are the reference's full forward's."""
    cfg, model, params = tiny_model()
    eng = tiny_engine(model)
    steps = 11
    ids = _ids(plen + steps, seed=plen)
    want = ref.forward(params.__getitem__, cfg, ids)[0]

    eng.alloc.ensure(0, plen + steps)
    width = eng._prefill_width(plen)
    assert width > plen or plen == TOPK       # padding is exercised
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
        assert int(aux["ctx_tokens_selected"]) == min(plen + i + 1, TOPK)
        # one live row: what landed on this chip's 4 experts of 16
        assert 0 <= int(aux["expert_rows_here"]) \
            <= 2 * cfg.num_experts_per_tok
        assert int(aux["experts_hit"]) == int(aux["expert_rows_here"])
    eng.close()


def test_engine_serves_two_rows_and_counts():
    """Through add_request / decode_segment with two rows of different
    lengths in flight: every served token is the reference's argmax, and
    the segment's span carries the new counters, which add up."""
    from paddle_tpu import tracing

    cfg, model, params = tiny_model()
    eng = tiny_engine(model)
    prompts = [_ids(TOPK + 6, seed=1), _ids(5, seed=2)]
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
    assert first["rows"] == 2 and first["steps"] == 4
    assert first["ctx_tokens"] == TOPK + 6 + 1 + 5 + 1
    # one table: the pages the two contexts span, of the table's
    assert first["pages_live"] == -(-(TOPK + 7) // PAGE) + -(-6 // PAGE)
    assert first["pages_table"] == 2 * 16
    # summed over the segment's 4 steps: the long row attends index_topk
    # positions a step, the short one its whole context (6, 7, then 8)
    assert first["ctx_tokens_selected"] == 4 * TOPK + (6 + 7 + 8 + 8)
    # (row, choice) pairs: 2 rows x 4 steps x 2 expert layers x 4 choices
    # in the layer, of which this chip's experts take their part
    assert 0 < first["expert_rows_here"] < 2 * 4 * 2 * 4
    assert first["experts_hit"] <= first["expert_rows_here"]
    assert 0 < first["expert_rows_max"] <= 2 * 4 * 2
    pre = [e for e in events if e["phase"] == "engine.prefill"]
    assert [(p["plen"], p["bucket"], p["fused"]) for p in pre] == [
        (TOPK + 6, 16, 1), (5, 8, 1)]
    assert eng.alloc.used_pages == 0
    eng.close()


def test_preempted_and_replayed_row_gives_the_same_tokens():
    """A row preempted mid-decode and re-admitted as prompt + generated
    (the scheduler's replay) continues exactly where an undisturbed run
    goes: the prefill's selection and the decode's agree."""
    cfg, model, _ = tiny_model()
    gen = GenerationConfig(max_new_tokens=14, do_sample=False)
    prompt = _ids(TOPK + 3, seed=7)

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


def test_logits_are_float32_and_bf16_pools_hold_no_heads():
    cfg, model, _ = tiny_model(dtype="bfloat16")
    logits = model(paddle.to_tensor(_ids(6)))
    assert logits.value.dtype == jnp.float32
    pools = model.init_paged_cache(8, PAGE)
    assert len(pools) == cfg.num_hidden_layers
    rows, keys = pools[0]
    assert rows.shape == (8, PAGE, cfg.cache_row) and cfg.cache_row == 128
    assert keys.shape == (8, PAGE, cfg.index_head_dim)
    assert rows.dtype == keys.dtype == jnp.bfloat16
    # the published widths: a 576-wide row stored 640 wide, 1,536 B a token a layer
    wide = DeepseekV32Config()
    assert wide.cache_row == 640 and wide.kv_lora_rank + wide.qk_rope_head_dim == 576
    assert 2 * (wide.cache_row + wide.index_head_dim) == 1536


# -- the indexer -------------------------------------------------------------------
def _paged_keys(lens, ps=4, maxp=6, d=16, seed=0, dtype=jnp.float32):
    """Each row's pages scattered over a pool in a shuffled order."""
    rs = np.random.RandomState(seed)
    b = len(lens)
    pool = rs.randn(b * maxp + 3, ps, d).astype(np.float32)
    order = rs.permutation(b * maxp + 3)
    table = np.full((b, maxp), -1, np.int32)
    for r, ln in enumerate(lens):
        n = -(-ln // ps)
        table[r, :n] = order[r * maxp:r * maxp + n]
    return jnp.asarray(pool, dtype), jnp.asarray(table)


def _dense_scores(q, w, pool, table, lens):
    pool, table = np.asarray(pool, np.float64), np.asarray(table)
    out = []
    for r, ln in enumerate(lens):
        keys = pool[np.maximum(table[r], 0)].reshape(-1, pool.shape[-1])
        dots = np.maximum(np.asarray(q[r], np.float64) @ keys.T, 0.0)
        out.append((np.asarray(w[r], np.float64)[:, None] * dots).sum(0))
    return np.stack(out)


@pytest.mark.parametrize("lens", [(9, 0, 24), (1, 17, 4)])
def test_index_scores_kernel_against_dense(lens):
    """The kernel over shuffled pages, a dead row between live ones, a
    length at a page's edge and a full table."""
    h, d = 4, 16
    rs = np.random.RandomState(sum(lens))
    pool, table = _paged_keys(lens, d=d)
    q = jnp.asarray(rs.randn(len(lens), h, d), jnp.float32)
    w = jnp.asarray(rs.randn(len(lens), h), jnp.float32)
    got = dsa_index_scores(q, w, pool, table, jnp.asarray(lens, jnp.int32))
    want = _dense_scores(q, w, pool, table, lens)
    assert got.shape == (len(lens), table.shape[1] * pool.shape[1])
    for r, ln in enumerate(lens):
        np.testing.assert_allclose(got[r, :ln], want[r, :ln], atol=1e-5)
    # a dead row costs no block: nothing of it was scored
    dead = [r for r, ln in enumerate(lens) if ln == 0]
    assert all(float(got[r].max()) <= -1e29 for r in dead)
    assert not bool(jnp.isnan(got).any())


def test_index_scores_of_a_bf16_query_are_the_rounded_querys():
    """The served dtype: a bf16 query against bf16 keys. The products are
    exact in float32, so the kernel gives the dense scores of the query as
    rounded, and the prefill's one-product form the same."""
    lens, h, d = (24, 13), 4, 16
    rs = np.random.RandomState(11)
    pool, table = _paged_keys(lens, d=d, dtype=jnp.bfloat16)
    q = jnp.asarray(rs.randn(2, h, d), jnp.bfloat16)
    w = jnp.asarray(rs.randn(2, h), jnp.float32)
    got = dsa_index_scores(q, w, pool, table, jnp.asarray(lens, jnp.int32))
    want = _dense_scores(q.astype(jnp.float32), w,
                         pool.astype(jnp.float32), table, lens)
    for r, n in enumerate(lens):
        np.testing.assert_allclose(got[r, :n], want[r, :n], atol=2e-5)
        keys = pool[jnp.maximum(table[r], 0)].reshape(-1, d)[:n]
        np.testing.assert_allclose(
            index_scores(q[r][None], w[r][None], keys)[0], want[r, :n],
            atol=2e-5)


@pytest.mark.parametrize("k", [1, 5, 64])
def test_top_k_mask_is_the_sorts(k):
    rs = np.random.RandomState(k)
    x = rs.randn(7, 64).astype(np.float32)
    x[2, 10:] = -np.inf                       # fewer than k above -inf
    x[3] = np.abs(x[3])
    x[4] = -np.abs(x[4])
    x[5, ::2] = 0.0
    x[5, 1::4] = -0.0
    ks = np.full((7,), k, np.int32)
    ks[6] = 3
    got = np.asarray(top_k_mask(jnp.asarray(x), jnp.asarray(ks)))
    for r in range(7):
        kth = np.sort(x[r])[::-1][ks[r] - 1]
        want = x[r] >= kth if np.isfinite(kth) else np.ones(64, bool)
        if r == 5:      # -0.0 sorts under 0.0 by its bits: ties aside
            want = got[r]
            assert got[r].sum() >= ks[r]
        np.testing.assert_array_equal(got[r], want)


@pytest.mark.parametrize("masked, last, h, s, dtype, selection", [
    (True, 1535, 4, 1536, "float32", "random"),
    (True, 300, 4, 1536, "float32", "random"),
    (False, 700, 4, 1536, "float32", "random"),
    # queries of the later blocks attend nothing of the first key block
    # and something later: nothing attended yet must stay nothing
    (True, 1535, 4, 1536, "float32", "first_block_empty"),
    # a mask that is not causal: the diagonal's block still applies it
    (True, 1535, 4, 1536, "float32", "everything"),
    # ``last`` on a sub-tile's edge and one short of it
    (True, 768, 4, 1536, "float32", "random"),
    (True, 767, 4, 1536, "float32", "random"),
    (False, 256, 4, 1536, "float32", "random"),
    (False, 255, 4, 1536, "float32", "random"),
    # heads that are no multiple of the heads a grid step
    (True, 1535, 6, 1536, "float32", "random"),
    (False, 1535, 3, 1536, "float32", "random"),
    # one block: of four sub-tiles, of two, of one, of less than a lane tile
    (True, 1023, 4, 1024, "float32", "random"),
    (True, 511, 4, 512, "float32", "random"),
    (False, 383, 4, 384, "float32", "random"),
    (True, 71, 2, 72, "float32", "random"),
    # two blocks of 1024
    (True, 2047, 2, 2048, "float32", "random"),
    (False, 1100, 2, 2048, "float32", "random"),
    # bf16 operands against the float32 reference
    (True, 1535, 4, 1536, "bfloat16", "random"),
    (False, 700, 4, 1536, "bfloat16", "random"),
])
def test_selected_attention_kernel_against_masked_softmax(masked, last, h, s,
                                                          dtype, selection):
    """Blocks of queries by blocks of keys, keys wider than values: the
    online softmax over key blocks under a random selection (a query may
    attend nothing of a whole block), the block above the diagonal skipped,
    and a block of queries past ``last`` left at zero."""
    dk, dv, dtype = 24, 16, jnp.dtype(dtype)
    rs = np.random.RandomState(last)
    q, k = (jnp.asarray(rs.randn(h, s, dk), dtype) for _ in range(2))
    v = jnp.asarray(rs.randn(h, s, dv), dtype)
    causal = np.tril(np.ones((s, s), bool))
    chosen = rs.rand(s, s) < 0.3
    chosen[np.arange(s), np.arange(s)] = True      # a query attends itself
    chosen[600:, :512] &= rs.rand(max(s - 600, 0), 1) < 0.5
    if selection == "first_block_empty":
        chosen[512:, :512] = False
    elif selection == "everything":
        chosen[:] = True
    mask = jnp.asarray(chosen, jnp.int8) if masked else None
    qs = (q.astype(jnp.float32) * 0.2).astype(dtype)   # the scale rides in q
    got = selected_attention(qs, k, v, mask, jnp.int32(last))
    assert got.dtype == dtype
    keep = causal & chosen if masked else causal
    sc = jnp.einsum("hqd,hkd->hqk", qs.astype(jnp.float32),
                    k.astype(jnp.float32), precision="highest")
    p = jax.nn.softmax(jnp.where(keep[None], sc, -jnp.inf), axis=-1)
    want = jnp.einsum("hqk,hkv->hqv", p, v.astype(jnp.float32),
                      precision="highest")
    blk = sla._tiling(s, h)[0]
    done = blk * (last // blk + 1)      # whole blocks of queries computed
    # bf16: p and the output are rounded to 8 bits of mantissa
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got[:, :done].astype(jnp.float32),
                               want[:, :done], atol=atol)
    assert float(jnp.abs(got[:, done:]).max(initial=0.0)) == 0.0


def test_selected_attention_of_a_query_that_attends_nothing_is_zero():
    """Rows of the mask that are all False (a bucket's padding inside a
    computed block) give zeros, not the mean of the values."""
    h, s = 2, 1536
    rs = np.random.RandomState(0)
    q, k = (jnp.asarray(rs.randn(h, s, 24), jnp.float32) for _ in range(2))
    v = jnp.asarray(rs.randn(h, s, 16), jnp.float32)
    chosen = np.tril(rs.rand(s, s) < 0.3)
    chosen[np.arange(s), np.arange(s)] = True
    chosen[700:] = False
    got = selected_attention(q, k, v, jnp.asarray(chosen, jnp.int8),
                             jnp.int32(699))
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got[:, 700:]).max()) == 0.0
    assert float(jnp.abs(got[:, :700]).max()) > 0.0


def test_absorbed_decode_equals_expanded_attention():
    """One layer's attention on the same input: the prefill (expanded
    heads, selection's mask) and the decode over pages (absorbed
    up-projection, chosen rows gathered) give the same output at every
    position, under and over ``index_topk``."""
    cfg, model, _ = tiny_model()
    attn = model.model.layers[1].self_attn
    n, ps, maxp = 21, PAGE, 8
    x = jnp.asarray(np.random.RandomState(4).randn(1, n, cfg.hidden_size),
                    jnp.float32) * 0.5
    cache = model.init_cache(1, n)[0]
    with paddle.no_grad():
        want, _ = attn.forward_with_cache(paddle.to_tensor(x), cache)
    want = want.value[0]
    pools = model.init_paged_cache(16, ps)[0]
    table = jnp.asarray(
        np.random.RandomState(5).permutation(16)[:maxp][None], jnp.int32)
    for t in range(n):
        with paddle.no_grad():
            out, pools = attn.forward_decode_paged(
                paddle.to_tensor(x[:, t:t + 1]), pools, table,
                jnp.asarray([t], jnp.int32), jnp.asarray([True]))
        np.testing.assert_allclose(out.value[0, 0], want[t], atol=2e-5,
                                   err_msg=f"position {t}")


# -- the expert layer ---------------------------------------------------------------
def _loop_route(scores, bias, k, n_group, topk_group, scale):
    """A token at a time, in numpy: group-limited top-k."""
    sel, wts = [], []
    per = scores.shape[1] // n_group
    for s in scores:
        g = s + bias
        group_score = [np.sort(g[i * per:(i + 1) * per])[-2:].sum()
                       for i in range(n_group)]
        kept = np.argsort(group_score)[::-1][:topk_group]
        allowed = [e for i in kept for e in range(i * per, (i + 1) * per)]
        chosen = sorted(allowed, key=lambda e: -g[e])[:k]
        sel.append(chosen)
        w = s[chosen]
        wts.append(scale * w / (w.sum() + 1e-20))
    return np.asarray(sel), np.asarray(wts)


def test_group_limited_routing_against_a_per_token_loop():
    t, h, e, k = 40, 32, 16, 4
    rs = np.random.RandomState(9)
    x = jnp.asarray(rs.randn(t, h), jnp.float32)
    router = jnp.asarray(rs.randn(h, e) * 0.3, jnp.float32)
    bias = jnp.asarray(rs.randn(e) * 0.2, jnp.float32)
    sel, w = route_top_k(x, router, bias, k, 2.5, True, n_group=4,
                         topk_group=2)
    scores = 1 / (1 + np.exp(-(np.asarray(x, np.float64)
                               @ np.asarray(router, np.float64))))
    want_sel, want_w = _loop_route(scores, np.asarray(bias, np.float64), k,
                                   4, 2, 2.5)
    np.testing.assert_array_equal(np.sort(np.asarray(sel), -1),
                                  np.sort(want_sel, -1))
    order = np.argsort(np.asarray(sel), -1)
    want_order = np.argsort(want_sel, -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, -1),
        np.take_along_axis(want_w, want_order, -1), rtol=1e-5)
    # the limit binds: plain top-k of 16 chooses otherwise for some token
    plain, _ = route_top_k(x, router, bias, k, 2.5, True)
    assert (np.sort(np.asarray(plain), -1)
            != np.sort(np.asarray(sel), -1)).any()
    # every choice lies in at most 2 of the 4 groups of 4
    assert all(len({e // 4 for e in row}) <= 2 for row in np.asarray(sel))


def test_one_group_is_todays_routing_bit_for_bit():
    """``n_group`` 1 adds nothing to the program: the same jaxpr as with
    the arguments left out, so the same bits."""
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(12, 32), jnp.float32)
    router = jnp.asarray(rs.randn(32, 8), jnp.float32)
    bias = jnp.asarray(rs.randn(8) * 0.1, jnp.float32)

    def old(x, r, b):
        return route_top_k(x, r, b, 2, 2.826, True)

    def new(x, r, b):
        return route_top_k(x, r, b, 2, 2.826, True, n_group=1, topk_group=1)

    assert str(jax.make_jaxpr(old)(x, router, bias)) \
        == str(jax.make_jaxpr(new)(x, router, bias))
    for a, b in zip(old(x, router, bias), new(x, router, bias)):
        np.testing.assert_array_equal(a, b)


def test_the_shares_add_up():
    """The routed parts of all ``ep_size`` ranks, each holding its slice
    of the experts, plus the shared expert once, are the uncut layer of the
    reference (``ep_size`` 1, every expert held)."""
    h, m, e, k, ranks = 32, 16, 16, 4, 4
    rs = np.random.RandomState(13)
    x = jnp.asarray(rs.randn(24, h), jnp.float32)
    full = {"router": rs.randn(h, e) * 0.3, "bias": rs.randn(e) * 0.1,
            "gate": rs.randn(e, h, m) * 0.2, "up": rs.randn(e, h, m) * 0.2,
            "down": rs.randn(e, m, h) * 0.2}
    full = {n: jnp.asarray(v, jnp.float32) for n, v in full.items()}
    shared = [jnp.asarray(rs.randn(*s) * 0.2, jnp.float32)
              for s in ((h, m), (h, m), (m, h))]
    total, rows_here = 0, 0
    for rank in range(ranks):
        paddle.seed(0)
        layer = RoutedExperts(h, m, e, k, route_scale=2.5, n_group=4,
                              topk_group=2, held=(rank * 4, 4))
        hold = slice(rank * 4, rank * 4 + 4)
        layer.router.set_value(full["router"])
        layer.expert_bias.set_value(full["bias"])
        layer.gate_proj.set_value(full["gate"][hold])
        layer.up_proj.set_value(full["up"][hold])
        layer.down_proj.set_value(full["down"][hold])
        out, stats = layer(paddle.to_tensor(x))
        total = total + out.value
        rows_here += int(stats["expert_rows_here"])
        assert int(stats["experts_hit"]) <= 4
    assert rows_here == 24 * k           # every choice landed on one rank
    weights = ref.route(x, full["router"], full["bias"], k, 4, 2, 2.5, True)
    want = ref.swiglu(x, *shared) + ref.experts(
        x, weights, full["gate"], full["up"], full["down"])
    np.testing.assert_allclose(ref.swiglu(x, *shared) + total, want,
                               atol=2e-5)


def test_a_prefill_routes_in_token_blocks():
    """More (token, choice) rows than ``ROWS_BYTES`` holds go block by
    block: the same output, counters added (the busiest expert's rows: the
    largest block's). At the two served widths: 8,192 tokens of hidden 2048
    go whole, 16,384 of hidden 7168 in blocks of 2,048."""
    assert RoutedExperts(2048, 8, 8, 8).token_block == 8192
    assert RoutedExperts(7168, 8, 8, 8).token_block == 2048
    paddle.seed(1)
    whole = RoutedExperts(32, 16, 8, 2, held=(2, 4))
    paddle.seed(1)
    blocks = RoutedExperts(32, 16, 8, 2, held=(2, 4))
    blocks.token_block = 8
    x = paddle.to_tensor(jnp.asarray(
        np.random.RandomState(3).randn(2, 16, 32), jnp.float32))
    valid = jnp.arange(32).reshape(2, 16) % 16 < 13
    a, sa = whole(x, valid=valid)
    b, sb = blocks(x, valid=valid)
    np.testing.assert_allclose(a.value, b.value, atol=1e-6)
    assert int(sa["expert_rows_here"]) == int(sb["expert_rows_here"])
    assert int(sb["expert_rows_max"]) <= int(sa["expert_rows_max"])


# -- the prefill's row-wise work under the prompt's extent -------------------------
ROW_BLOCK, WIDTH = 8, 32


def _prefill(model, ids, last, embed=None):
    """The engine's call: logits of ``last`` and every layer's cache."""
    from paddle_tpu.core.autograd import no_grad

    emb = model.model.embed_tokens
    if embed is not None:
        model.model.embed_tokens = lambda i: embed(emb(i))
    try:
        with no_grad():
            logits, caches = model.forward_with_cache(
                paddle.to_tensor(ids), model.init_cache(1, ids.shape[1]), 0,
                last_idx=jnp.int32(last))
    finally:
        model.model.embed_tokens = emb
    return logits.value[0, 0], caches


@pytest.mark.parametrize("last", [2 * ROW_BLOCK - 1, 2 * ROW_BLOCK,
                                  WIDTH - 1, ROW_BLOCK - 5])
def test_prefill_runs_the_prompts_row_blocks_and_no_other(last, monkeypatch):
    """``last_idx`` at a block's last row, its first, the bucket's last and
    inside the first block of a bucket four blocks wide: the logits of that
    position are the reference's of the prompt alone and the bucket-wide
    run's, the cache rows of the prompt are the bucket-wide run's; and the
    blocks past the prompt were not run: NaN in their embeddings reaches
    nothing, and their cache rows are zero."""
    from paddle_tpu.models import deepseek_v32 as dsv

    cfg, model, params = tiny_model()
    for layer in model.model.layers[cfg.first_k_dense_replace:]:
        # an expert layer's FFN goes in blocks as wide as the experts take
        layer.mlp.experts.token_block = 2 * ROW_BLOCK
    ids = _ids(WIDTH, seed=last)
    want = ref.forward(params.__getitem__, cfg, ids[:, :last + 1])[0, last]
    monkeypatch.setattr(dsv, "PREFILL_ROW_BLOCK", WIDTH)
    whole, whole_caches = _prefill(model, ids, last)
    monkeypatch.setattr(dsv, "PREFILL_ROW_BLOCK", ROW_BLOCK)
    ran = (last // ROW_BLOCK + 1) * ROW_BLOCK

    def nan_past_the_blocks(x):
        return paddle.to_tensor(x.value.at[:, ran:].set(jnp.nan))

    for embed in (None, nan_past_the_blocks):
        got, caches = _prefill(model, ids, last, embed)
        np.testing.assert_allclose(got, want, atol=5e-5)
        np.testing.assert_allclose(got, whole, atol=5e-5)
        for (rows, keys), (wrows, wkeys) in zip(caches, whole_caches):
            np.testing.assert_allclose(rows[:, :last + 1],
                                       wrows[:, :last + 1], atol=5e-5)
            np.testing.assert_allclose(keys[:, :last + 1],
                                       wkeys[:, :last + 1], atol=5e-5)
            assert bool(jnp.isfinite(rows).all() & jnp.isfinite(keys).all())
            assert not np.asarray(rows[:, ran:]).any()
            assert not np.asarray(keys[:, ran:]).any()


def test_engine_admits_under_the_row_loop_and_counts_its_rows(monkeypatch):
    """Through the engine with a block a quarter of the bucket: a 9-token
    prompt in the 16 bucket runs 12 rows (``engine.prefill{rows_run}``),
    and the served tokens are the reference's."""
    from paddle_tpu import tracing
    from paddle_tpu.models import deepseek_v32 as dsv

    monkeypatch.setattr(dsv, "PREFILL_ROW_BLOCK", 4)
    cfg, model, params = tiny_model()
    assert model.paged_layout(PAGE)["prefill_row_block"] == 4
    eng = tiny_engine(model)
    prompt = _ids(TOPK + 1, seed=5)
    tracing.enable()
    tracing.clear()
    try:
        rid = eng.add_request(prompt, GenerationConfig(max_new_tokens=6,
                                                       do_sample=False))
        while eng.decode_segment(4):
            pass
        events = tracing.events()
    finally:
        tracing.disable()
    toks = eng.collect_finished()[rid]
    full = np.concatenate([prompt[0], toks[:-1]])[None]
    logits = ref.forward(params.__getitem__, cfg, full, last=6)[0]
    assert float((logits.max(-1) - logits[np.arange(6), toks]).max()) <= 1e-4
    pre, = [e for e in events if e["phase"] == "engine.prefill"]
    assert (pre["plen"], pre["bucket"], pre["rows_run"]) == (9, 16, 12)
    eng.close()


# -- YaRN --------------------------------------------------------------------------
def test_yarn_inv_freq_and_mscale_against_hand_computed_values():
    """rope dim 64, theta 10000, factor 40 over 4096: the dimension that
    turns 32 times in 4096 positions is 10.47 (ramp from 10), the one that
    turns once 22.51 (ramp to 23)."""
    inv = yarn_inv_freq(64, 10000.0, PUBLISHED_YARN)
    base = 10000.0 ** (-np.arange(32) / 32.0)
    assert inv.shape == (32,) and inv.dtype == np.float32
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / (23 - 10)
    np.testing.assert_allclose(
        inv[16], base[16] * (1 - ramp) + base[16] / 40 * ramp, rtol=1e-6)
    np.testing.assert_allclose(inv[16], 0.0100000 * (1 - ramp * 39 / 40),
                               rtol=1e-5)
    np.testing.assert_allclose(inv, ref.inv_freq(64, 10000.0,
                                                 PUBLISHED_YARN), rtol=1e-6)
    assert abs(yarn_mscale(40, 1) - 1.3688879) < 1e-6
    cfg = DeepseekV32Config(rope_scaling=PUBLISHED_YARN)
    want = 192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2
    assert abs(cfg.softmax_scale - want) < 1e-9
    assert abs(cfg.softmax_scale - 0.135234) < 1e-5
    assert abs(ref.softmax_scale(cfg) - want) < 1e-9
    np.testing.assert_allclose(yarn_inv_freq(64, 10000.0, None), base,
                               rtol=1e-6)


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
    with pytest.raises(ValueError, match=named.replace("(", r"\(")) as e:
        tiny_engine(model, **kwargs)
    assert "latent rows" in str(e.value) and "ring" not in str(e.value)


def test_the_engine_reads_the_cache_description_in_one_place():
    """``paged_layout``'s keys each say one thing: this model has one
    table and the plain allocator, is told ``last_idx`` and hands out
    counters."""
    from paddle_tpu.inference.paged_cache import PageAllocator

    _, model, _ = tiny_model()
    layout = model.paged_layout(PAGE)
    assert layout["ring"] is None
    assert layout["last_idx"] and layout["counters"]
    eng = tiny_engine(model)
    assert type(eng.alloc) is PageAllocator
    assert eng._ring is None and eng._prefill_last_idx \
        and eng._step_counters
    eng.close()


def test_config_file_is_the_catalog_row_and_every_key_a_field():
    """benchmark/run.py:build_config passes config_class only the keys it
    has fields for and drops the rest in silence: every key of the catalog
    row must be a field and read back unchanged."""
    import dataclasses

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deepseek-v3.2.json")) as f:
        cfg_file = json.load(f)
    names = {f.name for f in dataclasses.fields(DeepseekV32Config)}
    cfg = DeepseekV32Config(
        **{k: v for k, v in cfg_file.items() if k in names})
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    shape_keys = {
        "attention_bias", "ep_size", "first_k_dense_replace", "hidden_act",
        "hidden_size", "index_head_dim", "index_n_heads", "index_topk",
        "intermediate_size", "kv_lora_rank", "max_position_embeddings",
        "model_type", "moe_intermediate_size", "moe_layer_freq", "n_group",
        "n_routed_experts", "n_shared_experts", "norm_topk_prob",
        "num_attention_heads", "num_experts_per_tok", "num_hidden_layers",
        "num_key_value_heads", "num_nextn_predict_layers", "q_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps",
        "rope_scaling", "rope_theta", "routed_scaling_factor",
        "scoring_func", "tie_word_embeddings", "topk_group", "topk_method",
        "v_head_dim", "vocab_size"}
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "DeepSeek-V3.2")
        assert shape_keys == set(row["config"])
        assert cfg_file["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in cfg_file["reduced"]:
                assert cfg_file["published"][key] == value, key
            else:
                assert cfg_file[key] == value, key
    for key in shape_keys | {"ep_rank", "dtype"}:
        assert key in names, f"{key} is not a field of DeepseekV32Config"
        assert getattr(cfg, key) == cfg_file[key], key
    # no width is reduced, and the share is the deployment's
    assert set(cfg_file["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "ep_size",
        "vocab_size"}
    assert (cfg.n_routed_experts, cfg.experts_held) == (256, 16)
    assert cfg.vocab_size * 8 == cfg_file["published"]["vocab_size"]


def _rms_of(p):
    v = np.asarray(p.value, np.float64)
    return float(np.sqrt(np.mean(v * v)))


@pytest.mark.parametrize("group", ["embedding", "attention",
                                   "routed_down", "untouched"])
def test_the_benchmarks_seeding_rescales_what_it_says(group):
    """``benchmark/lib/seeded_weights.condition`` changes the size of three
    groups of the model's own draws and nothing else; the model class
    itself keeps the Layer API's initialisers."""
    from benchmark.lib import seeded_weights as sw

    cfg = tiny_config()
    paddle.seed(5)
    plain = DeepseekV32ForCausalLM(cfg)
    paddle.seed(5)
    model = sw.condition(DeepseekV32ForCausalLM(cfg))
    before = dict(plain.named_parameters())
    after = dict(model.named_parameters())
    assert before.keys() == after.keys()
    attn = ("q_a_proj", "q_b_proj", "kv_a_proj_with_mqa", "kv_b_proj",
            "o_proj", "indexer.wq_b", "indexer.wk", "indexer.weights_proj")
    seen = 0
    for name, p in after.items():
        if name == "model.embed_tokens.weight":
            kind = "embedding"
            # the Layer API's draw over [vocab, hidden] is far from unit
            assert _rms_of(before[name]) < 0.2
            ok = abs(_rms_of(p) - sw.EMBEDDING_RMS) < 1e-3
        elif name.endswith(tuple(a + ".weight" for a in attn)):
            kind = "attention"
            ok = abs(_rms_of(p) - sw.ATTENTION_RMS) < 1e-5
        elif name.endswith("experts.down_proj"):
            kind = "routed_down"
            ok = abs(_rms_of(p) / _rms_of(before[name])
                     - sw.ROUTED_DOWN_GAIN) < 1e-6
        else:
            kind = "untouched"
            ok = bool(np.array_equal(np.asarray(p.value),
                                     np.asarray(before[name].value)))
        if kind == group:
            seen += 1
            assert ok, name
    assert seen >= (1 if group == "embedding" else 2)


def test_the_cells_model_class_is_the_seeding_of_the_model():
    """The configuration names the benchmark's factory; what it builds is
    the model class with the factory's rescaling, and serves."""
    from benchmark.lib import seeded_weights as sw
    from benchmark.run import resolve

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deepseek-v3.2.json")) as f:
        spec = json.load(f)["model_class"]
    assert resolve(spec) is sw.deepseek_v32
    cfg = tiny_config()
    paddle.seed(9)
    model = sw.deepseek_v32(cfg)
    assert type(model) is DeepseekV32ForCausalLM
    model.eval()
    params = {k: p.value for k, p in model.named_parameters()}
    ids = _ids(24, seed=2)
    np.testing.assert_allclose(model(paddle.to_tensor(ids)).value,
                               ref.forward(params.__getitem__, cfg, ids),
                               atol=2e-5)


def test_the_two_copies_of_the_reference_are_identical():
    with open(REF_PATH, "rb") as a, open(os.path.join(
            ROOT, "benchmark", "reference", "mla_dsa_decoder.py"),
            "rb") as b:
        assert a.read() == b.read()
