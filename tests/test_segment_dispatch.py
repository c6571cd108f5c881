"""The engine's steady loop dispatches nothing eagerly and blocks once
(PR 31): every device computation between two decode segments is inside
one of the engine's compiled programs.

- the loop (cold admission, segments, a retirement, a second admission)
  compiles and runs only ``prefill_one``, ``admit_state`` and
  ``segment``: after ``jax.clear_caches()`` every program that runs has
  to compile, so ``jax.log_compiles`` names every program that ran;
- the key a segment program makes inside is bit for bit the eager
  ``fold_in(PRNGKey(seed), n)``; two segments draw different noise, two
  engines with one seed agree;
- the first token, sampled inside ``cb_admit_state``, is
  ``CausalLMEngine``'s in a cold, a warm and a chunked admission, and a
  request whose first token is its eos retires at admission;
- the same for the tiny sparse-expert, window-attention model's cold
  path.
"""
import contextlib
import logging
import re

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.generation import (CausalLMEngine,
                                             GenerationConfig, _segment_key,
                                             _u32)
from paddle_tpu.models import LlamaForCausalLM, llama_config
from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM

from engine_helpers import paged_engine

ENGINE_PROGRAMS = {"prefill_one", "admit_state", "segment"}


def dense_model():
    paddle.seed(0)
    model = LlamaForCausalLM(llama_config(
        "tiny", num_hidden_layers=2, num_key_value_heads=2))
    model.eval()
    return model


def sparse_model():
    paddle.seed(3)
    model = AfmoeForCausalLM(AfmoeConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        sliding_window=16, num_dense_layers=1, num_experts=8,
        num_experts_per_tok=2,
        layer_types=["sliding_attention", "full_attention",
                     "sliding_attention", "full_attention"]))
    model.eval()
    return model


MODELS = {"dense": dense_model, "afmoe": sparse_model}


def engine_for(kind, **kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", [16, 64])
    return paged_engine(MODELS[kind](), **kw)


def prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 250, (n,)).astype(
        np.int32)


@contextlib.contextmanager
def compiled_names():
    """The names of the programs JAX compiles inside the block."""
    names = []

    class Handler(logging.Handler):
        def emit(self, record):
            m = re.match(r"Compiling jit\((\w+)\)", record.getMessage())
            if m:
                names.append(m.group(1))

    handler, log = Handler(), logging.getLogger("jax")
    log.addHandler(handler)
    try:
        with jax.log_compiles():
            yield names
    finally:
        log.removeHandler(handler)


def steady_loop(eng, sampled=False):
    """A cold admission, segments until the short request retires, a
    second admission into the freed capacity, segments to the end."""
    kw = dict(do_sample=True, temperature=0.9, top_k=8, seed=4) \
        if sampled else {}
    eng.add_request(prompt(9, 1), GenerationConfig(max_new_tokens=5, **kw))
    eng.add_request(prompt(20, 2), GenerationConfig(max_new_tokens=14))
    retired = False
    for _ in range(8):
        eng.decode_segment(2)
        if eng.collect_finished() and not retired:
            retired = True
            eng.add_request(prompt(12, 3),
                            GenerationConfig(max_new_tokens=4, **kw))
    assert retired and not eng._slot_req


# -- (a), (d): the programs of the steady loop ---------------------------------
@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("kind", ["dense", "afmoe"])
def test_steady_loop_runs_only_the_engines_programs(kind, sampled):
    """Everything the loop compiles after ``jax.clear_caches()`` is one
    of the engine's three programs: no key program, no scatter, no
    sampling chain of eager operations."""
    eng = engine_for(kind)
    jax.clear_caches()
    with compiled_names() as names:
        steady_loop(eng, sampled)
    eng.close()
    assert ENGINE_PROGRAMS <= set(names)        # the log was read
    assert set(names) <= ENGINE_PROGRAMS, sorted(
        set(names) - ENGINE_PROGRAMS)


@pytest.mark.parametrize("kind", ["dense", "afmoe"])
def test_warm_engine_compiles_nothing_in_the_loop(kind):
    """``warmup`` ran every program of the loop with the types and
    placement the loop hands them: nothing compiles afterwards."""
    eng = engine_for(kind)
    eng.warmup(2)
    with compiled_names() as names:
        steady_loop(eng, sampled=True)
    eng.close()
    assert names == []


# -- (b): the key inside the programs ------------------------------------------
@pytest.mark.parametrize("counter", [1, 2, 977, 2 ** 31 + 3])
@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 - 1, 2 ** 31, -3,
                                  2900000107, 2 ** 40 + 17])
def test_in_program_key_is_the_eager_key(seed, counter):
    want = jax.random.fold_in(jax.random.PRNGKey(seed), counter)
    got = jax.jit(_segment_key)(_u32(seed), _u32(counter))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _sampled_run(kind, seed):
    eng = engine_for(kind, max_batch=2)
    cfg = GenerationConfig(max_new_tokens=13, do_sample=True,
                           temperature=1.5, seed=seed)
    rid = eng.add_request(prompt(10, 5), cfg)
    eng.decode_segment(6, cfg)
    eng.decode_segment(6, cfg)
    toks = eng.collect_finished()[rid]
    eng.close()
    return toks


@pytest.mark.parametrize("kind", ["dense", "afmoe"])
def test_segments_draw_fresh_noise_and_engines_agree(kind):
    """A sampled row at a high temperature: the second segment's six
    tokens are not the first's (the counter folds in), and a second
    engine with the same seed gives the same 13 tokens."""
    a = _sampled_run(kind, seed=11)
    b = _sampled_run(kind, seed=11)
    np.testing.assert_array_equal(a, b)
    assert a[1:7].tolist() != a[7:13].tolist()
    assert _sampled_run(kind, seed=12).tolist() != a.tolist()


# -- (c), (d): the first token --------------------------------------------------
def _reference_first(kind, model, ids):
    if kind == "dense":
        out = CausalLMEngine(model, max_batch=1, max_len=64).generate(
            ids[None], GenerationConfig(max_new_tokens=1))
        return int(np.asarray(out)[0, -1])
    logits = model(paddle.to_tensor(ids[None])).value
    return int(np.asarray(logits)[0, -1].argmax())


def _admit(eng, how, ids, cfg):
    """Admit ``ids`` cold, warm (its prefix resident) or in chunks;
    returns the request id."""
    if how == "chunked":
        adm = eng.begin_admit(ids, cfg)
        while not eng.admit_chunk(adm):
            pass
        return adm.rid
    if how == "warm":
        # a first request leaves the prompt's full blocks resident
        first = eng.add_request(ids, GenerationConfig(max_new_tokens=1))
        assert first in eng.collect_finished()
        hits = eng.alloc.prefix_hits
        rid = eng.add_request(ids, cfg)
        assert eng.alloc.prefix_hits == hits + 1
        return rid
    return eng.add_request(ids, cfg)


ADMISSIONS = [("dense", "cold", {}),
              ("dense", "warm", {"prefix_cache": True}),
              ("dense", "chunked", {"prefill_chunk": 8}),
              ("afmoe", "cold", {})]


@pytest.mark.parametrize("kind,how,kw", ADMISSIONS,
                         ids=[f"{k}-{h}" for k, h, _ in ADMISSIONS])
def test_first_token_is_the_references(kind, how, kw):
    eng = engine_for(kind, **kw)
    ids = prompt(21, 7)
    want = _reference_first(kind, eng.model, ids)
    rid = _admit(eng, how, ids, GenerationConfig(max_new_tokens=4))
    assert eng.partial_tokens(rid) == [want]
    assert rid in eng._slot_req.values()
    eng.close()


@pytest.mark.parametrize("kind,how,kw", ADMISSIONS,
                         ids=[f"{k}-{h}" for k, h, _ in ADMISSIONS])
def test_first_token_eos_retires_at_admission(kind, how, kw):
    """The eos verdict comes out of the same program as the token: the
    request is finished when the admission returns, its slot and pages
    are free, and no segment ran."""
    eng = engine_for(kind, **kw)
    ids = prompt(21, 7)
    eos = _reference_first(kind, eng.model, ids)
    free = eng.alloc.free_pages
    rid = _admit(eng, how, ids, GenerationConfig(max_new_tokens=4,
                                                 eos_token_id=eos))
    assert not eng._slot_req and eng.free_slots() == eng.max_batch
    np.testing.assert_array_equal(eng.collect_finished()[rid], [eos])
    if how != "warm":       # a warm engine parks the prompt's blocks
        assert eng.alloc.free_pages == free
    assert eng._segments_run == 0
    # the slot serves the next request: the retired one left no flag
    nxt = eng.add_request(prompt(9, 8), GenerationConfig(max_new_tokens=3))
    eng.decode_segment(4)
    assert len(eng.collect_finished()[nxt]) == 3
    eng.close()


def test_retirement_writes_nothing_on_the_device():
    """The live mask is the host's: a retired sampled request leaves its
    slot's device flags as they were, and the next all-greedy segment
    still takes `_sample_rows`' greedy branch (its tokens are the
    reference's)."""
    eng = engine_for("dense", max_batch=2)
    hot = GenerationConfig(max_new_tokens=2, do_sample=True, seed=3)
    eng.add_request(prompt(8, 1), hot)
    eng.decode_segment(2)
    assert not eng._slot_req
    assert bool(np.asarray(eng.samp["sample"])[0])      # never reset
    assert eng._active_mask().tolist() == [False, False]
    ids = prompt(15, 2)
    ref = CausalLMEngine(eng.model, max_batch=1, max_len=64).generate(
        ids[None], GenerationConfig(max_new_tokens=6))
    rid = eng.add_request(ids, GenerationConfig(max_new_tokens=6))
    assert eng._active_mask().tolist() == [True, False]
    eng.decode_segment(8)
    np.testing.assert_array_equal(eng.collect_finished()[rid],
                                  np.asarray(ref)[0, -6:])
    eng.close()
