"""Ragged / continuous batching decode (VERDICT r3 #6).

The reference decode kernel serves mixed-length batches after
remove_padding (fused_multi_transformer_op.cu.h:1641) with per-sequence
lengths (:1680). The ragged contract of the serving engine
(PagedContinuousBatchingEngine); it must:

1. produce EXACTLY the per-request outputs of CausalLMEngine (greedy),
   regardless of batch composition (rows are independent),
2. admit new requests between decode segments (more requests than slots),
3. keep per-row lengths: rows advance independently, dead rows don't move.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from engine_helpers import paged_engine
from paddle_tpu.inference.generation import (CausalLMEngine,
                                             GenerationConfig)
from paddle_tpu.models import LlamaForCausalLM
from paddle_tpu.models.llama import LlamaConfig


def tiny_model(seed=0):
    np.random.seed(seed)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def prompts_mixed(rng, vocab, lens):
    return [rng.randint(0, vocab, (n,)).astype(np.int32) for n in lens]


class TestRaggedParity:
    def test_mixed_lengths_match_dense_engine(self):
        m = tiny_model()
        rng = np.random.RandomState(3)
        lens = [5, 11, 3, 8]
        prompts = prompts_mixed(rng, 97, lens)
        cfg = GenerationConfig(max_new_tokens=9)

        dense = CausalLMEngine(m, max_batch=1, max_len=64)
        want = [dense.generate(p[None], cfg)[0, len(p):] for p in prompts]

        eng = paged_engine(m, max_batch=4, max_len=64)
        got = eng.serve(prompts, cfg, segment_steps=4)
        for i, (w, g) in enumerate(zip(want, got)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=f"request {i}")

    def test_admission_between_segments(self):
        """5 requests through 2 slots: later requests are admitted only
        after earlier ones retire — outputs must still match the dense
        engine per request."""
        m = tiny_model()
        rng = np.random.RandomState(4)
        lens = [4, 9, 6, 3, 7]
        prompts = prompts_mixed(rng, 97, lens)
        cfg = GenerationConfig(max_new_tokens=6)

        dense = CausalLMEngine(m, max_batch=1, max_len=64)
        want = [dense.generate(p[None], cfg)[0, len(p):] for p in prompts]

        eng = paged_engine(m, max_batch=2, max_len=64)
        got = eng.serve(prompts, cfg, segment_steps=3)
        for i, (w, g) in enumerate(zip(want, got)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=f"request {i}")
        # every slot freed afterwards
        assert sorted(eng._free) == [0, 1]
        assert not eng._slot_req

    def test_eos_stops_row_early(self):
        """Force an EOS hit: the row must retire early and its slot be
        reused, with the other row unaffected."""
        m = tiny_model()
        rng = np.random.RandomState(5)
        prompts = prompts_mixed(rng, 97, [6, 6, 6])
        # run once greedy to discover a token that actually appears, then
        # use it as the eos id for one request
        probe = CausalLMEngine(m, max_batch=1, max_len=64)
        base = probe.generate(prompts[0][None],
                              GenerationConfig(max_new_tokens=8))[0, 6:]
        eos = int(base[2])             # third generated token
        cfg = GenerationConfig(max_new_tokens=8, eos_token_id=eos)

        dense = CausalLMEngine(m, max_batch=1, max_len=64)
        want = [dense.generate(p[None], cfg)[0, len(p):] for p in prompts]

        def trim(seq):                  # dense pads with eos after the hit
            seq = list(np.asarray(seq))
            if eos in seq:
                return seq[:seq.index(eos) + 1]
            return seq

        eng = paged_engine(m, max_batch=2, max_len=64)
        got = eng.serve(prompts, cfg, segment_steps=4)
        for i, (w, g) in enumerate(zip(want, got)):
            assert list(np.asarray(g)) == trim(w), (i, g, trim(w))


class TestRaggedState:
    def test_dead_rows_do_not_advance(self):
        m = tiny_model()
        rng = np.random.RandomState(6)
        eng = paged_engine(m, max_batch=3, max_len=64)
        cfg = GenerationConfig(max_new_tokens=20)
        eng.add_request(rng.randint(0, 97, (5,)).astype(np.int32), cfg)
        lens_before = np.asarray(eng.lens).copy()
        assert lens_before[0] == 5 and lens_before[1] == 0
        eng.decode_segment(4, cfg)
        lens_after = np.asarray(eng.lens)
        assert lens_after[0] == 9          # live row advanced 4 steps
        assert lens_after[1] == 0 and lens_after[2] == 0  # empty slots froze

    def test_lengths_are_per_row(self):
        """Two rows admitted with different prompt lengths keep distinct
        positions after a shared segment."""
        m = tiny_model()
        rng = np.random.RandomState(7)
        eng = paged_engine(m, max_batch=2, max_len=64)
        cfg = GenerationConfig(max_new_tokens=30)
        eng.add_request(rng.randint(0, 97, (4,)).astype(np.int32), cfg)
        eng.add_request(rng.randint(0, 97, (12,)).astype(np.int32), cfg)
        eng.decode_segment(5, cfg)
        lens = np.asarray(eng.lens)
        assert lens[0] == 9 and lens[1] == 17, lens


class TestOneEngine:
    """PR 30: the paged engine stands alone, and the helper the serving
    tests build it with gives a dense engine's capacity."""

    def test_paged_engine_is_a_plain_class(self):
        import inspect

        from paddle_tpu.inference import generation

        eng = generation.PagedContinuousBatchingEngine
        assert eng.__mro__ == (eng, object)
        assert "super()" not in inspect.getsource(eng)
        assert not hasattr(generation, "ContinuousBatchingEngine")
        assert "ContinuousBatchingEngine" not in generation.__all__

    def test_model_contract_is_four_forwards(self):
        from paddle_tpu.models.afmoe import AfmoeForCausalLM

        served = ("forward", "forward_with_cache", "init_paged_cache",
                  "forward_decode_paged")
        for name in served + ("forward_decode_spec_paged",):
            assert callable(getattr(LlamaForCausalLM, name)), name
        for name in served:
            assert callable(getattr(AfmoeForCausalLM, name)), name
        for gone in ("forward_decode_ragged", "forward_decode_spec"):
            assert not hasattr(LlamaForCausalLM, gone), gone

    def test_helper_gives_every_row_its_max_len(self):
        eng = paged_engine(tiny_model(), max_batch=3, max_len=60)
        assert (eng.page_size, eng.max_len, eng.num_pages) == (8, 64, 24)
        for slot in range(3):       # all rows full at once: no pressure
            eng.alloc.ensure(slot, eng.max_len)
        assert eng.alloc.free_pages == 0
        # a test that wants pressure says so
        tight = paged_engine(tiny_model(), max_batch=3, max_len=60,
                             num_pages=5, page_size=4)
        assert (tight.num_pages, tight.max_len) == (5, 60)

    def test_bare_engine_hides_the_pages_and_nothing_else(self):
        from engine_helpers import BareEngine

        eng = paged_engine(tiny_model(), max_batch=2, max_len=32)
        bare = BareEngine(eng)
        for name in ("alloc", "admission_mode", "set_kv_dtype"):
            assert getattr(bare, name, None) is None, name
        assert bare.free_slots() == 2 and bare.max_len == 32
        bare.draft_k = 3            # writes reach the engine
        assert eng.draft_k == 3
