"""Batched speculative decoding in the online serving path (ISSUE 8).

The tentpole contract, CPU-verified:

- BITWISE-GREEDY PARITY: a speculating request's output is identical
  to the same request decoded plain, MHA and GQA — speculation changes the schedule, never the tokens;
- ONE COMPILED PROGRAM: a mixed speculating/plain/sampled batch rides
  a single compiled verify-step program per (engine, draft_k) —
  asserted via the monitored_jit cache-miss counter;
- INTERACTION SUITES: a spec slot preempted mid-draft under KV
  pressure (PR 5), replayed through an engine restart (PR 4), and
  sharing a cached prefix with copy-on-write on divergence (PR 6) all
  keep greedy parity; eos landing mid-accepted-draft truncates
  exactly like the plain path;
- the extracted n-gram proposer (inference/ngram.py) is the same
  tested unit the offline path consumes.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from engine_helpers import paged_engine
from paddle_tpu.inference.generation import (CausalLMEngine,
                                             GenerationConfig,
                                             PagedContinuousBatchingEngine)
from paddle_tpu.inference.ngram import NgramIndex, NgramProposer
from paddle_tpu.models import LlamaForCausalLM, llama_config
from paddle_tpu.serving import Server


def tiny_model(layers=2, kv_heads=None, seed=0):
    paddle.seed(seed)
    cfg = llama_config("tiny", num_hidden_layers=layers,
                       num_key_value_heads=kv_heads)
    return LlamaForCausalLM(cfg), cfg


@pytest.fixture()
def mon():
    monitor.enable()
    monitor.reset()
    yield monitor
    monitor.reset()
    monitor.disable()


REP = np.tile(np.array([5, 6, 7, 8], np.int32), 6)       # accepting
RND = np.random.RandomState(0).randint(0, 64, (9,)).astype(np.int32)


def _greedy(n, **kw):
    return GenerationConfig(max_new_tokens=n, eos_token_id=None, **kw)


def _spec(n, **kw):
    return GenerationConfig(max_new_tokens=n, eos_token_id=None,
                            speculative=True, **kw)


def _run(eng, prompts, cfgs, steps=4):
    rids = [eng.add_request(p, c) for p, c in zip(prompts, cfgs)]
    while eng.decode_segment(steps):
        pass
    outs = eng.collect_finished()
    return [outs[r] for r in rids]


class TestNgramProposer:
    """The extracted unit (inference/ngram.py) both paths consume."""

    def test_index_proposes_recent_continuation(self):
        idx = NgramIndex(3)
        ctx = [1, 2, 3, 9, 1, 2, 3]
        assert idx.propose(ctx, 2) == [9, 1]

    def test_miss_pads_with_tail_token(self):
        assert NgramIndex(2).propose([4, 5, 6], 3) == [6, 6, 6]

    def test_proposer_state_is_incremental(self):
        p = NgramProposer([1, 2, 3, 9], draft_k=3, ngram_max=3)
        p.extend([1, 2, 3])
        # suffix [1,2,3] matched at position 0 -> continue with 9, then
        # the next occurrence's continuation
        d = p.propose()
        assert d[0] == 9
        assert p.proposed == 3
        assert len(p.ctx) == 7

    def test_validation(self):
        with pytest.raises(ValueError, match="draft_k"):
            NgramProposer([1], draft_k=0)
        with pytest.raises(ValueError, match="ngram_max"):
            NgramIndex(0)

    def test_offline_path_consumes_it(self):
        """generate_speculative rides the shared proposer and keeps
        its exact-match contract (the offline suite asserts the rest)."""
        model, cfg = tiny_model()
        eng = CausalLMEngine(model, max_batch=1, max_len=256)
        gc = _greedy(24)
        ref = eng.generate(REP[None], gc)
        out = eng.generate_speculative(REP[None], gc, draft_k=6)
        np.testing.assert_array_equal(ref, out)
        assert eng.last_spec_stats["accepted_draft_tokens"] > 0


class TestConfigKnobs:
    def test_generation_config_fields(self):
        cfg = GenerationConfig(speculative=True, draft_k=4)
        assert cfg.speculative and cfg.draft_k == 4
        assert GenerationConfig().speculative is False
        assert GenerationConfig().draft_k is None
        with pytest.raises(ValueError, match="draft_k"):
            GenerationConfig(draft_k=0)
        with pytest.raises(ValueError, match="draft_k"):
            GenerationConfig(draft_k=300)
        with pytest.raises(ValueError, match="draft_k"):
            GenerationConfig(draft_k=2.5)

    def test_engine_draft_k_validation(self):
        model, _ = tiny_model(layers=1)
        with pytest.raises(ValueError, match="draft_k"):
            paged_engine(model, max_batch=1, max_len=64,
                         draft_k=-1)

    def test_spec_k_eligibility(self):
        """Sampled requests and draft_k=0 engines fall back to plain;
        a request's own draft_k caps the engine's, never widens it."""
        model, _ = tiny_model(layers=1)
        eng = paged_engine(model, max_batch=1, max_len=64, draft_k=6)
        assert eng._spec_k_for(_spec(4)) == 6
        assert eng._spec_k_for(_spec(4, draft_k=3)) == 3
        assert eng._spec_k_for(_spec(4, draft_k=200)) == 6
        assert eng._spec_k_for(_greedy(4)) == 0
        assert eng._spec_k_for(GenerationConfig(
            max_new_tokens=4, do_sample=True, speculative=True,
            eos_token_id=None)) == 0
        off = paged_engine(model, max_batch=1, max_len=64)
        assert off._spec_k_for(_spec(4)) == 0


class TestBitwiseParity:
    """Greedy spec-vs-plain output is bitwise identical per slot —
    MHA + GQA, accepting and adversarial prompts."""

    @pytest.mark.parametrize("kv_heads", [None, 2],
                             ids=["mha", "gqa"])
    def test_paged(self, kv_heads):
        model, _ = tiny_model(kv_heads=kv_heads)
        ref = _run(PagedContinuousBatchingEngine(
            model, max_batch=2, num_pages=24, page_size=8,
            max_pages=16, debug_pages=True),
            [REP, RND], [_greedy(24), _greedy(24)])
        eng = PagedContinuousBatchingEngine(
            model, max_batch=2, num_pages=24, page_size=8,
            max_pages=16, draft_k=6, debug_pages=True)
        out = _run(eng, [REP, RND], [_spec(24), _spec(24)])
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)
        assert eng.spec_stats()["accepted"] > 0
        # all capacity reclaimed, validator armed throughout
        assert eng.alloc.free_pages == eng.num_pages

    def test_budget_smaller_than_draft_window(self):
        """A budget below draft_k must be respected exactly (the
        device lim-cap cuts acceptance; host never over-collects)."""
        model, _ = tiny_model()
        ref = _run(paged_engine(model, max_batch=1, max_len=128),
                   [REP], [_greedy(3)])
        eng = paged_engine(model, max_batch=1, max_len=128, draft_k=6)
        out = _run(eng, [REP], [_spec(3)])
        np.testing.assert_array_equal(ref[0], out[0])
        assert len(out[0]) == 3

    def test_near_max_len_stops_clean(self):
        """A spec row whose window would cross max_len caps its
        acceptance there instead of clamp-corrupting the cache tail."""
        model, _ = tiny_model()
        # plen 24 + 8 new = max_len exactly
        ref = _run(paged_engine(model, max_batch=1, max_len=32),
                   [REP], [_greedy(8)])
        eng = paged_engine(model, max_batch=1, max_len=32, draft_k=6)
        out = _run(eng, [REP], [_spec(8)])
        np.testing.assert_array_equal(ref[0], out[0])


class TestMixedBatchOneProgram:
    def test_mixed_spec_plain_sampled_single_compile(self, mon):
        """A mixed speculating/plain/sampled batch is served by ONE
        compiled verify-step program (per draft_k) — and the greedy
        rows keep bitwise parity while riding it."""
        model, _ = tiny_model()
        ref = _run(paged_engine(model, max_batch=2, max_len=128),
                   [REP, RND], [_greedy(20), _greedy(20)])
        monitor.reset()         # count only the MIXED run's compiles
        eng = paged_engine(model, max_batch=3, max_len=128, draft_k=6)
        outs = _run(eng, [REP, RND, REP],
                    [_spec(20), _greedy(20),
                     GenerationConfig(max_new_tokens=10, do_sample=True,
                                      temperature=0.8, seed=7,
                                      eos_token_id=None)])
        np.testing.assert_array_equal(outs[0], ref[0])   # spec row
        np.testing.assert_array_equal(outs[1], ref[1])   # plain row
        assert len(outs[2]) == 10                        # sampled row
        misses = monitor.jit_miss_by_fn()
        # ONE spec-step compile serves the whole spec/plain/sampled mix
        # (segments after the spec row retires revert to the plain scan
        # program, itself compiled at most once per n_steps)
        assert misses.get("cb_spec_step") == 1, misses
        assert misses.get("cb_segment", 0) <= 1, misses

    def test_draft_k_keys_the_program(self, mon):
        """Two engines with different draft_k compile their own width;
        within one engine every segment reuses the first compile."""
        model, _ = tiny_model(layers=1)
        for k in (2, 4):
            eng = paged_engine(model, max_batch=1, max_len=64, draft_k=k)
            _run(eng, [REP[:8]], [_spec(10)])
        misses = monitor.jit_miss_by_fn()
        assert misses.get("cb_spec_step") == 2, misses


class TestEosMidDraft:
    def test_eos_landing_mid_accepted_draft_truncates(self):
        """eos inside an accepted draft window: the emitted sequence
        truncates AT eos (stale device tail dies with retirement) and
        matches the plain path bitwise."""
        model, _ = tiny_model()
        probe = paged_engine(model, max_batch=1, max_len=128)
        free = _run(probe, [REP], [_greedy(24)])[0]
        eos = int(free[7])          # something it emits mid-stream
        kw = dict(max_new_tokens=24, eos_token_id=eos)
        ref = _run(paged_engine(model, max_batch=1, max_len=128),
                   [REP], [GenerationConfig(**kw)])[0]
        eng = paged_engine(model, max_batch=1, max_len=128, draft_k=6)
        out = _run(eng, [REP],
                   [GenerationConfig(speculative=True, **kw)])[0]
        np.testing.assert_array_equal(ref, out)
        assert out[-1] == eos and len(out) < 24
        # the slot retired cleanly — engine is idle and reusable
        assert eng.free_slots() == 1
        out2 = _run(eng, [RND], [_spec(6)])[0]
        assert len(out2) == 6


class TestServerIntegration:
    def test_server_knobs_and_default_opt_in(self, mon):
        """Server(draft_k=..., speculative=True) mirrors the engine
        knob and opts eligible requests in by default; warmup
        pre-compiles the verify program so requests pay zero segment
        compiles."""
        model, cfg = tiny_model()
        eng = PagedContinuousBatchingEngine(
            model, max_batch=2, num_pages=24, page_size=8, max_pages=8)
        srv = Server(eng, segment_steps=3, warmup=True, draft_k=4,
                     speculative=True)
        try:
            assert srv.wait_ready(120) and srv.status == "ok"
            pre = monitor.jit_miss_by_fn()
            h = srv.submit(REP, _greedy(12))      # no explicit opt-in
            out = h.result(timeout=120)
            assert len(out) == 12
            post = monitor.jit_miss_by_fn()
            assert post.get("cb_spec_step") == pre.get("cb_spec_step")
            assert eng.spec_stats()["forwards"] > 0   # it DID speculate
        finally:
            srv.shutdown(drain=False)

    def test_server_knob_validation(self):
        model, _ = tiny_model(layers=1)
        eng = paged_engine(model, max_batch=1, max_len=64)
        with pytest.raises(ValueError, match="draft_k"):
            Server(eng, start=False, draft_k=-2)
        with pytest.raises(ValueError, match="speculative"):
            Server(eng, start=False, speculative=True)   # draft_k == 0
        srv = Server(eng, start=False, draft_k=5)
        assert eng.draft_k == 5
        srv.shutdown(drain=False)

    def test_spec_metrics_exported_and_retired(self, mon):
        """paddle_tpu_spec_draft_tokens_total{engine,outcome} counts
        proposed/accepted per engine and retires in engine.close()."""
        model, _ = tiny_model()
        eng = paged_engine(model, max_batch=1, max_len=128, draft_k=6)
        _run(eng, [REP], [_spec(16)])
        snap = monitor.snapshot()["metrics"]
        by = {s["labels"]["outcome"]: s["value"]
              for s in snap["paddle_tpu_spec_draft_tokens_total"]
              ["samples"]
              if s["labels"]["engine"] == eng._monitor_engine}
        assert by["proposed"] > 0 and 0 <= by["accepted"] <= by["proposed"]
        eng.close()
        snap = monitor.snapshot()["metrics"]
        left = [s for s in snap.get(
            "paddle_tpu_spec_draft_tokens_total", {}).get("samples", [])
            if s["labels"].get("engine") == eng._monitor_engine]
        assert not left


class TestPressureInteraction:
    """PR 5 composition: spec slots under optimistic admission grow
    their widened window per gap, get preempted mid-draft when the
    pool is dry, and replay warm with greedy parity."""

    def test_spec_slot_preempted_mid_draft_replays_bitwise(self):
        model, _ = tiny_model()
        big = PagedContinuousBatchingEngine(
            model, max_batch=2, num_pages=32, page_size=8, max_pages=16,
            debug_pages=True)
        ref = _run(big, [REP, REP[:20]], [_greedy(24), _greedy(24)])
        # 10 pages = 80 tokens for two requests needing (24+24)+(20+24)
        # worst case — optimistic admission with spec growth forces
        # preemption mid-decode
        eng = PagedContinuousBatchingEngine(
            model, max_batch=2, num_pages=10, page_size=8, max_pages=16,
            admission_mode="optimistic", draft_k=6, debug_pages=True)
        srv = Server(eng, segment_steps=4, max_preemptions=10,
                     speculative=True, idle_wait_s=0.005)
        try:
            h1 = srv.submit(REP, _greedy(24))
            h2 = srv.submit(REP[:20], _greedy(24))
            o1 = h1.result(timeout=180)
            o2 = h2.result(timeout=180)
            np.testing.assert_array_equal(ref[0], o1)
            np.testing.assert_array_equal(ref[1], o2)
            assert eng.alloc.preemptions >= 1, \
                "pool was sized to force at least one preemption"
            assert srv.drain(timeout=60)
        finally:
            srv.shutdown(drain=False)
        assert eng.alloc.free_pages == eng.num_pages

    def test_spec_growth_accounts_window_width(self):
        """grow_for_segment targets n_steps * (spec_k+1) for a
        speculating row — the draft window's worst-case advance."""
        model, _ = tiny_model(layers=1)
        eng = PagedContinuousBatchingEngine(
            model, max_batch=1, num_pages=16, page_size=8, max_pages=16,
            admission_mode="optimistic", draft_k=3, debug_pages=True)
        eng.add_request(REP[:8], _spec(40))
        before = eng.alloc.covered_tokens(0)     # prompt + 1 page = 16
        assert eng.grow_for_segment(4) == []
        # plain target would be lens(8) + 4 = 12 (inside the existing
        # 16-token claim); spec must cover lens + 4*(3+1) = 24
        covered = eng.alloc.covered_tokens(0)
        assert covered >= 24 > before


class TestRestartInteraction:
    """PR 4 composition: a spec slot survives an engine-scoped fault —
    reset_state + replay re-prefills prompt + generated, the proposer
    rebuilds from full context, greedy parity holds."""

    def test_spec_slot_through_restart_replay_bitwise(self):
        from paddle_tpu.inference.generation import EngineFault
        from paddle_tpu.testing.faults import FaultPlan, FaultyEngine

        model, _ = tiny_model()
        clean = PagedContinuousBatchingEngine(
            model, max_batch=2, num_pages=24, page_size=8, max_pages=8,
            debug_pages=True)
        ref = _run(clean, [REP], [_greedy(20)])
        plan = FaultPlan().raise_at("decode", nth=2,
                                    exc=EngineFault("injected"))
        raw = PagedContinuousBatchingEngine(
            model, max_batch=2, num_pages=24, page_size=8, max_pages=8,
            draft_k=6, debug_pages=True)
        srv = Server(FaultyEngine(raw, plan), segment_steps=3,
                     restart_backoff_s=0.01, speculative=True)
        try:
            h = srv.submit(REP, _greedy(20))
            out = h.result(timeout=180)
            np.testing.assert_array_equal(ref[0], out)
            assert srv.restarts == 1
            assert srv.drain(timeout=60)
        finally:
            srv.shutdown(drain=False)
        assert raw.free_slots() == raw.max_batch
        assert raw.alloc.free_pages == raw.num_pages


class TestPrefixCacheInteraction:
    """PR 6 composition: a spec slot admits WARM off a cached prefix,
    copy-on-writes the partial boundary page before its first draft
    write, and still matches the cold plain run bitwise."""

    def test_spec_warm_admission_cow_on_divergence_bitwise(self):
        model, _ = tiny_model()
        # prompt B shares a 20-token head with A, diverges mid-block
        # (page_size 8 -> coverage ends mid page 2), then decodes
        # speculatively: the divergent suffix + drafts must CoW, never
        # write A's shared pages
        pa = REP                                   # 24 tokens
        pb = np.concatenate([REP[:20], np.array([9, 9], np.int32)])
        cold = PagedContinuousBatchingEngine(
            model, max_batch=2, num_pages=32, page_size=8, max_pages=8,
            debug_pages=True)
        ref_a = _run(cold, [pa], [_greedy(16)])[0]
        ref_b = _run(cold, [pb], [_greedy(16)])[0]
        eng = PagedContinuousBatchingEngine(
            model, max_batch=2, num_pages=32, page_size=8, max_pages=8,
            prefix_cache=True, draft_k=6, debug_pages=True)
        out_a = _run(eng, [pa], [_spec(16)])[0]
        np.testing.assert_array_equal(ref_a, out_a)
        # warm re-run of A's exact prompt (fully cached head), then B
        out_a2 = _run(eng, [pa], [_spec(16)])[0]
        np.testing.assert_array_equal(ref_a, out_a2)
        out_b = _run(eng, [pb], [_spec(16)])[0]
        np.testing.assert_array_equal(ref_b, out_b)
        assert eng.alloc.prefix_hits >= 2
        assert eng.alloc.cow_copies >= 1


class TestSpecStatsSurface:
    def test_spec_stats_identity_and_reset(self):
        model, _ = tiny_model()
        eng = paged_engine(model, max_batch=2, max_len=128, draft_k=4)
        _run(eng, [REP, RND], [_spec(12), _spec(12)])
        st = eng.spec_stats()
        assert st["emitted"] == st["slot_steps"] + st["accepted"]
        assert 0.0 <= st["acceptance_rate"] <= 1.0
        assert st["tokens_per_forward"] >= 1.0
        eng.reset_state()
        assert eng._spec == {}          # proposers die with the slots
        # totals survive reset (engine-lifetime accounting)
        assert eng.spec_stats()["emitted"] == st["emitted"]
