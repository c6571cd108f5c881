"""Bounded-compile bucketed + chunked prefill (ISSUE-3).

Covers the two acceptance demos — (a) >= 6 distinct prompt lengths
compile at most len(buckets) prefill programs with tokens identical to
the unbucketed engine, (b) a long prompt admitted in >= 4 chunks during
active decoding interleaves decode segments between chunks and matches
single-shot prefill — plus the bitwise parity contracts they rest on
(padded-bucket and chunked prefill reproduce exact prefill logits AND
KV bit for bit, dense and paged), warmup (no request-path compiles
after ``Server(warmup=True)``), and the heap free-list determinism.
"""
import threading

import numpy as np
import pytest

import jax

from engine_helpers import paged_engine
import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference.generation import (CausalLMEngine,
                                             GenerationConfig,
                                             PagedContinuousBatchingEngine,
                                             _pad_ids, prefill_buckets_for)
from paddle_tpu.models import LlamaForCausalLM, llama_config
from paddle_tpu.serving import Server, serve_http


def tiny_model(layers=2, seed=0, **cfg_kw):
    paddle.seed(seed)
    cfg = llama_config("tiny", num_hidden_layers=layers, **cfg_kw)
    return LlamaForCausalLM(cfg), cfg


@pytest.fixture()
def mon():
    monitor.enable()
    monitor.reset()
    yield monitor
    monitor.reset()
    monitor.disable()


def _jit_misses():
    # summed per entry point: the counter carries ("fn", "program")
    # since the ledger split, and one fn compiles many programs
    samples = monitor.snapshot()["metrics"].get(
        "paddle_tpu_jit_cache_miss_total", {}).get("samples", [])
    out = {}
    for s in samples:
        fn = s["labels"]["fn"]
        out[fn] = out.get(fn, 0) + int(s["value"])
    return out


def _val(x):
    return np.asarray(getattr(x, "value", x))


class TestBucketSpec:
    def test_auto_powers_of_two(self):
        assert prefill_buckets_for("auto", 256) == (16, 32, 64, 128, 256)
        assert prefill_buckets_for("auto", 48) == (16, 32, 48)
        assert prefill_buckets_for("auto", 8) == (8,)

    def test_explicit_extended_to_max_len(self):
        # every admissible prompt must land in SOME bucket
        assert prefill_buckets_for([8, 24], 64) == (8, 24, 64)
        assert prefill_buckets_for((32, 8, 8), 32) == (8, 32)

    def test_disabled_and_invalid(self):
        assert prefill_buckets_for(None, 64) is None
        with pytest.raises(ValueError, match="max_len"):
            prefill_buckets_for([128], 64)
        with pytest.raises(ValueError, match="positive"):
            prefill_buckets_for([0, 8], 64)

    def test_engine_knob_validation(self):
        model, _ = tiny_model(layers=1)
        with pytest.raises(ValueError, match="prefill_chunk"):
            paged_engine(model, max_batch=1, max_len=32, prefill_chunk=0)
        # a chunk that doesn't divide max_len would let a final chunk
        # window overhang the cache, where dynamic_update_slice CLAMPS
        # and silently overwrites earlier prompt KV — rejected up front
        with pytest.raises(ValueError, match="multiple"):
            paged_engine(model, max_batch=1, max_len=96, prefill_chunk=64)


class TestPrefillParityBitwise:
    """Padded-bucket and chunked prefill must reproduce EXACT prefill —
    last-position logits and the KV written for real positions — bit
    for bit (ops/pallas.prefix_chunk_attention shares the one-shot
    flash fallback's reduction structure; masked pad columns contribute
    exact float zeros). Driven through the engines' OWN jitted prefill
    programs (the production path, and fast — eager model calls are
    minutes-scale here); two layers so layer-2 KV also covers attention
    -output propagation."""

    def _kv_prefix(self, caches, plen):
        return [(_val(k)[:, :plen], _val(v)[:, :plen])
                for k, v in caches]

    def _exact(self, eng, ids, plen):
        import jax.numpy as jnp

        logits, caches = eng._prefill(eng.params, ids,
                                      eng.model.init_cache(1, 64),
                                      jnp.int32(plen - 1))
        return _val(logits), caches

    @pytest.mark.parametrize("kv_heads", [4, 2])
    def test_padded_and_chunked_prefill_bitwise(self, kv_heads):
        import jax.numpy as jnp

        model, cfg = tiny_model(num_key_value_heads=kv_heads)
        eng = CausalLMEngine(model, max_batch=1, max_len=64,
                             prefill_buckets=None, prefill_chunk=4)
        ids = np.random.RandomState(0).randint(
            0, cfg.vocab_size, (1, 13)).astype(np.int32)
        want_logits, want_caches = self._exact(eng, ids, 13)
        want_kv = self._kv_prefix(want_caches, 13)

        # padded to bucket 16, same program, last_idx still 12
        padded = np.pad(ids, ((0, 0), (0, 3)))
        got_logits, got_caches = self._exact(eng, padded, 13)
        np.testing.assert_array_equal(want_logits, got_logits)
        for (wk, wv), (gk, gv) in zip(want_kv,
                                      self._kv_prefix(got_caches, 13)):
            np.testing.assert_array_equal(wk, gk)
            np.testing.assert_array_equal(wv, gv)

        # chunked: 4-token chunks at traced offsets (the
        # prefix_chunk_attention path), ONE compiled program
        caches = model.init_cache(1, 64)
        pos, C = 0, 4
        while pos < 13:
            chunk = ids[:, pos:pos + C]
            r = chunk.shape[1]
            if r < C:
                chunk = np.pad(chunk, ((0, 0), (0, C - r)))
            logits, caches = eng._prefill_chunk(
                eng.params, chunk, caches, jnp.int32(pos),
                jnp.int32(r - 1))
            pos += C
        np.testing.assert_array_equal(want_logits, _val(logits))
        for (wk, wv), (gk, gv) in zip(want_kv,
                                      self._kv_prefix(caches, 13)):
            np.testing.assert_array_equal(wk, gk)
            np.testing.assert_array_equal(wv, gv)

    def test_dense_engine_generate_parity(self):
        # bucketed-program parity is the bitwise test above; here the
        # offline engine's CHUNKED generate path (shared by speculative
        # prefill) must reproduce exact generate end to end
        model, cfg = tiny_model(layers=1)
        ids = np.random.RandomState(2).randint(
            0, cfg.vocab_size, (2, 11)).astype(np.int32)
        gc = GenerationConfig(max_new_tokens=6)
        want = CausalLMEngine(model, max_batch=2, max_len=64,
                              prefill_buckets=None).generate(ids, gc)
        chunked = CausalLMEngine(model, max_batch=2, max_len=64,
                                 prefill_chunk=4)
        np.testing.assert_array_equal(want, chunked.generate(ids, gc))


PLENS = (3, 5, 9, 12, 17, 30)   # spans buckets 16/16/16/16/32/32
_REF = {}                       # memoized unbucketed reference outputs


def _serve(eng, prompts, gc):
    return [list(o) for o in eng.serve(prompts, gc, segment_steps=4)]


def _prompts(cfg):
    rng = np.random.RandomState(3)
    return [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in PLENS]


def _reference(model, cfg):
    """Unbucketed (exact-length prefill) engine outputs — the parity
    target for the bucketed engine."""
    if "want" not in _REF:
        gc = GenerationConfig(max_new_tokens=6, eos_token_id=None)
        _REF["want"] = _serve(paged_engine(
            model, max_batch=3, max_len=64, prefill_buckets=None),
            _prompts(cfg), gc)
    return _REF["want"]


class TestBoundedCompile:
    """ISSUE-3 acceptance: >= 6 requests with distinct prompt lengths
    compile at most len(buckets) prefill programs (monitored_jit miss
    counters), with tokens identical to the unbucketed engine."""

    def test_paged_engine(self, mon):
        model, cfg = tiny_model(layers=1)
        prompts = _prompts(cfg)
        gc = GenerationConfig(max_new_tokens=6, eos_token_id=None)
        want = _reference(model, cfg)
        monitor.reset()
        eng = PagedContinuousBatchingEngine(
            model, max_batch=3, num_pages=24, page_size=8, max_pages=8)
        got = _serve(eng, prompts, gc)
        assert got == want
        misses = _jit_misses()
        assert misses.get("cb_prefill", 0) <= len(eng.prefill_buckets), \
            misses
        # per-bucket admission counters exported for dashboards
        buckets = {s["labels"]["bucket"]: s["value"]
                   for s in monitor.snapshot()["metrics"]
                   ["paddle_tpu_prefill_requests_total"]["samples"]}
        assert sum(buckets.values()) == len(PLENS)


class TestChunkedAdmission:
    """ISSUE-3 acceptance: one long prompt (>= 4 chunks) admitted during
    active decoding — decode segments run BETWEEN chunks (bounded gap
    work) and the final output matches single-shot prefill."""

    def test_server_interleaves_decode_between_chunks(self, mon):
        model, cfg = tiny_model(layers=1)
        rng = np.random.RandomState(5)
        long_p = rng.randint(0, cfg.vocab_size, (30,)).astype(np.int32)
        gc = GenerationConfig(max_new_tokens=8, eos_token_id=None)

        single = PagedContinuousBatchingEngine(
            model, max_batch=3, num_pages=24, page_size=8, max_pages=8)
        rid = single.add_request(long_p, gc)
        while single.decode_segment(2):
            pass
        want = list(single.collect_finished()[rid])

        eng = PagedContinuousBatchingEngine(
            model, max_batch=3, num_pages=24, page_size=8, max_pages=8,
            prefill_chunk=8)
        events = []
        ds, ac = eng.decode_segment, eng.admit_chunk
        eng.decode_segment = \
            lambda n, cfg=None, **kw: (events.append("seg"),
                                       ds(n, cfg, **kw))[1]
        eng.admit_chunk = \
            lambda adm, **kw: (events.append("chunk"), ac(adm, **kw))[1]
        srv = Server(eng, max_queue=8, segment_steps=2)
        try:
            h_short = srv.submit(
                rng.randint(0, cfg.vocab_size, (5,)).astype(np.int32),
                GenerationConfig(max_new_tokens=24, eos_token_id=None))
            next(iter(h_short.stream(timeout=60)))   # decoding active
            h_long = srv.submit(long_p, gc)
            got = list(h_long.result(timeout=120))
            assert got == want
            assert len(h_short.result(timeout=120)) == 24
            chunk_idx = [i for i, e in enumerate(events)
                         if e == "chunk"]
            assert len(chunk_idx) == 4               # ceil(30/8)
            # bounded gap work: a decode segment ran between chunks
            assert any("seg" in events[a + 1:b]
                       for a, b in zip(chunk_idx, chunk_idx[1:])), \
                events
        finally:
            srv.shutdown(drain=False)

    def test_deadline_expiring_mid_admission_aborts(self, mon):
        """Chunked admission spans many gaps, so the admission deadline
        must keep applying AFTER the request leaves the queue: a
        deadline passing mid-admission aborts it (EXPIRED, capacity
        reclaimed) instead of decoding for a client that gave up."""
        import time as _time

        from paddle_tpu.serving import DeadlineExpired

        model, cfg = tiny_model(layers=1)
        eng = PagedContinuousBatchingEngine(
            model, max_batch=2, num_pages=24, page_size=8, max_pages=8,
            prefill_chunk=8)
        real = eng.admit_chunk
        eng.admit_chunk = \
            lambda adm, **kw: (_time.sleep(0.05), real(adm, **kw))[1]
        srv = Server(eng, segment_steps=2)
        try:
            h = srv.submit(np.arange(30, dtype=np.int32)
                           % cfg.vocab_size,
                           GenerationConfig(max_new_tokens=8,
                                            eos_token_id=None),
                           timeout_s=0.08)   # expires after ~1 chunk
            with pytest.raises(DeadlineExpired):
                h.result(timeout=60)
            deadline = _time.monotonic() + 10
            while (eng.free_slots() < 2
                   and _time.monotonic() < deadline):
                _time.sleep(0.01)
            assert eng.free_slots() == 2
            assert eng.alloc.free_pages == eng.num_pages
        finally:
            srv.shutdown(drain=False)

    def test_cancel_mid_chunked_admission_reclaims(self):
        model, cfg = tiny_model(layers=1)
        eng = PagedContinuousBatchingEngine(
            model, max_batch=2, num_pages=12, page_size=8, max_pages=8,
            prefill_chunk=8)
        gc = GenerationConfig(max_new_tokens=8, eos_token_id=None)
        p = np.arange(30, dtype=np.int32) % cfg.vocab_size
        adm = eng.begin_admit(p, gc)
        assert eng.free_slots() == 1
        assert eng.alloc.free_pages < eng.num_pages   # reserved UP FRONT
        assert not eng.admit_chunk(adm)
        eng.abort_admit(adm)
        eng.abort_admit(adm)                           # idempotent
        assert eng.free_slots() == 2
        assert eng.alloc.free_pages == eng.num_pages
        with pytest.raises(RuntimeError, match="admission"):
            eng.admit_chunk(adm)
        # capacity is genuinely reusable afterwards
        rid = eng.add_request(p, gc)
        while eng.decode_segment(4):
            pass
        assert len(eng.collect_finished()[rid]) == 8


class TestWarmup:
    def test_engine_warmup_precompiles_all_buckets(self, mon):
        model, cfg = tiny_model(layers=1)
        eng = PagedContinuousBatchingEngine(
            model, max_batch=3, num_pages=24, page_size=8, max_pages=8,
            prefill_chunk=8)
        out = eng.warmup(segment_steps=4)
        assert set(out) >= {f"prefill_{b}" for b in eng.prefill_buckets}
        assert "prefill_chunk" in out and "segment_4" in out
        before = _jit_misses()
        assert before.get("cb_prefill", 0) == len(eng.prefill_buckets)
        # warmup time is exported for the serving dashboards
        warm = monitor.snapshot()["metrics"][
            "paddle_tpu_prefill_warmup_seconds"]["samples"]
        assert warm and warm[0]["value"] > 0
        rng = np.random.RandomState(6)
        gc = GenerationConfig(max_new_tokens=4, eos_token_id=None)
        prompts = [rng.randint(0, cfg.vocab_size, (n,))
                   .astype(np.int32) for n in PLENS]
        _serve(eng, prompts, gc)
        after = _jit_misses()
        # NO user request paid a prefill/segment compile
        assert after.get("cb_prefill", 0) == before.get("cb_prefill", 0)
        assert after.get("cb_segment", 0) == before.get("cb_segment", 0)
        with pytest.raises(RuntimeError, match="idle"):
            eng.add_request(prompts[0], gc)
            eng.warmup()

    def test_server_warmup_reports_warming_then_ready(self, mon):
        import json
        from urllib.error import HTTPError
        from urllib.request import urlopen

        model, cfg = tiny_model(layers=1)
        eng = PagedContinuousBatchingEngine(
            model, max_batch=2, num_pages=12, page_size=8, max_pages=4)
        gate = threading.Event()
        real = eng.warmup
        eng.warmup = lambda n=None: (gate.wait(30), real(n))[1]
        srv = Server(eng, warmup=True)
        httpd = serve_http(srv)
        port = httpd.server_address[1]
        try:
            assert srv.status == "warming"
            with pytest.raises(HTTPError) as ei:   # readiness gate: 503
                urlopen(f"http://127.0.0.1:{port}/healthz", timeout=30)
            assert ei.value.code == 503
            assert json.load(ei.value)["status"] == "warming"
            # submissions QUEUE during warmup instead of failing
            h = srv.submit(np.arange(4, dtype=np.int32),
                           GenerationConfig(max_new_tokens=3,
                                            eos_token_id=None))
            gate.set()
            assert srv.wait_ready(60)
            assert len(h.result(timeout=120)) == 3
            with urlopen(f"http://127.0.0.1:{port}/healthz",
                         timeout=30) as r:
                assert json.load(r)["status"] == "ok"
        finally:
            gate.set()
            httpd.shutdown()
            srv.shutdown(drain=False)


class TestWarmupFailure:
    def test_wait_ready_unblocks_when_warmup_dies(self):
        """A warmup crash must not hang wait_ready() forever — the
        event fires on the way out and status says 'failed'."""
        model, cfg = tiny_model(layers=1)
        eng = PagedContinuousBatchingEngine(
            model, max_batch=2, num_pages=12, page_size=8, max_pages=4)
        eng.warmup = lambda n=None: (_ for _ in ()).throw(
            RuntimeError("injected warmup fault"))
        srv = Server(eng, warmup=True)
        try:
            assert srv.wait_ready(30)
            assert srv.status == "failed"
            from paddle_tpu.serving import RequestRejected
            with pytest.raises(RequestRejected, match="warmup fault"):
                srv.submit(np.arange(3, dtype=np.int32),
                           GenerationConfig(max_new_tokens=2))
        finally:
            srv.shutdown(drain=False)


class TestFreeListDeterminism:
    """Heap-backed free lists (engine slots + KV pages): admission order
    stays deterministic — lowest id first — after aborts and
    cancellations, without the old O(n log n) sort per retirement."""

    def test_slot_order_after_aborts(self):
        model, cfg = tiny_model(layers=1)
        eng = paged_engine(model, max_batch=4, max_len=32)
        gc = GenerationConfig(max_new_tokens=8, eos_token_id=None)
        rng = np.random.RandomState(7)

        def admit():
            return eng.add_request(
                rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32),
                gc)

        r0, r1, r2 = admit(), admit(), admit()
        slot_of = {r: s for s, r in eng._slot_req.items()}
        assert [slot_of[r] for r in (r0, r1, r2)] == [0, 1, 2]
        eng.cancel_request(r1)
        eng.cancel_request(r0)
        # a failed admission (abort path) returns its slot too
        orig = eng._admit_state
        eng._admit_state = lambda *a: (_ for _ in ()).throw(
            RuntimeError("injected"))
        with pytest.raises(RuntimeError, match="injected"):
            admit()
        eng._admit_state = orig
        # lowest freed slot is reused first, deterministically
        r3, r4 = admit(), admit()
        slot_of = {r: s for s, r in eng._slot_req.items()}
        assert slot_of[r3] == 0 and slot_of[r4] == 1

    def test_page_allocator_reuses_lowest_pages(self):
        from paddle_tpu.inference.paged_cache import PageAllocator

        alloc = PageAllocator(num_pages=8, page_size=4, max_batch=4,
                              max_pages=4)
        alloc.ensure(0, 8)    # pages 0,1
        alloc.ensure(1, 8)    # pages 2,3
        alloc.free_slot(0)
        alloc.ensure(2, 12)   # must take lowest free: 0,1,4
        assert list(alloc.page_table[2][:3]) == [0, 1, 4]
        alloc.close()


@pytest.mark.slow
class TestChunkedPrefillSoak:
    def test_long_prompt_soak(self, mon):
        """Long-prompt chunked-prefill soak: many mixed admissions with
        several multi-chunk prompts in flight back to back, outputs
        matching the unchunked engine throughout."""
        model, cfg = tiny_model(layers=1)
        rng = np.random.RandomState(8)
        gc = GenerationConfig(max_new_tokens=8, eos_token_id=None)
        lens = [rng.randint(3, 100) for _ in range(24)]
        prompts = [rng.randint(0, cfg.vocab_size, (n,))
                   .astype(np.int32) for n in lens]

        def outputs(prefill_chunk):
            eng = PagedContinuousBatchingEngine(
                model, max_batch=4, num_pages=64, page_size=8,
                max_pages=16, prefill_chunk=prefill_chunk)
            srv = Server(eng, max_queue=32, segment_steps=3,
                         warmup=True)
            try:
                handles = [srv.submit(p, gc) for p in prompts]
                return [list(h.result(timeout=300)) for h in handles]
            finally:
                srv.shutdown(drain=False)

        assert outputs(prefill_chunk=16) == outputs(prefill_chunk=None)


# one prompt a bucket of the tiny engines below: 16, 32 and 64
FUSED_PLENS = (9, 20, 40)


def _fused_paged(model, **kw):
    kw.setdefault("debug_pages", True)
    return PagedContinuousBatchingEngine(
        model, max_batch=3, num_pages=32, page_size=8, max_pages=8, **kw)


def _unfused_admit(eng, prompt, gc):
    """A cold admission by its separate steps, as the engine ran it
    before the fused program (and as a chunked admission still ends):
    mini cache, prefill, claim, install, first token."""
    import heapq

    ids = np.asarray(prompt, np.int32)[None]
    plen = ids.shape[1]
    slot = heapq.heappop(eng._free)
    eng._aidx_stash[slot] = 0
    rid = eng._next_req
    eng._next_req += 1
    width = eng._prefill_width(plen)
    logits, mini = jax.jit(lambda params, padded, mini: eng._fwd_prefill(
        params, padded, mini))(eng.params, _pad_ids(ids, width),
                               eng._mini_cache(width))
    logits = logits[:, plen - 1]
    eng._reserve_admit(slot, plen, gc)
    eng._install_mini(slot, mini, plen)
    eng._first_token(slot, rid, ids, plen, logits, gc, 0, 0.0)
    return rid, slot, logits


def _drain(eng, steps=4):
    while eng.decode_segment(steps):
        pass
    return {r: list(t) for r, t in eng.collect_finished().items()}


class TestFusedColdAdmission:
    """ISSUE 26: a cold one-shot admission of the paged engine is ONE
    program per bucket (mini cache, prefill and page install), with the
    pages claimed before it. Same values as the separate steps and as
    ``CausalLMEngine``."""

    @pytest.mark.parametrize("plen", FUSED_PLENS)
    def test_tokens_match_dense_and_reference(self, plen):
        model, cfg = tiny_model()
        p = np.random.RandomState(plen).randint(
            0, cfg.vocab_size, (plen,)).astype(np.int32)
        gc = GenerationConfig(max_new_tokens=6, eos_token_id=None)
        want = list(CausalLMEngine(model, max_batch=1, max_len=64)
                    .generate(p[None], gc)[0][plen:])
        eng = _fused_paged(model)
        assert eng._prefill_width(plen) == {9: 16, 20: 32, 40: 64}[plen]
        got = _serve(eng, [p], gc)[0]
        assert got == want
        eng.alloc.check()
        assert eng.alloc.free_pages == eng.num_pages

    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    def test_pools_and_tokens_equal_the_separate_steps(self, kv_dtype):
        """Fused against unfused on two engines fed the same prompts
        in the same order: the same pages hold the same rows (int8:
        the same scales too), bit for bit, and the tokens agree."""
        model, cfg = tiny_model()
        gc = GenerationConfig(max_new_tokens=5, eos_token_id=None)
        rng = np.random.RandomState(11)
        prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in FUSED_PLENS]
        fused = _fused_paged(model, kv_dtype=kv_dtype)
        plain = _fused_paged(model, kv_dtype=kv_dtype)
        for p in prompts:
            fused.add_request(p, gc)
            _unfused_admit(plain, p, gc)
        np.testing.assert_array_equal(fused.alloc.page_table,
                                      plain.alloc.page_table)
        ps = fused.page_size
        for slot, p in enumerate(prompts):
            # positions below the prompt length: the pad tail past it
            # is not state (float pools hold it, int8 pools drop it)
            pos = np.arange(len(p))
            pages = fused.alloc.page_table[slot, pos // ps]
            assert (pages >= 0).all()
            for a, b in zip(fused.caches[0], plain.caches[0]):
                for x, y in zip(a[:2], b[:2]):
                    np.testing.assert_array_equal(
                        _val(x)[pages, pos % ps], _val(y)[pages, pos % ps])
                for x, y in zip(a[2:], b[2:]):      # int8: scale rows
                    np.testing.assert_array_equal(_val(x)[pages],
                                                  _val(y)[pages])
        assert _drain(fused) == _drain(plain)
        fused.alloc.check()

    def test_lora_adapter_rides_the_fused_program(self):
        from test_lora_serving import _merged_clone, make_adapter

        model, cfg = tiny_model(layers=1)
        params = make_adapter(model, 7)
        gc = GenerationConfig(max_new_tokens=6, eos_token_id=None,
                              adapter="a")
        base = GenerationConfig(max_new_tokens=6, eos_token_id=None)
        p = np.arange(1, 21, dtype=np.int32)
        kw = dict(lora_capacity=2, lora_rank=2, lora_targets=("q", "v"))
        eng = _fused_paged(model, **kw)
        eng.load_adapter("a", params, alpha=4)
        # the reference: the adapter merged into a clone's weights
        # (alpha 4 over rank 2), one request through CausalLMEngine
        want = list(CausalLMEngine(_merged_clone(params, 2.0), max_batch=1,
                                   max_len=64).generate(p[None], base)[
            0, len(p):])
        got = _serve(eng, [p], gc)[0]
        assert got == want
        # and the adapter did something: the base model answers otherwise
        assert _serve(eng, [p], base)[0] != got
        eng.close()

    def test_admitted_while_others_decode(self):
        model, cfg = tiny_model()
        gc = GenerationConfig(max_new_tokens=10, eos_token_id=None)
        rng = np.random.RandomState(4)
        a, b = (rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
                for n in (12, 33))
        want = [_serve(_fused_paged(model), [p], gc)[0] for p in (a, b)]
        eng = _fused_paged(model)
        ra = eng.add_request(a, gc)
        assert eng.decode_segment(3)          # a is mid-decode
        rb = eng.add_request(b, gc)           # its pools are donated on
        done = _drain(eng)
        assert [done[ra], done[rb]] == want
        eng.alloc.check()

    def test_one_admission_is_a_handful_of_programs(self, tmp_path):
        """The count of device programs one cold admission executes on
        a warmed engine does not grow with the layers: the prefill,
        and what samples and installs the first token. A per-layer
        loop (2 zeros + 2 slices + 1 write a layer before) would read
        20 more here."""
        import glob
        import os

        import jax
        from jax.profiler import ProfileData

        model, cfg = tiny_model(layers=4)
        eng = _fused_paged(model)
        eng.warmup(segment_steps=4)
        gc = GenerationConfig(max_new_tokens=4, eos_token_id=None)
        eng.add_request(np.arange(9, dtype=np.int32), gc)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            eng.add_request(np.arange(1, 11, dtype=np.int32), gc)
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
        names = [ev.name for plane in ProfileData.from_file(path).planes
                 if plane.name == "/host:CPU"
                 for line in plane.lines for ev in line.events]
        runs = names.count("PjRtCpuExecutable::Execute")
        calls = {n[len("PjitFunction("):-1] for n in names
                 if n.startswith("PjitFunction(")}
        assert "prefill_one" in calls
        assert not {"write_tokens", "write_prompt", "mini_cache",
                    "broadcast_in_dim"} & calls, calls
        assert 1 <= runs <= 10, (runs, calls)

    def test_warmup_covers_every_bucket(self, mon):
        """After ``warmup()`` a request a bucket adds no jit miss of any
        entry point, float and int8 pools."""
        model, cfg = tiny_model(layers=1)
        gc = GenerationConfig(max_new_tokens=4, eos_token_id=None)
        for kv_dtype in ("bf16", "int8"):
            eng = _fused_paged(model, kv_dtype=kv_dtype)
            eng.warmup(segment_steps=4)
            before = monitor.jit_miss_by_fn()
            assert before.get("cb_prefill", 0) >= len(eng.prefill_buckets)
            for n in FUSED_PLENS:
                _serve(eng, [np.arange(n, dtype=np.int32)], gc)
            assert monitor.jit_miss_by_fn() == before
            eng.close()
