"""Automatic prefix caching suite (ISSUE 6): refcounted
copy-on-write shared KV pages.

Covers the content-addressable paged-pool contract on CPU:

- the sharing-era ``PageAllocator.check()`` invariant validator: free
  ∪ parked ∪ referenced partitions the pool by REFCOUNT ACCOUNTING (a
  page may appear in several slots' rows iff its refcount matches the
  appearance count), and a refcount leak / double-own / index leak
  fails loudly;
- ``check_coverage``: the per-gap net under ``debug_pages`` for
  :func:`write_tokens`' silent drop — a live length past the mapped
  pages, or an imminent write into a shared/indexed page (forgotten
  copy-on-write), raises instead of corrupting KV downstream;
- BITWISE PARITY (greedy): a warm-prefix admission produces exactly
  the tokens of a cold run — one-shot and chunked, MHA and GQA, full
  hits, divergence at a block boundary, divergence mid-block (CoW),
  and decode appending into a partially-filled shared tail page (CoW);
- lifecycle: cancel / preempt / replay / chunked-admission abort all
  DECREMENT instead of freeing, leak-free with the validator armed;
  shared pages survive their sharer's preemption; ``reset_state``
  drops the index with the pools;
- LRU: fully-released cached pages park indexed-but-reclaimable, are
  evicted oldest-first when the pool needs pages, and lookups refresh
  recency;
- the metrics surface: hits / lookups / tokens-saved counters,
  ``Server.pressure()`` prefix fields, monitor series retired by
  ``alloc.close()``.

Every paged engine here runs with ``debug_pages=True`` — the
refcount-aware validator is armed at every page op and every gap, so
any sharing bug in these paths fails the suite loudly.
"""
import functools

import numpy as np
import pytest

import engine_helpers
import paddle_tpu as paddle
from paddle_tpu.inference.generation import (CausalLMEngine,
                                             GenerationConfig)
from paddle_tpu.inference.paged_cache import PageAllocator
from paddle_tpu.serving import Server

_MODELS = {}
_REFS = {}


def tiny_model(kv_heads=4):
    """One tiny llama per kv-head layout (4 = MHA, 2 = GQA), shared by
    the whole module: jit programs are keyed on shapes, so reusing the
    model keeps the suite to a handful of compiles."""
    if kv_heads not in _MODELS:
        paddle.seed(0)
        from paddle_tpu.models import LlamaForCausalLM, llama_config
        cfg = llama_config("tiny", num_hidden_layers=1,
                           num_key_value_heads=kv_heads)
        _MODELS[kv_heads] = (LlamaForCausalLM(cfg), cfg)
    return _MODELS[kv_heads]


def ref_tokens(ids, n=6, kv_heads=4):
    """Greedy reference tokens from a module-cached plain paged engine
    (no prefix cache). Engines here serve one request at a time and
    drain fully, so reuse is safe — and each request's greedy tokens
    are batching-independent (PR 2's mixed-config parity bar), so a
    sequential reference is valid for concurrent runs too."""
    if kv_heads not in _REFS:
        _REFS[kv_heads] = paged_engine(tiny_model(kv_heads)[0])
    return _run_one(_REFS[kv_heads], np.asarray(ids, np.int32), n=n)


paged_engine = functools.partial(
    engine_helpers.paged_engine, max_batch=4, num_pages=64, page_size=4,
    max_pages=8, debug_pages=True)


def _greedy(n, eos=None):
    return GenerationConfig(max_new_tokens=n, eos_token_id=eos)


def _run_one(eng, ids, n=6, seg=4):
    rid = eng.add_request(ids, _greedy(n))
    while eng.decode_segment(seg):
        pass
    return list(dict(eng.collect_finished())[rid])


def _assert_no_leaks(eng):
    """All references released: every page is free or parked, no slot
    holds anything, and the refcount-aware validator is clean."""
    assert eng.free_slots() == eng.max_batch
    assert eng.alloc.used_pages == 0
    assert (eng.alloc.free_pages + eng.alloc.cached_pages
            == eng.num_pages)
    eng.alloc.check()


# -- allocator: refcount-aware invariant validator ---------------------------
class TestAllocatorSharing:
    def _alloc(self, num_pages=12, **kw):
        kw.setdefault("prefix_cache", True)
        return PageAllocator(num_pages=num_pages, page_size=4,
                             max_batch=3, max_pages=6, **kw)

    def _populate(self, a, toks, slot=0):
        """Cold-path bookkeeping: claim pages, register full blocks,
        release — the blocks park in the LRU. Returns the chain
        hashes."""
        _, _, hashes = a.lookup_prefix(toks)
        a.ensure(slot, len(toks))
        a.register_blocks(slot, hashes, toks, 0,
                          len(toks) // a.page_size)
        a.free_slot(slot)
        return hashes

    def test_shared_page_partitions_by_refcount(self):
        a = self._alloc()
        toks = np.arange(8, dtype=np.int32)
        self._populate(a, toks)
        assert a.cached_pages == 2
        pids, cov, _ = a.lookup_prefix(toks)
        assert cov == 8
        a.map_shared(0, pids)
        a.map_shared(1, list(pids))
        a.check()                       # refcount 2, two appearances
        assert a.shared_pages == 2
        a.free_slot(0)
        a.check()                       # refcount 1, one appearance
        assert a.shared_pages == 0
        a.free_slot(1)
        a.check()                       # parked again, still indexed
        assert a.cached_pages == 2 and a.used_pages == 0

    def test_appearance_without_refcount_detected(self):
        a = self._alloc()
        a.ensure(0, 4)
        a._owned[1] = [a._owned[0][0]]  # double-own, no refcount
        a.page_table[1, 0] = a._owned[0][0]
        with pytest.raises(RuntimeError, match="matching refcount"):
            a.check()

    def test_refcount_leak_detected(self):
        a = self._alloc()
        a.ensure(0, 4)
        a._ref[a._owned[0][0]] = 2      # refcount says 2, appears once
        with pytest.raises(RuntimeError, match="refcount"):
            a.check()

    def test_parked_page_also_free_detected(self):
        a = self._alloc()
        self._populate(a, np.arange(4, dtype=np.int32))
        pid = next(iter(a._parked))
        a._free.append(pid)
        with pytest.raises(RuntimeError, match="parked"):
            a.check()

    def test_indexed_unparked_orphan_detected(self):
        a = self._alloc()
        self._populate(a, np.arange(4, dtype=np.int32))
        a._parked.clear()               # indexed, ref 0, not parked
        with pytest.raises(RuntimeError, match="not.*parked|missing"):
            a.check()

    def test_lookup_is_token_verified(self):
        a = self._alloc()
        toks = np.arange(8, dtype=np.int32)
        self._populate(a, toks)
        # identical hash chain but corrupted recorded tokens: the
        # match must fail token verification, not alias KV
        pid = a._index[a.lookup_prefix(toks)[2][0]]
        a._tok_of[pid] = a._tok_of[pid] + 1
        pids, cov, _ = a.lookup_prefix(toks)
        assert cov == 0 and pids == []

    def test_partial_block_match(self):
        a = self._alloc()
        toks = np.arange(8, dtype=np.int32)
        self._populate(a, toks)
        # shares the first block and HALF the second
        probe = np.array([0, 1, 2, 3, 4, 5, 99, 98], np.int32)
        pids, cov, _ = a.lookup_prefix(probe)
        assert len(pids) == 2 and cov == 6

    def test_lru_reclaim_oldest_first_and_touch(self):
        a = self._alloc(num_pages=3)
        blocks = [np.full((4,), 10 + i, np.int32) for i in range(3)]
        for i, b in enumerate(blocks):
            self._populate(a, b, slot=0)
        assert a.cached_pages == 3 and a.free_pages == 0
        a.lookup_prefix(blocks[0])      # touch: 0 becomes most recent
        a.ensure(1, 4)                  # needs one page -> evict LRU
        assert a.cached_pages == 2
        assert a.lookup_prefix(blocks[1])[1] == 0     # evicted
        assert a.lookup_prefix(blocks[0])[1] == 4     # survived
        a.free_slot(1)
        a.check()

    def test_available_counts_parked(self):
        a = self._alloc(num_pages=3)
        self._populate(a, np.arange(12, dtype=np.int32))
        assert a.free_pages == 0 and a.available_pages == 3
        assert a.can_fit(1, 12)
        a.ensure(1, 12)                 # reclaims all parked pages
        assert a.cached_pages == 0
        a.free_slot(1)
        a.check()

    def test_cow_bookkeeping(self):
        a = self._alloc()
        toks = np.arange(4, dtype=np.int32)
        self._populate(a, toks)
        pids, _, _ = a.lookup_prefix(toks)
        a.map_shared(0, pids)
        a.map_shared(1, list(pids))
        old, new = a.cow(1, 0)
        assert old == pids[0] and new != old
        assert a._ref[old] == 1 and a._ref[new] == 1
        assert a.page_table[1, 0] == new
        assert a.cow_copies == 1
        a.check()
        a.free_slot(0)
        a.free_slot(1)
        # the original survived for slot 0 and re-parked after
        assert a.lookup_prefix(toks)[1] == 4
        a.check()

    def test_map_shared_needs_empty_slot(self):
        a = self._alloc()
        toks = np.arange(4, dtype=np.int32)
        self._populate(a, toks)
        a.ensure(0, 4)
        with pytest.raises(RuntimeError, match="empty slot"):
            a.map_shared(0, a.lookup_prefix(toks)[0])
        a.free_slot(0)

    def test_check_coverage_past_mapping(self):
        a = self._alloc()
        a.ensure(0, 8)                  # 2 pages = 8 positions
        a.check_coverage(0, 8)          # boundary: next write unmapped
        with pytest.raises(RuntimeError, match="extends past"):
            a.check_coverage(0, 9)

    def test_check_coverage_shared_write_detected(self):
        a = self._alloc()
        toks = np.arange(8, dtype=np.int32)
        self._populate(a, toks)
        pids, _, _ = a.lookup_prefix(toks)
        a.map_shared(0, pids)
        # live length 6: the next write (position 6) lands mid-way
        # into an indexed page — a forgotten copy-on-write
        with pytest.raises(RuntimeError, match="copy-on-write"):
            a.check_coverage(0, 6)
        a.cow(0, 1)
        a.check_coverage(0, 6)          # private now: fine
        a.free_slot(0)

    def test_disabled_prefix_cache_is_plain_allocator(self):
        a = self._alloc(prefix_cache=False)
        toks = np.arange(8, dtype=np.int32)
        pids, cov, _ = a.lookup_prefix(toks)
        a.ensure(0, 8)
        a.register_blocks(0, [], toks, 0, 2)   # no-op when disabled
        a.free_slot(0)
        assert a.cached_pages == 0 and a.free_pages == a.num_pages
        a.check()


# -- engine: bitwise parity cold vs warm -------------------------------------
class TestParity:
    @pytest.mark.parametrize("kv_heads", [4, 2])
    def test_cold_warm_cow_parity(self, kv_heads):
        model, cfg = tiny_model(kv_heads)
        rng = np.random.RandomState(0)
        eng = paged_engine(model, prefix_cache=True)

        donor = rng.randint(0, cfg.vocab_size, (12,)).astype(np.int32)
        want = ref_tokens(donor, kv_heads=kv_heads)
        assert _run_one(eng, donor) == want       # cold populates
        assert eng.alloc.cached_pages == 3
        assert _run_one(eng, donor) == want       # full block hit
        assert eng.alloc.prefix_hits == 1

        # divergence exactly at a block boundary: no CoW needed
        pb = donor.copy()
        pb[8] = (pb[8] + 1) % cfg.vocab_size
        assert _run_one(eng, pb) == ref_tokens(pb, kv_heads=kv_heads)
        assert eng.alloc.cow_copies == 0

        # divergent suffix mid-block: CoW before the first write
        pm = donor.copy()
        pm[10] = (pm[10] + 1) % cfg.vocab_size
        assert _run_one(eng, pm) == ref_tokens(pm, kv_heads=kv_heads)
        assert eng.alloc.cow_copies == 1

        # fully-cached prompt ending mid-page: decode's first append
        # lands in the shared tail page -> CoW
        pt = donor[:10].copy()
        assert _run_one(eng, pt) == ref_tokens(pt, kv_heads=kv_heads)
        assert eng.alloc.cow_copies == 2

        assert eng.alloc.prefix_hits >= 3
        assert eng.alloc.prefix_tokens_saved > 0
        _assert_no_leaks(eng)

        if kv_heads == 4:
            # the reference engine has no prefix-cache machinery at all
            # (no pages either) — and its tokens agree with the paged
            # warm path
            dense = CausalLMEngine(model, max_batch=1, max_len=32)
            assert list(dense.generate(donor[None], _greedy(6))[
                0, len(donor):]) == want

    def test_concurrent_sharing_parity(self):
        model, cfg = tiny_model()
        rng = np.random.RandomState(1)
        shared = rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32)
        prompts = [np.concatenate(
            [shared, rng.randint(0, cfg.vocab_size, (2,)).astype(np.int32)])
            for _ in range(3)]
        want = [ref_tokens(p) for p in prompts]

        eng = paged_engine(model, prefix_cache=True)
        srv = Server(eng, segment_steps=4)
        hs = [srv.submit(p, _greedy(6)) for p in prompts]
        got = [list(h.result(timeout=120)) for h in hs]
        hits = eng.alloc.prefix_hits
        srv.shutdown()
        _assert_no_leaks(eng)
        assert got == want
        assert hits >= 1

    def test_chunked_warm_parity(self):
        model, cfg = tiny_model()
        rng = np.random.RandomState(2)
        shared = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
        prompts = [np.concatenate(
            [shared, rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)])
            for _ in range(2)]
        # chunked admission is bitwise-equal to one-shot (PR 3), so the
        # plain one-shot reference engine is a valid chunked baseline
        want = [ref_tokens(p, n=5) for p in prompts]

        eng = paged_engine(model, prefill_chunk=8, prefix_cache=True)
        srv = Server(eng, segment_steps=4)
        hs = [srv.submit(p, _greedy(5)) for p in prompts]
        got = [list(h.result(timeout=120)) for h in hs]
        saved = eng.alloc.prefix_tokens_saved
        srv.shutdown()
        _assert_no_leaks(eng)
        assert got == want
        # the second admission starts its chunk cursor past the cached
        # coverage: whole chunks of prefill compute skipped
        assert saved >= 8


# -- lifecycle: every retirement decrements, never frees shared --------------
class TestLifecycle:
    def test_cancel_and_reset_state_decrement_leak_free(self):
        model, cfg = tiny_model()
        rng = np.random.RandomState(4)
        shared = rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32)
        p1 = np.concatenate([shared, [1, 2]]).astype(np.int32)
        p2 = np.concatenate([shared, [3, 4]]).astype(np.int32)
        want = ref_tokens(p1, n=10)

        eng = paged_engine(model, prefix_cache=True)
        r1 = eng.add_request(p1, _greedy(10))
        r2 = eng.add_request(p2, _greedy(10))
        eng.decode_segment(2)
        assert eng.alloc.shared_pages == 2
        eng.cancel_request(r2)
        eng.alloc.check()
        # the shared blocks survive for r1 (refcount 2 -> 1)
        assert eng.alloc.shared_pages == 0
        while eng.decode_segment(4):
            pass
        assert list(dict(eng.collect_finished())[r1]) == want
        _assert_no_leaks(eng)

        # reset_state on the same engine: the pools rebuild from
        # zeros, so the content index MUST go with them
        assert eng.alloc.cached_pages > 0
        eng.reset_state()
        assert eng.alloc.cached_pages == 0
        assert eng.alloc.free_pages == eng.num_pages
        assert eng.alloc.lookup_prefix(p1)[1] == 0
        eng.alloc.check()
        # and a fresh cold run still produces the same tokens
        assert _run_one(eng, p1, n=10) == want

    def test_chunked_abort_decrements_leak_free(self):
        model, cfg = tiny_model()
        rng = np.random.RandomState(5)
        shared = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
        eng = paged_engine(model, max_pages=16, prefill_chunk=8,
                           prefix_cache=True)
        donor = np.concatenate(
            [shared, rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)])
        want = _run_one(eng, donor, n=4)          # populates the cache
        cached = eng.alloc.cached_pages
        assert cached > 0
        # a warm chunked admission maps shared pages at begin_admit;
        # aborting mid-flight must release exactly its references.
        # The uncached tail spans >1 chunk so the first admit_chunk
        # cannot complete the admission
        adm = eng.begin_admit(np.concatenate(
            [shared, rng.randint(0, cfg.vocab_size, (17,)).astype(np.int32)]),
            _greedy(4))
        assert eng.admit_chunk(adm) is False
        eng.abort_admit(adm)
        eng.alloc.check()
        assert eng.alloc.cached_pages == cached
        _assert_no_leaks(eng)
        # the cache is still intact: the donor replays warm, same tokens
        assert _run_one(eng, donor, n=4) == want
        assert eng.alloc.prefix_hits >= 1

        # partial-block warm CHUNKED admission: coverage ends mid-page
        # (18 % 4 != 0), so the shared page copy-on-writes EAGERLY at
        # begin_admit — the claim is atomic with the reservation, gaps
        # before install cannot steal the spare page
        probe = np.concatenate(
            [donor[:18],
             rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)])
        adm2 = eng.begin_admit(probe, _greedy(4))
        assert eng.alloc.cow_copies >= 1
        while not eng.admit_chunk(adm2):
            pass
        while eng.decode_segment(4):
            pass
        got = list(dict(eng.collect_finished())[adm2.rid])
        assert got == ref_tokens(probe, n=4)
        _assert_no_leaks(eng)

    def test_preempt_releases_only_own_refs(self):
        model, cfg = tiny_model()
        rng = np.random.RandomState(6)
        shared = rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32)
        p1 = np.concatenate([shared, [5, 6]]).astype(np.int32)
        p2 = np.concatenate([shared, [7, 8]]).astype(np.int32)
        want = ref_tokens(p1, n=10)

        eng = paged_engine(model, prefix_cache=True,
                           admission_mode="optimistic")
        r1 = eng.add_request(p1, _greedy(10))
        r2 = eng.add_request(p2, _greedy(10))
        eng.decode_segment(2)
        assert eng.alloc.shared_pages == 2
        toks = eng.preempt_request(r2, reason="pressure")
        assert toks is not None
        eng.alloc.check()
        # r2's references released; the shared blocks stay mapped for
        # r1 — preemption must never free a page another slot reads
        slot1 = [s for s, r in eng._slot_req.items() if r == r1][0]
        row1 = set(eng.alloc._owned[slot1])
        assert all(eng.alloc._ref.get(p, 0) >= 1 for p in row1)
        while eng.decode_segment(4):
            pass
        assert list(dict(eng.collect_finished())[r1]) == want
        _assert_no_leaks(eng)

    def test_preempt_replay_warm_parity_under_pressure(self):
        """Optimistic small pool + shared prefixes: pressure preempts
        a sharer, the replay re-admits WARM, and every request's
        greedy tokens still match an unpressured run (with the
        refcount-aware validator armed per gap)."""
        model, cfg = tiny_model()
        rng = np.random.RandomState(7)
        shared = rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32)
        prompts = [np.concatenate(
            [shared, rng.randint(0, cfg.vocab_size, (2,)).astype(np.int32)])
            for _ in range(3)]
        maxes = [12, 12, 12]

        want = [ref_tokens(p, n=m) for p, m in zip(prompts, maxes)]
        eng = paged_engine(model, max_batch=3, num_pages=12,
                           prefix_cache=True,
                           admission_mode="optimistic")
        srv = Server(eng, segment_steps=4, max_preemptions=10)
        hs = [srv.submit(p, _greedy(m)) for p, m in zip(prompts, maxes)]
        got = [list(h.result(timeout=180)) for h in hs]
        preempts = eng.alloc.preemptions
        srv.shutdown()
        _assert_no_leaks(eng)
        assert got == want
        assert preempts >= 1


# -- LRU reclaim under pressure ----------------------------------------------
class TestReclaim:
    def test_parked_pages_reclaimed_on_demand(self):
        model, cfg = tiny_model()
        rng = np.random.RandomState(9)
        # pool of 8: a retired 12-token donor parks 3 cached pages
        eng = paged_engine(model, max_batch=2, num_pages=8,
                           prefix_cache=True)
        donor = rng.randint(0, cfg.vocab_size, (12,)).astype(np.int32)
        _run_one(eng, donor, n=4)
        assert eng.alloc.cached_pages == 3
        # can_admit == True must mean add_request cannot raise for
        # capacity, even with most of the pool parked
        probe = rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32)
        if eng.can_admit(len(probe), _greedy(4)):
            _run_one(eng, probe, n=4)
        # an unrelated request needing more than the strictly-free
        # pages must succeed by evicting parked cache pages
        other = rng.randint(0, cfg.vocab_size, (12,)).astype(np.int32)
        need = eng.alloc.pages_for(12 + 10)
        assert need > eng.alloc.free_pages
        _run_one(eng, other, n=10)
        eng.alloc.check()
        assert eng.free_slots() == eng.max_batch

    def test_full_pool_request_still_admits(self):
        """A request whose worst case exactly fills the pool must
        admit with the cache on (the probe never demands CoW slack);
        a warm partial-block hit DEGRADES to full blocks instead of
        demanding the page the pool cannot spare — parity holds."""
        model, cfg = tiny_model()
        rng = np.random.RandomState(13)
        eng = paged_engine(model, max_batch=2, num_pages=8,
                           prefix_cache=True)
        donor = rng.randint(0, cfg.vocab_size, (20,)).astype(np.int32)
        g = _greedy(12)                     # 32 tokens = whole pool
        assert eng.can_admit(20, g)
        assert _run_one(eng, donor, n=12) == ref_tokens(donor, n=12)
        # warm, partial-block coverage (18 % 4 != 0), full pool again:
        # the partial page's CoW cannot fit -> hit degrades to 16
        probe = donor[:18].copy()
        gp = _greedy(14)
        assert eng.can_admit(18, gp)
        assert _run_one(eng, probe, n=14) == ref_tokens(probe, n=14)
        assert eng.alloc.cow_copies == 0    # degraded, never CoW'd
        assert eng.alloc.prefix_hits == 1
        eng.alloc.check()


# -- metrics and surfaces ----------------------------------------------------
class TestMetrics:
    def test_counters_pressure_surface_and_series_lifecycle(self):
        from paddle_tpu import monitor

        model, cfg = tiny_model()
        ids = np.random.RandomState(11).randint(
            0, cfg.vocab_size, (10,)).astype(np.int32)
        monitor.enable()
        try:
            eng = paged_engine(model, prefix_cache=True)
            pool = eng.alloc.monitor_pool
            srv = Server(eng, segment_steps=4)
            assert list(srv.submit(ids, _greedy(4)).result(timeout=60))
            assert list(srv.submit(ids, _greedy(4)).result(timeout=60))
            p = srv.pressure()
            assert p["prefix_cache"] is True
            assert p["prefix_hits"] == 1
            assert p["prefix_lookups"] == 2
            assert p["prefix_tokens_saved"] > 0
            assert p["cached_pages"] > 0
            srv.shutdown()

            def series(name):
                snap = monitor.snapshot()["metrics"]
                return [s for s in snap.get(name, {}).get("samples", [])
                        if s["labels"].get("pool") == pool]

            hits = series("paddle_tpu_kv_prefix_hits_total")
            assert hits and hits[0]["value"] == 1
            saved = series("paddle_tpu_kv_prefix_tokens_saved_total")
            assert saved and saved[0]["value"] > 0
            assert series("paddle_tpu_kv_shared_pages") != []
            eng.close()
            for name in ("paddle_tpu_kv_prefix_hits_total",
                         "paddle_tpu_kv_prefix_tokens_saved_total",
                         "paddle_tpu_kv_shared_pages"):
                assert series(name) == [], name
        finally:
            monitor.disable()


@pytest.mark.slow
def test_serve_bench_prefix_ab_smoke(capsys):
    """serve_bench --shared-prefix-len/--cache-prefixes end to end: the
    warm run records a positive hit rate and tokens saved."""
    import json

    from tools.serve_bench import main as bench_main

    rc = bench_main(["--shared-prefix-len", "32", "--cache-prefixes",
                     "on", "--requests", "8", "--rate", "16",
                     "--max-new", "4", "--prompt-len", "2:4",
                     "--num-pages", "64", "--max-pages", "16",
                     "--warmup"])
    assert rc == 0
    recs = {}
    for line in capsys.readouterr().out.splitlines():
        try:
            r = json.loads(line)
            recs[r["metric"]] = r["value"]
        except (json.JSONDecodeError, KeyError):
            continue
    assert recs["serve_prefix_hit_rate"] > 0
    assert recs["serve_prefill_tokens_saved"] > 0
