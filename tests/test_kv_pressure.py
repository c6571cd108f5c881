"""KV memory-pressure suite (ISSUE 5): optimistic paged admission with
preempt-and-replay.

Covers the graceful-degradation contract on CPU:

- ``PageAllocator.check()`` invariant validator (free ∪ owned
  partitions the pool; page-table rows mirror ownership) and the
  ``debug_pages`` per-op arming;
- admission modes: optimistic claims prompt + one page and GROWS per
  gap; the ``kv_watermark`` pauses new admissions under crowding (but
  never an idle pool); validation of the knobs;
- PARITY: a greedy run with forced preemption (small pool) is
  bitwise-identical to the same workload unpreempted;
- ACCEPTANCE: optimistic mode completes a workload reserved mode
  cannot even admit at equal ``num_pages``, with >= 1 preemption
  observed, zero leaked pages, and the oldest request never preempted;
- rails: per-request ``max_preemptions`` fails a thrasher with
  ``PreemptionBudgetExceeded``; a request the pool cannot hold even
  alone fails ALONE with ``PagePoolExhausted`` as its typed cause
  (request-scoped, not an engine restart);
- races: preempt-then-cancel and preempt-then-engine-restart compose
  with the PR 4 recovery machinery (handles terminal exactly once,
  ``fault_stats``/drain stay accurate), and pressure during a chunked
  admission aborts the claim without leaking slot/pages;
- queue priority aging (``age_after_s``) un-starves low-priority work;
- the ``pressure`` surface: ``Server.pressure()`` and ``/healthz``.

Every paged engine here runs with ``debug_pages=True`` — the
allocator's invariant validator is armed at every page op and every
gap, so any reclaim bug in the preemption paths fails the suite
loudly.
"""
import functools
import json
import time
import urllib.request

import numpy as np
import pytest

import engine_helpers
from engine_helpers import BareEngine
import paddle_tpu as paddle
from paddle_tpu.inference.generation import (
    ADMISSION_MODES, CausalLMEngine, EngineFault, GenerationConfig,
    PagePoolExhausted)
from paddle_tpu.inference.paged_cache import PageAllocator
from paddle_tpu.serving import (RequestCancelled, RequestFailed, Server,
                                serve_http)
from paddle_tpu.serving.queue import RequestHandle, RequestQueue
from paddle_tpu.serving.scheduler import PreemptionBudgetExceeded

_MODEL = None


def tiny_model():
    """ONE tiny llama shared by the whole module: jit programs are
    keyed on shapes, so reusing the model (and the same page_size /
    bucket shapes below) keeps the suite to a handful of compiles."""
    global _MODEL
    if _MODEL is None:
        paddle.seed(0)
        from paddle_tpu.models import LlamaForCausalLM, llama_config
        cfg = llama_config("tiny", num_hidden_layers=1)
        _MODEL = (LlamaForCausalLM(cfg), cfg)
    return _MODEL


paged_engine = functools.partial(
    engine_helpers.paged_engine, max_batch=4, num_pages=64, page_size=4,
    max_pages=8, debug_pages=True)


def _greedy(n, eos=None):
    return GenerationConfig(max_new_tokens=n, eos_token_id=eos)


def _prompts(cfg, n, plen=6, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (plen,)).astype(np.int32)
            for _ in range(n)]


def _reference(prompts, maxes, eos=None):
    """Expected greedy tokens via a big reserved-mode pool (no
    pressure possible) — the parity baseline."""
    model, _ = tiny_model()
    eng = paged_engine(model)
    srv = Server(eng, segment_steps=4)
    hs = [srv.submit(p, _greedy(m, eos)) for p, m in zip(prompts, maxes)]
    out = [h.result(timeout=180) for h in hs]
    srv.shutdown()
    return out


def _assert_no_leaks(eng):
    assert eng.free_slots() == eng.max_batch
    assert eng.alloc.free_pages == eng.num_pages
    eng.alloc.check()


# -- allocator invariant validator ------------------------------------------
class TestAllocatorCheck:
    def _alloc(self, debug=False):
        return PageAllocator(num_pages=8, page_size=4, max_batch=2,
                             max_pages=6, debug=debug)

    def test_clean_states_pass(self):
        a = self._alloc()
        a.check()                       # empty pool
        a.ensure(0, 10)                 # 3 pages
        a.ensure(1, 4)
        a.check()
        a.free_slot(0)
        a.check()

    def test_double_owned_page_detected(self):
        a = self._alloc()
        a.ensure(0, 4)
        a._owned[1] = [a._owned[0][0]]  # same page owned twice
        # the sharing-era check reports this as a refcount mismatch
        # (two appearances, refcount 1) — sharing is only legal with
        # matching refcount accounting
        with pytest.raises(RuntimeError, match="matching refcount"):
            a.check()

    def test_lost_page_detected(self):
        a = self._alloc()
        a.ensure(0, 4)
        a._owned[0] = []                # page vanished from both sides
        a.page_table[0, :] = -1
        # refcount says 1, appears nowhere: the sharing-era check
        # flags the leak before the partition sweep reports 'missing'
        with pytest.raises(RuntimeError, match="refcount leak|missing"):
            a.check()

    def test_free_list_duplicate_detected(self):
        a = self._alloc()
        pid = a._free[0]
        a._free.append(pid)
        with pytest.raises(RuntimeError, match="twice in the free"):
            a.check()

    def test_stale_table_row_detected(self):
        a = self._alloc()
        a.ensure(0, 8)
        a.page_table[0, 0] = 99         # table disagrees with _owned
        with pytest.raises(RuntimeError, match="row 0 inconsistent"):
            a.check()

    def test_debug_flag_arms_every_op(self):
        a = self._alloc(debug=True)
        a.ensure(0, 8)
        a.page_table[0, 1] = -1         # corrupt between ops
        with pytest.raises(RuntimeError, match="inconsistent"):
            a.ensure(1, 4)              # next op trips the validator


# -- admission-mode knobs ----------------------------------------------------
class TestAdmissionModes:
    def test_knob_validation(self):
        model, _ = tiny_model()
        with pytest.raises(ValueError, match="admission_mode"):
            paged_engine(model, admission_mode="eager")
        for bad in (0, -0.1, 1.5):
            with pytest.raises(ValueError, match="kv_watermark"):
                paged_engine(model, admission_mode="optimistic",
                             kv_watermark=bad)
        assert ADMISSION_MODES == ("reserved", "optimistic")

    def test_server_mirror_needs_idle_paged_engine(self):
        model, _ = tiny_model()
        bare = BareEngine(paged_engine(model))
        with pytest.raises(ValueError, match="paged engine"):
            Server(bare, admission_mode="optimistic", start=False)
        with pytest.raises(ValueError, match="admission_mode"):
            Server(paged_engine(model), admission_mode="nope",
                   start=False)
        eng = paged_engine(model)
        srv = Server(eng, admission_mode="optimistic", start=False)
        assert eng.admission_mode == "optimistic"
        srv.shutdown(drain=False)
        busy = paged_engine(model)
        busy.add_request(np.arange(4, dtype=np.int32), _greedy(4))
        with pytest.raises(ValueError, match="idle"):
            Server(busy, admission_mode="optimistic", start=False)

    def test_optimistic_claim_is_prompt_plus_one_page(self):
        model, _ = tiny_model()
        eng = paged_engine(model, admission_mode="optimistic")
        cfg = _greedy(20)
        assert eng._optimistic_claim(6, cfg) == 6 + eng.page_size
        # never beyond the reserved worst case
        assert (eng._optimistic_claim(6, _greedy(1))
                == eng._reserved(6, _greedy(1)))

    def test_watermark_pauses_new_admissions_but_not_idle(self):
        model, _ = tiny_model()
        eng = paged_engine(model, num_pages=8, admission_mode="optimistic",
                           kv_watermark=0.5)
        cfg = _greedy(8)
        # idle pool: the watermark must NOT block a lone admission
        assert eng.can_admit(6, cfg)
        eng.add_request(np.arange(6, dtype=np.int32), cfg)  # 3 pages
        # 3 used + 3 more would cross 0.5 * 8 = 4 -> paused
        assert not eng.can_admit(6, cfg)
        # reserved mode at the same occupancy would also refuse (worst
        # case 14 tokens = 4 pages > 5 free is fine, but watermark is
        # not consulted): check the optimistic refusal came from the
        # watermark, not can_fit
        assert eng.alloc.can_fit(eng._free[0],
                                 eng._optimistic_claim(6, cfg))
        eng.cancel_request(next(iter(eng._slot_req.values())))
        _assert_no_leaks(eng)


# -- engine-level grow / preempt / exhaustion guard --------------------------
class TestEngineGrowPreempt:
    def test_exhaustion_is_loud_and_preempt_unblocks(self):
        """A bare engine driver that ignores pressure sees
        PagePoolExhausted from decode_segment (never a silent dropped
        KV write); preempt_request reclaims the victim and decoding
        continues."""
        model, mcfg = tiny_model()
        eng = paged_engine(model, num_pages=10,
                           admission_mode="optimistic", kv_watermark=1.0)
        p1, p2 = _prompts(mcfg, 2)
        r1 = eng.add_request(p1, _greedy(24))
        r2 = eng.add_request(p2, _greedy(24))
        with pytest.raises(PagePoolExhausted) as ei:
            for _ in range(8):
                eng.decode_segment(4)
        assert set(ei.value.rids) <= {r1, r2}
        toks = eng.preempt_request(r2)
        assert toks is not None and len(toks) >= 1
        assert eng.preempt_request(r2) is None      # not active now
        assert eng.alloc.preemptions == 1
        while eng.decode_segment(4):
            pass
        done = eng.collect_finished()
        assert len(done[r1]) == 24
        _assert_no_leaks(eng)

    def test_serve_parity_under_repeated_preemption(self):
        """Bare ``engine.serve()`` on a tight pool preempts the SAME
        request more than once (each replay re-admits with the newest
        rid, so it stays the preferred victim while the oldest
        survives) — its replay budget must be measured against the
        ORIGINAL cfg each time; measuring against an earlier replay's
        already-reduced ``max_new_tokens`` double-subtracts the first
        prefix and silently truncates the result."""
        model, mcfg = tiny_model()
        prompts = _prompts(mcfg, 3)
        ref = paged_engine(model).serve(prompts, _greedy(24),
                                        segment_steps=4)
        eng = paged_engine(model, num_pages=12,
                           admission_mode="optimistic", kv_watermark=1.0)
        out = eng.serve(prompts, _greedy(24), segment_steps=4)
        # more preemptions than preemptable requests: some request
        # replayed with a non-empty prior prefix (oldest is never
        # the victim, so at most 2 of the 3 are preemptable)
        assert eng.alloc.preemptions >= 3
        for a, b in zip(ref, out):
            assert np.array_equal(a, b)
        _assert_no_leaks(eng)

    def test_grow_noop_in_reserved_mode(self):
        model, mcfg = tiny_model()
        eng = paged_engine(model, num_pages=10)
        eng.add_request(_prompts(mcfg, 1)[0], _greedy(8))
        assert eng.grow_for_segment(4) == []
        while eng.decode_segment(4):
            pass
        eng.collect_finished()
        _assert_no_leaks(eng)

    def test_growth_stamp_skips_redundant_recheck(self):
        """A clean grow_for_segment(n) stamps the engine so the
        scheduler's decode_segment(n) in the same gap skips its
        (device-syncing) exhaustion re-check; the stamp is single-shot
        (the segment advances lens) and any new admission invalidates
        it, so the loud-failure guard still fires for bare drivers
        that skip pressure relief."""
        model, mcfg = tiny_model()
        eng = paged_engine(model, num_pages=64,
                           admission_mode="optimistic", kv_watermark=1.0)
        p = _prompts(mcfg, 2)
        eng.add_request(p[0], _greedy(8))
        assert eng._growth_stamp is None     # admission invalidates
        assert eng.grow_for_segment(4) == []
        assert eng._growth_stamp == 4
        eng.add_request(p[1], _greedy(8))
        assert eng._growth_stamp is None     # new slot: stamp is stale
        assert eng.grow_for_segment(4) == []
        eng.decode_segment(4)
        assert eng._growth_stamp is None     # consumed single-shot
        while eng.decode_segment(4):
            pass
        eng.collect_finished()
        _assert_no_leaks(eng)


# -- server-level preemption -------------------------------------------------
class TestServerPreemption:
    def test_parity_and_acceptance_under_forced_preemption(self):
        """THE acceptance test: greedy tokens under forced preemption
        are bitwise-identical to the unpreempted baseline; >= 1
        preemption actually happened; the oldest request was never
        preempted; zero pages leaked (validator clean at exit)."""
        model, mcfg = tiny_model()
        prompts = _prompts(mcfg, 4)
        ref = _reference(prompts, [20] * 4)
        # 4 x (6 + 20) tokens = 28 worst-case pages; 14 forces pressure
        eng = paged_engine(model, num_pages=14,
                           admission_mode="optimistic", kv_watermark=1.0)
        srv = Server(eng, segment_steps=4, max_preemptions=50)
        hs = [srv.submit(p, _greedy(20)) for p in prompts]
        out = [h.result(timeout=180) for h in hs]
        for a, b in zip(ref, out):
            assert np.array_equal(a, b)
        assert eng.alloc.preemptions >= 1
        assert sum(h._preempts for h in hs) >= 1
        assert hs[0]._preempts == 0        # oldest never preempted
        assert srv.drain(timeout=30)
        _assert_no_leaks(eng)
        pr = srv.pressure()
        assert pr["preemptions"] == eng.alloc.preemptions
        assert pr["admission_mode"] == "optimistic"
        assert pr["waiting_on_pages"] == 0 and pr["occupancy"] == 0.0
        srv.shutdown()

    def test_optimistic_completes_what_reserved_cannot_admit(self):
        """Equal num_pages: reserved mode cannot even ADMIT the
        requests (worst case 26 tokens = 7 pages > the 6-page pool),
        optimistic completes all three because they stop on EOS early
        (10 generated tokens = 4 pages actually used) — the whole
        EOS-early gap the optimistic policy exists to harvest."""
        model, mcfg = tiny_model()
        # IDENTICAL prompts: greedy streams are identical, so one EOS
        # value (the reference's 10th token) cuts every request at 10
        # generated tokens while max_new_tokens stays 20
        p = _prompts(mcfg, 1)[0]
        ref = list(map(int, _reference([p], [20])[0]))
        eos = ref[9]
        assert ref.index(eos) == 9      # seeded run: first occurrence
        want = ref[:10]

        def build(mode):
            return paged_engine(model, num_pages=6,
                                admission_mode=mode, kv_watermark=1.0)

        res = build("reserved")
        srv = Server(res, segment_steps=4)
        h = srv.submit(p, _greedy(20, eos))
        with pytest.raises(RequestFailed, match="never be admitted"):
            h.result(timeout=60)
        srv.shutdown()
        _assert_no_leaks(res)

        opt = build("optimistic")
        srv2 = Server(opt, segment_steps=4, max_preemptions=50)
        hs = [srv2.submit(p, _greedy(20, eos)) for _ in range(3)]
        out = [list(map(int, h.result(timeout=180))) for h in hs]
        assert out == [want] * 3
        assert opt.alloc.preemptions >= 1
        assert hs[0]._preempts == 0
        assert srv2.drain(timeout=30)
        _assert_no_leaks(opt)
        srv2.shutdown()

    def test_preemption_budget_exceeded_typed_failure(self):
        """max_preemptions=0: the first preemption fails the victim
        with PreemptionBudgetExceeded as the cause instead of
        replaying it — and everyone else still completes."""
        model, mcfg = tiny_model()
        prompts = _prompts(mcfg, 3)
        eng = paged_engine(model, num_pages=10,
                           admission_mode="optimistic", kv_watermark=1.0)
        srv = Server(eng, segment_steps=4, max_preemptions=0)
        hs = [srv.submit(p, _greedy(16)) for p in prompts]
        failed = 0
        for h in hs:
            try:
                assert len(h.result(timeout=180)) == 16
            except RequestFailed as e:
                assert isinstance(e.__cause__,
                                  PreemptionBudgetExceeded)
                failed += 1
        assert failed >= 1
        assert hs[0].status == "finished"    # oldest always survives
        assert srv.drain(timeout=30)
        _assert_no_leaks(eng)
        srv.shutdown()

    def test_unsatisfiable_request_fails_alone(self):
        """A request whose growth cannot fit even with the pool to
        itself fails with PagePoolExhausted as its typed cause — a
        request-scoped, contained event (no engine restart, no other
        victims)."""
        model, mcfg = tiny_model()
        # pool holds 16 tokens; request wants 6 + 20 = 26 <= max_len 32
        eng = paged_engine(model, num_pages=4,
                           admission_mode="optimistic", kv_watermark=1.0)
        srv = Server(eng, segment_steps=4)
        h = srv.submit(_prompts(mcfg, 1)[0], _greedy(20))
        with pytest.raises(RequestFailed) as ei:
            h.result(timeout=120)
        assert isinstance(ei.value.__cause__, PagePoolExhausted)
        assert srv.restarts == 0             # contained, not recovered
        assert srv.fault_stats()["faults"] == {}
        # the server still serves: a fitting request completes
        h2 = srv.submit(_prompts(mcfg, 1)[0], _greedy(4))
        assert len(h2.result(timeout=120)) == 4
        assert srv.drain(timeout=30)
        _assert_no_leaks(eng)
        srv.shutdown()

    def test_preempt_then_cancel(self):
        """A preempted handle parked on the replay list is cancelled:
        it finishes CANCELLED exactly once, never re-admits, and no
        capacity leaks."""
        model, mcfg = tiny_model()
        eng = paged_engine(model, num_pages=10,
                           admission_mode="optimistic", kv_watermark=1.0)
        srv = Server(eng, segment_steps=4, max_preemptions=50)
        p = _prompts(mcfg, 2)
        h_old = srv.submit(p[0], _greedy(24))   # hogs the pool
        h_vic = srv.submit(p[1], _greedy(24))
        deadline = time.monotonic() + 120
        while h_vic._preempts == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert h_vic._preempts >= 1
        h_vic.cancel()
        with pytest.raises(RequestCancelled):
            h_vic.result(timeout=120)
        assert len(h_old.result(timeout=120)) == 24
        assert srv.drain(timeout=30)
        _assert_no_leaks(eng)
        srv.shutdown()

    def test_preempt_then_engine_restart_composes(self):
        """An engine-scoped fault while a preempted handle sits on the
        replay list: recovery replays BOTH the in-flight and the
        preempted requests; greedy tokens stay bitwise-identical;
        fault_stats/drain stay accurate."""
        from paddle_tpu.testing.faults import FaultPlan, FaultyEngine

        model, mcfg = tiny_model()
        prompts = _prompts(mcfg, 3)
        ref = _reference(prompts, [16] * 3)
        plan = FaultPlan().raise_at(
            "decode", nth=4, exc=EngineFault("injected"))
        eng = paged_engine(model, num_pages=10,
                           admission_mode="optimistic", kv_watermark=1.0)
        srv = Server(FaultyEngine(eng, plan), segment_steps=4,
                     max_preemptions=50, max_restarts=3, max_replays=8,
                     restart_backoff_s=0.01)
        hs = [srv.submit(p, _greedy(16)) for p in prompts]
        out = [h.result(timeout=180) for h in hs]
        for a, b in zip(ref, out):
            assert np.array_equal(a, b)
        assert srv.restarts == 1
        assert eng.alloc.preemptions >= 1
        fs = srv.fault_stats()
        assert fs["faults"].get(("engine", "decode")) == 1
        assert fs["degraded"] is None
        assert srv.drain(timeout=30)
        _assert_no_leaks(eng)
        srv.shutdown()

    def test_pressure_during_chunked_admission_aborts_claim(self):
        """When growth pressure hits with only the oldest request
        active, the in-flight chunked admission is the victim: its
        claim aborts (slot + pages reclaimed), the handle parks with a
        preemption charged, and it completes via replay once the pool
        breathes — zero leaks throughout (validator armed)."""
        model, mcfg = tiny_model()
        rng = np.random.RandomState(3)
        long_p = rng.randint(0, mcfg.vocab_size, (12,)).astype(np.int32)
        short_p = _prompts(mcfg, 1)[0]
        ref = _reference([short_p, long_p], [20, 8])
        eng = paged_engine(model, num_pages=8,
                           admission_mode="optimistic",
                           kv_watermark=1.0, prefill_chunk=4)
        srv = Server(eng, segment_steps=4, max_preemptions=50)
        h_old = srv.submit(short_p, _greedy(20))
        time.sleep(0.05)                 # oldest admits first
        h_chk = srv.submit(long_p, _greedy(8))
        out = [h_old.result(timeout=180), h_chk.result(timeout=180)]
        assert np.array_equal(out[0], ref[0])
        assert np.array_equal(out[1], ref[1])
        assert eng.alloc.preemptions >= 1
        assert h_old._preempts == 0
        assert srv.drain(timeout=30)
        _assert_no_leaks(eng)
        srv.shutdown()

    def test_pressure_aborted_admission_keeps_deadline(self):
        """A handle parked for replay WITHOUT ever completing an
        admission (``engine_rid is None`` — its in-flight chunked
        claim was aborted by pressure relief) still honours its
        admission deadline: ``_admit_replays`` expires it instead of
        serving it late. A handle that DID admit once (``engine_rid``
        set) is exempt — its deadline was met the first time, so a
        crowded pool defers it rather than expiring it."""
        from paddle_tpu.serving.queue import DeadlineExpired

        model, mcfg = tiny_model()
        eng = paged_engine(model, num_pages=4,
                           admission_mode="optimistic", kv_watermark=1.0)
        srv = Server(eng, segment_steps=4, max_preemptions=50)
        srv.shutdown()       # loop stopped, engine alive: the test
        #                      thread drives _admit_replays directly
        hog = eng.add_request(_prompts(mcfg, 1)[0], _greedy(24))
        p = _prompts(mcfg, 1, seed=7)[0]
        dead = RequestHandle(990, p, len(p), _greedy(8),
                             deadline=time.monotonic() - 0.1)
        met = RequestHandle(991, p, len(p), _greedy(8),
                            deadline=time.monotonic() - 0.1)
        met.engine_rid = 12345      # admitted once, then preempted
        srv._replay.extend([dead, met])
        srv._admit_replays()
        srv._flush()         # the loop hands the finish over after it
        assert dead.status == "expired"
        with pytest.raises(DeadlineExpired):
            dead.result(timeout=1)
        assert met.status == "queued"       # deferred, NOT expired
        assert met in srv._replay
        eng.cancel_request(hog)
        _assert_no_leaks(eng)

    def test_pressure_surface_healthz(self):
        """/healthz carries the pressure block for a paged engine
        (occupancy, waiting_on_pages, preemptions) and omits it for a
        dense engine — operators can tell memory-pressure degradation
        apart from the stall/fault degraded reason."""
        model, mcfg = tiny_model()
        eng = paged_engine(model, num_pages=10,
                           admission_mode="optimistic", kv_watermark=1.0)
        srv = Server(eng, segment_steps=4, max_preemptions=50)
        hs = [srv.submit(p, _greedy(16)) for p in _prompts(mcfg, 3)]
        for h in hs:
            h.result(timeout=180)
        httpd = serve_http(srv, port=0)
        try:
            port = httpd.server_address[1]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz") as r:
                body = json.loads(r.read())
            assert body["status"] == "ok"
            pr = body["pressure"]
            assert pr["admission_mode"] == "optimistic"
            assert pr["preemptions"] == eng.alloc.preemptions >= 1
            assert pr["free_pages"] == eng.num_pages
        finally:
            httpd.shutdown()
            srv.shutdown()
        srv2 = Server(BareEngine(paged_engine(model)), segment_steps=4)
        assert srv2.pressure() is None
        httpd2 = serve_http(srv2, port=0)
        try:
            port = httpd2.server_address[1]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz") as r:
                body = json.loads(r.read())
            assert "pressure" not in body
        finally:
            httpd2.shutdown()
            srv2.shutdown()


# -- monitor export ----------------------------------------------------------
class TestMonitorExport:
    def test_preemption_family_exported_and_retired(self):
        """paddle_tpu_kv_preemptions_total{pool,reason} and the
        per-server kv_pressure gauge export while serving and retire
        with alloc.close()/server shutdown (the monitor_report
        --serving families)."""
        from paddle_tpu import monitor
        monitor.enable()
        monitor.reset()
        try:
            model, mcfg = tiny_model()
            eng = paged_engine(model, num_pages=10,
                               admission_mode="optimistic",
                               kv_watermark=1.0)
            srv = Server(eng, segment_steps=4, max_preemptions=50)
            hs = [srv.submit(p, _greedy(16)) for p in _prompts(mcfg, 3)]
            for h in hs:
                h.result(timeout=180)
            snap = monitor.snapshot()["metrics"]
            samples = snap.get("paddle_tpu_kv_preemptions_total",
                               {}).get("samples", [])
            assert sum(s["value"] for s in samples) \
                == eng.alloc.preemptions >= 1
            assert any(s["labels"].get("reason") == "pressure"
                       for s in samples)
            assert snap.get("paddle_tpu_serving_kv_pressure",
                            {}).get("samples")
            srv.shutdown()
            eng.close()
            snap2 = monitor.snapshot()["metrics"]
            assert not snap2.get("paddle_tpu_kv_preemptions_total",
                                 {}).get("samples", [])
            assert not snap2.get("paddle_tpu_serving_kv_pressure",
                                 {}).get("samples", [])
        finally:
            monitor.reset()
            monitor.disable()


# -- queue priority aging ----------------------------------------------------
class TestPriorityAging:
    def _handle(self, rid, priority, age_s=0.0):
        h = RequestHandle(rid, np.arange(4, dtype=np.int32), 4,
                          _greedy(4), priority=priority)
        h.submit_ts -= age_s
        return h

    def test_validation(self):
        with pytest.raises(ValueError, match="age_after_s"):
            RequestQueue(4, age_after_s=0.0)
        with pytest.raises(ValueError, match="age_after_s"):
            RequestQueue(4, age_after_s=-1)

    def test_static_priority_starves_without_aging(self):
        q = RequestQueue(4)
        q.put(self._handle(0, priority=5, age_s=100.0))
        q.put(self._handle(1, priority=0))
        q.reap(time.monotonic())
        assert q.pop_if(lambda h: True).id == 1

    def test_aging_bumps_long_waiters(self):
        q = RequestQueue(4, age_after_s=10.0)
        q.put(self._handle(0, priority=5, age_s=100.0))   # 10 levels
        q.put(self._handle(1, priority=0))
        q.reap(time.monotonic())
        # effective priority 5 - 10 = -5 beats the fresh 0
        assert q.pop_if(lambda h: True).id == 0
        assert q.pop_if(lambda h: True).id == 1

    def test_fifo_within_effective_level_preserved(self):
        q = RequestQueue(4, age_after_s=10.0)
        a = self._handle(0, priority=1, age_s=11.0)   # -> effective 0
        b = self._handle(1, priority=0)
        c = self._handle(2, priority=0)
        q.put(b)
        q.put(c)
        q.put(a)
        q.reap(time.monotonic())
        # a reached level 0 but entered the queue LAST: b, c keep
        # their FIFO precedence at that level
        assert [q.pop_if(lambda h: True).id for _ in range(3)] \
            == [1, 2, 0]

    def test_server_passes_age_after_s_through(self):
        model, _ = tiny_model()
        eng = paged_engine(model)
        srv = Server(eng, age_after_s=0.5, start=False)
        assert srv.queue.age_after_s == 0.5
        srv.shutdown(drain=False)
