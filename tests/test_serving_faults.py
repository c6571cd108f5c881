"""Chaos suite for the fault-isolated serving path (ISSUE 4).

Covers the blast-radius contract end to end on CPU, driven by the
deterministic injection harness (`paddle_tpu.testing.faults`):

- per-request CONTAINMENT: a fault injected at a request-scoped seam
  (admission call, prefill inside the abort guard, chunked-prefill
  chunk) fails ONLY the poisoned request with its cause; concurrent
  requests complete with token parity vs a fault-free run, and after
  drain the slot heap and page free-list show zero leaked capacity;
- supervised ENGINE RECOVERY: an engine-scoped fault during
  ``decode_segment`` triggers reset + replay (re-prefill of
  prompt + generated) within ``max_restarts``; greedy in-flight
  requests finish with IDENTICAL final tokens; per-request
  ``max_replays`` and server ``max_restarts`` budgets both enforce,
  the latter falling through to the fatal path (prompt terminal
  states, never hangs);
- the STALL WATCHDOG: an injected hang flips ``/healthz`` to
  ``degraded`` (503) within ``stall_timeout_s`` and clears when the
  loop beats again; a degraded server rejects submissions with reason;
- satellites: client-disconnect reclaim (BrokenPipe mid-stream →
  cancel → slot AND pages back), failed/degraded HTTP surfacing,
  shutdown/drain during warmup and submit-after-crash returning
  promptly, monitor fault/restart/degraded export, and the
  serve_bench chaos soak (slow tier).
"""
import functools
import json
import threading
import time

import numpy as np
import pytest

import engine_helpers
import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference.generation import (CausalLMEngine, EngineFault,
                                             GenerationConfig,
                                             RequestFault, classify_fault)
from paddle_tpu.serving import (ControlPlane, ControlPolicy,
                                ElasticController, RequestCancelled,
                                RequestFailed, RequestRejected, Server,
                                serve_http)
from paddle_tpu.testing.faults import (NET_SITES, SITES, FaultPlan,
                                       FaultyEngine, InjectedFault,
                                       NetworkFaultPlan)


def tiny_model(layers=1, seed=0):
    paddle.seed(seed)
    from paddle_tpu.models import LlamaForCausalLM, llama_config
    cfg = llama_config("tiny", num_hidden_layers=layers)
    return LlamaForCausalLM(cfg), cfg


# the whole chaos suite runs with the allocator's invariant validator
# armed: a reclaim bug on any abort/retire path fails loudly at the
# faulty op instead of corrupting a neighbour's KV
paged_engine = functools.partial(
    engine_helpers.paged_engine, max_batch=3, num_pages=24, page_size=8,
    max_pages=8, debug_pages=True)


def faulty_server(plan=None, model_layers=1, **kw):
    """(server, RAW engine, model cfg) — the engine is wrapped in a
    FaultyEngine when a plan is given; capacity assertions go against
    the raw engine."""
    model, cfg = tiny_model(layers=model_layers)
    eng_keys = ("max_batch", "num_pages", "page_size", "max_pages",
                "prefill_buckets", "prefill_chunk")
    eng_kw = {k: kw.pop(k) for k in list(kw) if k in eng_keys}
    raw = paged_engine(model, **eng_kw)
    eng = FaultyEngine(raw, plan) if plan is not None else raw
    return Server(eng, **kw), raw, cfg


@pytest.fixture()
def mon():
    monitor.enable()
    monitor.reset()
    yield monitor
    monitor.reset()
    monitor.disable()


def _greedy(n):
    return GenerationConfig(max_new_tokens=n, eos_token_id=None)


def _oracle(model, prompts, maxes, max_len=64):
    """Expected greedy tokens per prompt via the dense engine (bitwise
    parity with the continuous-batching engines is established by the
    existing suites)."""
    dense = CausalLMEngine(model, max_batch=1, max_len=max_len)
    return [dense.generate(p[None], _greedy(m))[0, len(p):]
            for p, m in zip(prompts, maxes)]


def _assert_no_leaks(eng):
    assert eng.free_slots() == eng.max_batch
    assert eng.alloc.free_pages == eng.num_pages


class TestTaxonomy:
    def test_classify_fault(self):
        assert classify_fault(RequestFault("x"), "decode") == "request"
        assert classify_fault(EngineFault("x"), "admit") == "engine"
        for site in ("admit", "prefill", "chunk"):
            assert classify_fault(RuntimeError("x"), site) == "request"
        for site in ("decode", "collect", "cancel"):
            assert classify_fault(RuntimeError("x"), site) == "engine"
        assert classify_fault(KeyboardInterrupt(), "admit") == "fatal"
        assert classify_fault(SystemExit(), "decode") == "fatal"


class TestFaultPlan:
    def test_nth_and_times_deterministic(self):
        plan = FaultPlan()
        plan.raise_at("decode", nth=2, times=2)
        plan.fire("decode")                    # call 1: clean
        with pytest.raises(InjectedFault, match="call 2"):
            plan.fire("decode")
        with pytest.raises(InjectedFault):
            plan.fire("decode")
        plan.fire("decode")                    # rule retired
        assert [(s, n) for s, n, _ in plan.injected] == [
            ("decode", 2), ("decode", 3)]
        assert plan.calls["decode"] == 4

    def test_sites_are_independent_and_validated(self):
        plan = FaultPlan().raise_at("admit", nth=1)
        plan.fire("decode")                    # other seams untouched
        with pytest.raises(InjectedFault):
            plan.fire("admit")
        with pytest.raises(ValueError, match="unknown site"):
            plan.raise_at("nope")
        assert set(SITES) == {"admit", "prefill", "chunk", "decode",
                              "collect", "preempt"}

    def test_hang_bounded_and_releasable(self):
        plan = FaultPlan().hang_at("decode", nth=1, seconds=30)
        t = threading.Timer(0.05, plan.release_hangs)
        t.start()
        t0 = time.monotonic()
        plan.fire("decode")                    # returns once released
        assert time.monotonic() - t0 < 5
        t.join()

    def test_custom_exception_passthrough(self):
        plan = FaultPlan().raise_at("decode",
                                    exc=EngineFault("device lost"))
        with pytest.raises(EngineFault, match="device lost"):
            plan.fire("decode")

    def test_plan_reassignment_rearms_proxy_seams(self):
        """``fe.plan = new_plan`` between scenarios must stay on the
        PROXY and rearm every seam — including the engine-internal
        prefill shadow — not forward to the wrapped engine as a dead
        attribute while the seams keep firing the stale plan."""
        model, cfg = tiny_model()
        raw = paged_engine(model)
        fe = FaultyEngine(raw, FaultPlan())
        fe.decode_segment(1)                   # original plan: clean
        fe.plan = FaultPlan().raise_at("decode", nth=1)
        assert "plan" not in vars(raw)         # no dead engine attr
        with pytest.raises(InjectedFault):
            fe.decode_segment(1)
        fe.plan = FaultPlan().raise_at("prefill", nth=1)
        p = np.random.RandomState(0).randint(
            0, cfg.vocab_size, (6,)).astype(np.int32)
        with pytest.raises(InjectedFault):     # prefill shadow rearmed
            fe.add_request(p, _greedy(4))
        assert raw.free_slots() == raw.max_batch   # abort guard ran
        assert raw.alloc.free_pages == raw.num_pages
        raw.alloc.check()


class TestEngineReset:
    def test_reset_state_reclaims_everything_and_still_serves(self):
        """reset_state (the recovery hook) must rebuild to a state
        indistinguishable from fresh: full slot heap and page pool, no
        collectables, and subsequent greedy decode identical."""
        model, cfg = tiny_model()
        eng = paged_engine(model, max_batch=2, num_pages=12)
        rng = np.random.RandomState(0)
        p = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
        want = _oracle(model, [p], [5])[0]
        eng.add_request(p, _greedy(30))
        eng.add_request(rng.randint(0, cfg.vocab_size, (4,))
                        .astype(np.int32), _greedy(30))
        eng.decode_segment(2)
        eng.reset_state()
        _assert_no_leaks(eng)
        assert eng.collect_finished() == {}
        rid = eng.add_request(p, _greedy(5))
        while eng.decode_segment(4):
            pass
        np.testing.assert_array_equal(eng.collect_finished()[rid], want)
        _assert_no_leaks(eng)


class TestRequestContainment:
    def test_prefill_fault_fails_one_alone_with_parity(self, mon):
        """A fault INSIDE the second admission's prefill (capacity
        already claimed) fails only that request with its cause; the
        neighbours finish with token parity vs a fault-free run and
        nothing leaks."""
        model, _ = tiny_model()
        rng = np.random.RandomState(1)
        prompts = [rng.randint(0, 100, (n,)).astype(np.int32)
                   for n in (5, 7, 4)]
        want = _oracle(model, [prompts[0], prompts[2]], [8, 6])

        plan = FaultPlan().raise_at("prefill", nth=2)
        srv, eng, cfg = faulty_server(plan, max_batch=3,
                                      segment_steps=2)
        try:
            h1 = srv.submit(prompts[0], _greedy(8))
            h2 = srv.submit(prompts[1], _greedy(8))
            h3 = srv.submit(prompts[2], _greedy(6))
            with pytest.raises(RequestFailed, match="injected fault"):
                h2.result(timeout=120)
            np.testing.assert_array_equal(h1.result(timeout=120),
                                          want[0])
            np.testing.assert_array_equal(h3.result(timeout=120),
                                          want[1])
            # the loop kept serving: no restart, status stays ok
            assert srv.restarts == 0
            assert srv.status == "ok"
            fs = srv.fault_stats()
            # the prefill raise surfaces at the admission seam
            assert fs["faults"] == {("request", "admit"): 1}
            assert srv.drain(timeout=120)
            _assert_no_leaks(eng)
            # monitor export (before shutdown retires the series)
            snap = monitor.snapshot()["metrics"]
            s = snap["paddle_tpu_serving_faults_total"]["samples"][0]
            assert s["labels"]["kind"] == "request"
            assert s["labels"]["site"] == "admit"
            assert s["value"] == 1
        finally:
            srv.shutdown(drain=False)

    def test_admit_seam_fault_fails_one_alone(self):
        """A fault at the admission CALL seam (before any capacity is
        claimed) — same containment, zero leak."""
        plan = FaultPlan().raise_at("admit", nth=1)
        srv, eng, cfg = faulty_server(plan, max_batch=2,
                                      segment_steps=2)
        try:
            h1 = srv.submit(np.arange(4, dtype=np.int32), _greedy(4))
            with pytest.raises(RequestFailed, match="injected fault"):
                h1.result(timeout=120)
            h2 = srv.submit(np.arange(5, dtype=np.int32), _greedy(4))
            assert len(h2.result(timeout=120)) == 4
            assert srv.drain(timeout=120)
            _assert_no_leaks(eng)
        finally:
            srv.shutdown(drain=False)

    def test_chunk_fault_fails_long_request_alone(self, mon):
        """A fault on the SECOND chunk of a chunked admission fails the
        long request only (admit_chunk's abort guard reclaims the
        up-front slot + worst-case pages); a concurrent short request
        completes with parity."""
        model, _ = tiny_model()
        rng = np.random.RandomState(2)
        long_p = rng.randint(0, 100, (20,)).astype(np.int32)
        short_p = rng.randint(0, 100, (4,)).astype(np.int32)
        want = _oracle(model, [short_p], [6])[0]

        plan = FaultPlan().raise_at("chunk", nth=2)
        srv, eng, cfg = faulty_server(
            plan, max_batch=2, num_pages=24, page_size=8, max_pages=8,
            prefill_chunk=8, segment_steps=2)
        try:
            hl = srv.submit(long_p, _greedy(6))
            hs = srv.submit(short_p, _greedy(6))
            with pytest.raises(RequestFailed, match="injected fault"):
                hl.result(timeout=120)
            np.testing.assert_array_equal(hs.result(timeout=120), want)
            assert srv.fault_stats()["faults"] == {
                ("request", "chunk"): 1}
            assert srv.drain(timeout=120)
            _assert_no_leaks(eng)
        finally:
            srv.shutdown(drain=False)


class TestClaimBeforeDispatch:
    """ISSUE 26: the paged engine's cold admission claims its pages
    BEFORE its one program is dispatched (the program needs the slot's
    page-table row), so both ways an admission can fail past the probe
    must hand back slot and pages."""

    def _spy(self, eng):
        calls = []
        real = eng._prefill_paged
        eng._prefill_paged = lambda *a: (calls.append(1), real(*a))[1]
        return calls

    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    def test_failed_claim_fails_before_any_device_work(self, kv_dtype):
        model, _ = tiny_model()
        eng = paged_engine(model, max_batch=2, num_pages=3,
                           kv_dtype=kv_dtype)
        calls = self._spy(eng)
        eng._can_admit = lambda plen, cfg: True    # a caller past the probe
        p = np.arange(1, 31, dtype=np.int32)       # 30 + 8 tokens: 5 pages
        with pytest.raises(RuntimeError, match="exhausted"):
            eng.add_request(p, _greedy(8))
        assert calls == []
        _assert_no_leaks(eng)
        eng.alloc.check()
        # the capacity is whole: a request that fits is served
        del eng._can_admit
        rid = eng.add_request(p[:9], _greedy(4))
        assert calls == [1]
        while eng.decode_segment(4):
            pass
        assert len(eng.collect_finished()[rid]) == 4
        _assert_no_leaks(eng)
        eng.alloc.check()

    @pytest.mark.parametrize("prefix_cache", [False, True])
    def test_prefill_fault_fires_after_the_claim(self, prefix_cache):
        model, _ = tiny_model()
        raw = paged_engine(model, prefix_cache=prefix_cache)
        calls = self._spy(raw)
        seen = []

        def fault():
            # at the seam the slot and its pages are already claimed
            seen.append((raw.free_slots(), raw.alloc.free_pages))
            return InjectedFault("injected fault @ prefill")

        eng = FaultyEngine(raw, FaultPlan().raise_at("prefill", exc=fault))
        p = np.arange(1, 12, dtype=np.int32)
        with pytest.raises(InjectedFault):
            eng.add_request(p, _greedy(6))
        assert seen == [(raw.max_batch - 1, raw.num_pages - 3)]
        assert calls == []                   # and nothing was dispatched
        _assert_no_leaks(raw)
        raw.alloc.check()
        assert not raw._prefix_stash
        want = _oracle(model, [p], [6])[0]
        rid = eng.add_request(p, _greedy(6))         # the plan is spent
        while eng.decode_segment(4):
            pass
        np.testing.assert_array_equal(eng.collect_finished()[rid], want)
        # (a served prompt's full block parks in the prefix cache)
        assert raw.free_slots() == raw.max_batch
        assert raw.alloc.available_pages == raw.num_pages
        raw.alloc.check()


class TestEngineRecovery:
    def test_decode_fault_recovers_with_identical_tokens(self, mon):
        """An EngineFault mid-serving triggers ONE supervised restart;
        both in-flight greedy requests replay (re-prefill of
        prompt + generated) and finish with final tokens identical to
        a fault-free run; zero leaked capacity after drain."""
        model, _ = tiny_model()
        rng = np.random.RandomState(3)
        p1 = rng.randint(0, 100, (6,)).astype(np.int32)
        p2 = rng.randint(0, 100, (9,)).astype(np.int32)
        want = _oracle(model, [p1, p2], [10, 7])

        plan = FaultPlan().raise_at(
            "decode", nth=2, exc=EngineFault("injected device loss"))
        srv, eng, cfg = faulty_server(plan, max_batch=2,
                                      segment_steps=2,
                                      restart_backoff_s=0.01)
        try:
            h1 = srv.submit(p1, _greedy(10))
            h2 = srv.submit(p2, _greedy(7))
            np.testing.assert_array_equal(h1.result(timeout=120),
                                          want[0])
            np.testing.assert_array_equal(h2.result(timeout=120),
                                          want[1])
            assert srv.restarts == 1
            fs = srv.fault_stats()
            assert fs["faults"] == {("engine", "decode"): 1}
            assert len(fs["recovery_s"]) == 1
            assert fs["degraded"] is None and srv.status == "ok"
            # at most one replay each, and the server still serves
            assert h1._replays <= 1 and h2._replays <= 1
            h3 = srv.submit(p1, _greedy(3))
            assert len(h3.result(timeout=120)) == 3
            assert srv.drain(timeout=120)
            _assert_no_leaks(eng)
            # monitor export (before shutdown retires the series)
            snap = monitor.snapshot()["metrics"]
            restarts = snap["paddle_tpu_serving_restarts_total"][
                "samples"]
            assert restarts[0]["value"] == 1
            assert "paddle_tpu_serving_recovery_seconds" in snap
        finally:
            srv.shutdown(drain=False)

    def test_fault_while_tokens_are_owed_replays_identically(self):
        """ISSUE 37: the second decode call faults before its dispatch,
        so the first segment's tokens are still owed to the handles.
        Recovery hands them over first (a ``push`` with
        ``after_dispatch`` 0 before the ``recover`` span), and the
        replays, which re-prefill from the handles' tokens, finish
        identical to a fault-free run."""
        from paddle_tpu import tracing

        model, _ = tiny_model()
        rng = np.random.RandomState(5)
        ps = [rng.randint(0, 100, (n,)).astype(np.int32)
              for n in (6, 9, 4)]
        maxes = [10, 7, 12]
        want = _oracle(model, ps, maxes)
        plan = FaultPlan().raise_at(
            "decode", nth=2, exc=EngineFault("injected device loss"))
        srv, eng, cfg = faulty_server(plan, max_batch=3,
                                      segment_steps=2,
                                      restart_backoff_s=0.01, start=False)
        tracing.clear()
        tracing.enable()
        try:
            hs = [srv.submit(p, _greedy(m)) for p, m in zip(ps, maxes)]
            srv._thread.start()
            for h, w in zip(hs, want):
                np.testing.assert_array_equal(h.result(timeout=120), w)
            assert srv.restarts == 1
            assert [h._replays for h in hs] == [1, 1, 1]
            assert srv.drain(timeout=60)
            evs = tracing.events()
        finally:
            tracing.disable()
            tracing.clear()
            srv.shutdown(drain=False)
        _assert_no_leaks(eng)
        rec = next(e for e in evs if e["phase"] == "recover")
        owed = [e for e in evs if e["phase"] == "push"
                and e["ts_ns"] + e["dur_ns"] <= rec["ts_ns"]]
        # after a dispatch up to the fault (two admissions' and the
        # first segment's); then the three deltas no dispatch followed
        assert [e["after_dispatch"] for e in owed] == [1, 1, 1, 0]
        assert owed[-1]["handles"] == 3

    def test_engine_fault_during_admission_replays_request(self):
        """An EngineFault raised at the ADMISSION seam escalates to
        recovery with the triggering request riding along — it replays
        after the reset instead of being stranded."""
        plan = FaultPlan().raise_at(
            "admit", nth=1, exc=EngineFault("admission device loss"))
        srv, eng, cfg = faulty_server(plan, max_batch=2,
                                      segment_steps=2,
                                      restart_backoff_s=0.01)
        try:
            h = srv.submit(np.arange(5, dtype=np.int32), _greedy(4))
            assert len(h.result(timeout=120)) == 4
            assert srv.restarts == 1
            assert h._replays == 1
            assert srv.drain(timeout=120)
            _assert_no_leaks(eng)
        finally:
            srv.shutdown(drain=False)

    def test_chunked_replay_rides_chunked_admission(self):
        """A replay whose prompt + generated exceeds prefill_chunk
        re-admits CHUNKED (one fixed-shape chunk per gap) and still
        finishes with the fault-free greedy tokens."""
        model, _ = tiny_model()
        rng = np.random.RandomState(4)
        long_p = rng.randint(0, 100, (20,)).astype(np.int32)
        want = _oracle(model, [long_p], [10])[0]

        # decode calls 1-2 are the no-op segments interleaved with the
        # 3-chunk admission; the fault lands mid-decode, with tokens
        # already emitted, so the replay prompt (20 + generated) is
        # longer than the chunk and takes the chunked path
        plan = FaultPlan().raise_at(
            "decode", nth=5, exc=EngineFault("mid-decode loss"))
        srv, eng, cfg = faulty_server(
            plan, max_batch=2, num_pages=24, page_size=8, max_pages=8,
            prefill_chunk=8, segment_steps=2, restart_backoff_s=0.01)
        try:
            h = srv.submit(long_p, _greedy(10))
            np.testing.assert_array_equal(h.result(timeout=120), want)
            assert srv.restarts == 1
            assert h._replays == 1
            assert srv.drain(timeout=120)
            _assert_no_leaks(eng)
        finally:
            srv.shutdown(drain=False)

    def test_replay_budget_fails_request_server_survives(self):
        """Two consecutive engine faults with max_replays=1: the
        in-flight request exceeds ITS replay budget and fails with the
        fault as cause, but the SERVER recovers and serves new work."""
        plan = FaultPlan().raise_at(
            "decode", nth=1, times=2, exc=EngineFault("flaky device"))
        srv, eng, cfg = faulty_server(plan, max_batch=2,
                                      segment_steps=2, max_replays=1,
                                      restart_backoff_s=0.01)
        try:
            h = srv.submit(np.arange(5, dtype=np.int32), _greedy(6))
            with pytest.raises(RequestFailed,
                               match="exceeded its replay budget"):
                h.result(timeout=120)
            assert srv.restarts == 2
            h2 = srv.submit(np.arange(4, dtype=np.int32), _greedy(3))
            assert len(h2.result(timeout=120)) == 3
            assert srv.status in ("ok", "draining")
            assert srv.drain(timeout=120)
            _assert_no_leaks(eng)
        finally:
            srv.shutdown(drain=False)

    def test_rebuild_failure_fails_inflight_never_hangs(self):
        """If reset_state() ITSELF raises during recovery, the
        snapshotted in-flight handles must still reach terminal FAILED
        (parked for the fatal _finalize) — clients must never hang —
        and the degraded flag must not survive into the failed state."""
        plan = FaultPlan().raise_at(
            "decode", nth=1, exc=EngineFault("device loss"))
        srv, eng, cfg = faulty_server(plan, max_batch=2,
                                      segment_steps=2,
                                      restart_backoff_s=0.01)
        try:
            def broken_rebuild():
                raise RuntimeError("rebuild also failed")
            eng.reset_state = broken_rebuild
            h = srv.submit(np.arange(4, dtype=np.int32), _greedy(6))
            # the diagnosis must carry the REBUILD failure, not claim
            # an exhausted restart budget (the budget wasn't)
            with pytest.raises(RequestFailed, match="rebuild"):
                h.result(timeout=120)
            assert srv.status == "failed"
            assert srv.fault_stats()["degraded"] is None
            assert ("engine", "reset") in srv.fault_stats()["faults"]
        finally:
            srv.shutdown(drain=False)

    def test_admission_engine_fault_with_zero_restarts_terminal(self):
        """max_restarts=0 + an EngineFault at the ADMISSION seam: the
        triggering handle is in no collection yet (popped from the
        queue) — it must still reach terminal FAILED, not be
        stranded."""
        plan = FaultPlan().raise_at(
            "admit", nth=1, exc=EngineFault("admission device loss"))
        srv, eng, cfg = faulty_server(plan, max_batch=2,
                                      segment_steps=2, max_restarts=0)
        try:
            h = srv.submit(np.arange(4, dtype=np.int32), _greedy(4))
            with pytest.raises(RequestFailed, match="scheduler died"):
                h.result(timeout=120)
            assert srv.status == "failed"
        finally:
            srv.shutdown(drain=False)

    def test_chunked_replay_ignores_admission_deadline(self):
        """The admission deadline was met the first time the request
        admitted; a chunked REPLAY crossing it mid-recovery (backoff
        longer than the deadline) must complete, not EXPIRE."""
        plan = FaultPlan().raise_at(
            "decode", nth=3, exc=EngineFault("mid-decode loss"))
        srv, eng, cfg = faulty_server(
            plan, max_batch=2, num_pages=24, page_size=8, max_pages=8,
            prefill_chunk=8, segment_steps=2, warmup=True,
            restart_backoff_s=1.0)   # backoff alone outlives the ddl
        try:
            assert srv.wait_ready(timeout=300)
            h = srv.submit(np.arange(12, dtype=np.int32) % 97,
                           _greedy(8), timeout_s=0.8)
            assert len(h.result(timeout=120)) == 8
            assert srv.restarts == 1
            assert h._replays == 1
        finally:
            srv.shutdown(drain=False)

    def test_restart_budget_falls_through_to_fatal(self):
        """A persistent engine fault exhausts max_restarts and falls
        through to the fatal path: handles reach terminal FAILED
        promptly (no hung result()), status reads 'failed', and
        submit-after-crash rejects immediately with the cause."""
        plan = FaultPlan().raise_at(
            "decode", nth=1, times=1000,
            exc=EngineFault("persistent device loss"))
        srv, eng, cfg = faulty_server(plan, max_batch=2,
                                      segment_steps=2, max_restarts=1,
                                      max_replays=100,
                                      restart_backoff_s=0.01)
        try:
            h = srv.submit(np.arange(4, dtype=np.int32), _greedy(6))
            with pytest.raises(RequestFailed, match="scheduler died"):
                h.result(timeout=120)
            assert srv.status == "failed"
            assert srv.restarts == 1       # the one allowed restart
            assert srv.wait_ready(timeout=10)
            with pytest.raises(RequestRejected,
                               match="scheduler died") as ei:
                srv.submit(np.arange(3, dtype=np.int32), _greedy(2))
            assert ei.value.reason == "shutdown"
        finally:
            srv.shutdown(drain=False)


class TestStallWatchdog:
    def test_timeout_below_idle_heartbeat_rejected(self):
        """An idle loop only beats every idle_wait_s; a stall timeout
        at/below that cadence would flap a healthy idle server into
        degraded — rejected at construction."""
        model, _ = tiny_model()
        eng = paged_engine(model)
        with pytest.raises(ValueError, match="idle_wait_s"):
            Server(eng, idle_wait_s=0.02, stall_timeout_s=0.03,
                   start=False)
        with pytest.raises(ValueError, match="> 0"):
            Server(eng, stall_timeout_s=0, start=False)


    def test_hang_flips_healthz_degraded_then_recovers(self, mon):
        """An injected hang in decode flips /healthz to degraded (503)
        within stall_timeout_s; a degraded server rejects submissions
        with reason; once the hang releases the status returns to ok
        and the wedged request completes."""
        plan = FaultPlan().hang_at("decode", nth=1, seconds=60)
        srv, eng, cfg = faulty_server(plan, max_batch=2,
                                      segment_steps=2,
                                      stall_timeout_s=0.2)
        httpd = serve_http(srv)
        port = httpd.server_address[1]
        from urllib.error import HTTPError
        from urllib.request import Request, urlopen

        def healthz():
            try:
                with urlopen(f"http://127.0.0.1:{port}/healthz",
                             timeout=10) as r:
                    return r.status, json.load(r)
            except HTTPError as e:
                return e.code, json.load(e)

        try:
            h = srv.submit(np.arange(4, dtype=np.int32), _greedy(4))
            deadline = time.monotonic() + 30
            code = body = None
            while time.monotonic() < deadline:
                code, body = healthz()
                if body["status"] == "degraded":
                    break
                time.sleep(0.02)
            assert body["status"] == "degraded", body
            assert code == 503
            assert ("stall", "loop") in srv.fault_stats()["faults"]
            snap = monitor.snapshot()["metrics"]
            deg = snap["paddle_tpu_serving_degraded"]["samples"][0]
            assert deg["value"] == 1
            # degraded rejects instead of queueing into a stalled loop
            with pytest.raises(RequestRejected, match="degraded") as ei:
                srv.submit(np.arange(3, dtype=np.int32), _greedy(2))
            assert ei.value.reason == "degraded"
            body_http = json.dumps({"prompt": [1, 2],
                                    "max_new_tokens": 2}).encode()
            with pytest.raises(HTTPError) as he:
                urlopen(Request(f"http://127.0.0.1:{port}/generate",
                                data=body_http), timeout=10)
            assert he.value.code == 503
            assert json.load(he.value)["reason"] == "degraded"
            # release the hang: the loop beats, degraded clears, and
            # the wedged request finishes
            plan.release_hangs()
            assert len(h.result(timeout=120)) == 4
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                code, body = healthz()
                if body["status"] == "ok":
                    break
                time.sleep(0.02)
            assert body["status"] == "ok" and code == 200
        finally:
            plan.release_hangs()
            httpd.shutdown()
            srv.shutdown(drain=False)


class TestHTTPSatellites:
    def test_client_disconnect_reclaims_slot_and_pages(self):
        """BrokenPipeError mid-stream (serving/http.py cancel path):
        the slot AND its KV pages must actually return to the pool at
        the next gap — free-slot heap and page free-list back to full
        after the disconnect drains."""
        srv, eng, cfg = faulty_server(None, max_batch=2,
                                      segment_steps=2)
        httpd = serve_http(srv)
        port = httpd.server_address[1]
        try:
            import http.client
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=60)
            conn.request("POST", "/generate", json.dumps(
                {"prompt": [3, 1, 4], "max_new_tokens": 4000,
                 "stream": True}),
                {"Content-Type": "application/json"})
            resp = conn.getresponse()
            line = resp.readline()          # first streamed token
            assert b"token" in line
            # abrupt client disconnect mid-stream
            conn.sock.close()
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if (eng.free_slots() == eng.max_batch
                        and eng.alloc.free_pages == eng.num_pages):
                    break
                time.sleep(0.02)
            _assert_no_leaks(eng)
            # the server is still healthy for the next client
            h = srv.submit(np.arange(3, dtype=np.int32), _greedy(3))
            assert len(h.result(timeout=120)) == 3
        finally:
            httpd.shutdown()
            srv.shutdown(drain=False)

    def test_failed_server_healthz_503_and_reject(self):
        """A failed (dead-scheduler) server: /healthz 503 with
        status 'failed' in the body, and POST /generate rejects
        immediately with a reason instead of queueing."""
        plan = FaultPlan().raise_at(
            "decode", nth=1, exc=EngineFault("boom"))
        srv, eng, cfg = faulty_server(plan, max_batch=2,
                                      segment_steps=2, max_restarts=0)
        httpd = serve_http(srv)
        port = httpd.server_address[1]
        from urllib.error import HTTPError
        from urllib.request import Request, urlopen
        try:
            h = srv.submit(np.arange(4, dtype=np.int32), _greedy(4))
            with pytest.raises(RequestFailed):
                h.result(timeout=120)
            assert srv.status == "failed"
            with pytest.raises(HTTPError) as ei:
                urlopen(f"http://127.0.0.1:{port}/healthz", timeout=10)
            assert ei.value.code == 503
            body = json.load(ei.value)
            assert body["status"] == "failed"
            assert body["restarts"] == 0
            with pytest.raises(HTTPError) as ei:
                urlopen(Request(
                    f"http://127.0.0.1:{port}/generate",
                    data=json.dumps({"prompt": [1],
                                     "max_new_tokens": 2}).encode()),
                    timeout=10)
            assert ei.value.code == 503
            err = json.load(ei.value)
            assert err["reason"] == "shutdown"
            assert "scheduler died" in err["error"]
        finally:
            httpd.shutdown()
            srv.shutdown(drain=False)


class TestWarmupLifecycle:
    def test_shutdown_during_warmup_returns_promptly(self):
        """shutdown() issued while the server is still warming must
        come back with every queued handle in a terminal state — no
        hung result()/wait_ready() (builds on the PR 3 _ready-in-
        finally fix)."""
        srv, eng, cfg = faulty_server(None, max_batch=2,
                                      segment_steps=2, warmup=True)
        try:
            # submissions queue while warming
            h = srv.submit(np.arange(4, dtype=np.int32), _greedy(4))
            srv.shutdown(drain=False, timeout=300)
            assert srv.wait_ready(timeout=10)
            assert srv.status == "stopped"
            assert h.done and h.status == "cancelled"
            with pytest.raises(RequestCancelled):
                h.result(timeout=10)
        finally:
            srv.shutdown(drain=False)

    def test_drain_during_warmup_completes_queued(self):
        """drain() issued mid-warmup waits for warmup + the queued
        work, then returns True with everything finished."""
        srv, eng, cfg = faulty_server(None, max_batch=2,
                                      segment_steps=2, warmup=True)
        try:
            hs = [srv.submit(np.arange(n, dtype=np.int32) % 97,
                             _greedy(4)) for n in (3, 5)]
            assert srv.drain(timeout=600)
            for h in hs:
                assert h.status == "finished"
                assert len(h.result(timeout=10)) == 4
            _assert_no_leaks(eng)
        finally:
            srv.shutdown(drain=False)


class TestTooling:
    def test_monitor_report_serving_shows_fault_columns(self):
        import importlib.util
        import os

        spec = importlib.util.spec_from_file_location(
            "monitor_report", os.path.join(
                os.path.dirname(__file__), "..", "tools",
                "monitor_report.py"))
        mr = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mr)
        records = [
            {"metric": "paddle_tpu_serving_faults_total",
             "labels": {"server": "server0", "kind": "engine",
                        "site": "decode"}, "value": 2},
            {"metric": "paddle_tpu_serving_restarts_total",
             "labels": {"server": "server0"}, "value": 2},
            {"metric": "paddle_tpu_serving_degraded",
             "labels": {"server": "server0"}, "value": 0},
            {"metric": "paddle_tpu_serving_recovery_seconds",
             "labels": {"server": "server0"}, "value": 0.04,
             "count": 2, "sum": 0.08},
            {"metric": "paddle_tpu_something_else", "labels": {},
             "value": 1},
        ]
        out = mr.render(records, serving=True)
        assert "paddle_tpu_serving_faults_total" in out
        assert "kind=engine" in out and "site=decode" in out
        assert "paddle_tpu_serving_restarts_total" in out
        assert "paddle_tpu_serving_degraded" in out
        assert "paddle_tpu_serving_recovery_seconds" in out
        assert "something_else" not in out


class TestFlightRecorder:
    """Chaos-suite wiring for the flight recorder (ISSUE 9): an
    engine-scoped fault must leave a black-box dump behind, its final
    events must NAME the faulting site, and the dump path must surface
    through ``fault_stats()`` and ``/healthz``."""

    @pytest.fixture()
    def tr(self, tmp_path):
        from paddle_tpu import tracing
        tracing.clear()
        tracing.enable(dump_dir=str(tmp_path))
        yield tracing
        tracing.disable()
        tracing.clear()

    def test_engine_fault_dumps_and_names_site(self, tr):
        plan = FaultPlan().raise_at("decode", nth=2,
                                    exc=EngineFault("injected"))
        srv, raw, mcfg = faulty_server(plan, restart_backoff_s=0.01,
                                       segment_steps=4)
        try:
            prompts = [np.arange(1, 7, dtype=np.int32) + i
                       for i in range(2)]
            hs = [srv.submit(p, _greedy(10)) for p in prompts]
            for h in hs:
                h.result(timeout=180)
            fs = srv.fault_stats()
            assert fs["restarts"] == 1
            assert fs["flight_dumps"], \
                "engine fault produced no flight-recorder dump"
            path = fs["flight_dumps"][-1]
            doc = json.load(open(path))
            assert doc["otherData"]["reason"] == "engine_fault_decode"
            # the final events name the faulting site: the seam's
            # fault-classification event AND the injection marker
            faults = [e for e in doc["traceEvents"]
                      if e["name"] == "fault"]
            assert faults and faults[-1]["args"]["site"] == "decode"
            assert faults[-1]["args"]["kind"] == "engine"
            inject = [e for e in doc["traceEvents"]
                      if e["name"] == "fault.injected"]
            assert inject and inject[-1]["args"]["site"] == "decode"
            # ... and the dump path reaches /healthz
            httpd = serve_http(srv, port=0)
            try:
                port = httpd.server_address[1]
                from urllib.request import urlopen
                body = json.loads(urlopen(
                    f"http://127.0.0.1:{port}/healthz",
                    timeout=10).read())
                assert body["flight_dump"] == path
            finally:
                httpd.shutdown()
        finally:
            srv.shutdown()
        _assert_no_leaks(raw)

    def test_restart_backoff_replay_traced(self, tr):
        """The recovery trail lands in the ring AFTER the dump: the
        next dump (or a live /trace read) shows backoff -> restart ->
        replay -> re-admit for the surviving request."""
        from paddle_tpu import tracing
        plan = FaultPlan().raise_at("decode", nth=2,
                                    exc=EngineFault("injected"))
        srv, raw, _ = faulty_server(plan, restart_backoff_s=0.01,
                                    segment_steps=4)
        try:
            h = srv.submit(np.arange(1, 7, dtype=np.int32),
                           _greedy(10))
            h.result(timeout=180)
            ph = [e["phase"] for e in h.timeline()]
            i = ph.index
            assert i("replay") < ph.index("admit", i("replay"))
            names = [e["phase"] for e in tracing.events()]
            assert "backoff" in names and "restart" in names \
                and "recover" in names
            j = names.index
            assert j("backoff") < j("restart") < j("recover")
        finally:
            srv.shutdown()
        _assert_no_leaks(raw)

    def test_no_dump_when_tracing_disabled(self):
        from paddle_tpu import tracing
        assert not tracing.enabled()
        plan = FaultPlan().raise_at("decode", nth=2,
                                    exc=EngineFault("injected"))
        srv, raw, _ = faulty_server(plan, restart_backoff_s=0.01,
                                    segment_steps=4)
        try:
            h = srv.submit(np.arange(1, 7, dtype=np.int32),
                           _greedy(10))
            h.result(timeout=180)
            fs = srv.fault_stats()
            assert fs["restarts"] == 1
            # no recorder armed -> no black box, honestly empty
            assert fs["flight_dumps"] == []
            assert h.timeline() == []
        finally:
            srv.shutdown()
        _assert_no_leaks(raw)

    def test_preemption_storm_dumps_once(self, tr):
        """The storm trigger fires on preemption DENSITY (not any
        single preemption) and re-arms only after a full window —
        driven synthetically through _park_preempted so the test does
        not depend on pool-thrash timing."""
        import types

        from paddle_tpu.serving.queue import RequestHandle
        srv = Server(types.SimpleNamespace(max_len=64), start=False)
        srv.STORM_PREEMPTS = 3
        try:
            for k in range(3):
                h = RequestHandle(k, np.arange(3), 3, _greedy(4))
                h._trace_rid = f"{srv.monitor_server}:{k}"
                srv._park_preempted(h)
            dumps = srv.fault_stats()["flight_dumps"]
            assert len(dumps) == 1
            doc = json.load(open(dumps[0]))
            assert doc["otherData"]["reason"] == "preemption_storm"
            storm = [e for e in doc["traceEvents"]
                     if e["name"] == "preempt.storm"]
            assert storm and storm[-1]["args"]["count"] == 3
            # within the same window a 4th preemption does NOT re-dump
            h = RequestHandle(9, np.arange(3), 3, _greedy(4))
            h._trace_rid = f"{srv.monitor_server}:9"
            srv._park_preempted(h)
            assert len(srv.fault_stats()["flight_dumps"]) == 1
        finally:
            srv.shutdown(drain=False)


class TestControlPlaneUnit:
    """Overload control plane (ISSUE 19), host-side unit surface:
    burn-rate shed windows, the brownout ladder's engage-immediately /
    disengage-hysteretically asymmetry, config degradation semantics,
    and the elastic controller's provable flap resistance — all driven
    through explicit synthetic clocks (the same code paths production
    ticks through, minus the wall clock)."""

    def test_policy_validation(self):
        with pytest.raises(ValueError, match="shed_burn"):
            ControlPolicy(shed_burn=0)
        with pytest.raises(ValueError, match="non-decreasing"):
            ControlPolicy(rung_up=(0.5, 0.4, 0.8, 0.9))
        with pytest.raises(ValueError, match="engage thresholds"):
            ControlPolicy(rung_up=(0.5, 0.9))
        with pytest.raises(ValueError, match="scale_up_depth"):
            ControlPolicy(scale_up_depth=0.2, scale_down_depth=0.5)
        with pytest.raises(ValueError, match="ControlPolicy"):
            ControlPlane(object())

    def test_shed_window_lifecycle(self):
        pol = ControlPolicy(shed_burn=2.0, shed_min_count=2,
                            tick_interval_s=0.0)
        cp = ControlPlane(pol, fast_window_s=10.0)
        stats = {"hot": {"burn_fast": 3.0, "met": 1, "missed": 3},
                 "cold": {"burn_fast": 0.1, "met": 4, "missed": 0},
                 "thin": {"burn_fast": 9.0, "met": 1, "missed": 0},
                 "idle": {"burn_fast": None}}
        dec = cp.tick(100.0, queue_depth=0, max_queue=64,
                      tenant_stats=stats)
        # only the hot tenant with enough scored requests sheds ("thin"
        # has a loud burn off one request — one unlucky request must
        # not shed a tenant)
        assert dec["shed"] == [("hot", 110.0)]
        assert cp.shed_check("hot", 104.0) == pytest.approx(6.0)
        assert cp.shed_check("cold", 104.0) is None
        assert cp.shed_check(None, 104.0) is None
        # a hot burn forces at least rung 1 even with an empty queue
        assert dec["rung"] >= 1
        assert cp.snapshot()["shed_active"] == ["hot"]
        # re-firing while hot EXTENDS the window without a new "shed"
        dec = cp.tick(105.0, queue_depth=0, max_queue=64,
                      tenant_stats={"hot": stats["hot"]})
        assert dec["shed"] == []
        assert cp.shed_check("hot", 105.0) == pytest.approx(10.0)
        # window expiry: tick reports the unshed, shed_check clears
        dec = cp.tick(116.0, queue_depth=0, max_queue=64,
                      tenant_stats={})
        assert dec["unshed"] == ["hot"]
        assert cp.shed_check("hot", 116.5) is None

    def test_ladder_engages_immediately_disengages_one_per_dwell(self):
        pol = ControlPolicy(tick_interval_s=0.0, rung_dwell_s=2.0,
                            rung_hysteresis=0.15)
        cp = ControlPlane(pol)
        # overload is urgent: the ladder jumps straight to rung 4
        dec = cp.tick(0.0, queue_depth=60, max_queue=64,
                      tenant_stats=None)
        assert (dec["prev_rung"], dec["rung"]) == (0, 4)
        assert cp.snapshot()["rung_action"] == "prefix_pause"
        # load vanished, but dwell not served: hold the rung
        dec = cp.tick(1.0, queue_depth=0, max_queue=64,
                      tenant_stats=None)
        assert dec["rung"] == 4
        # disengage is ONE rung per dwell, never a cliff
        rungs = [cp.tick(3.0 + 2.5 * i, queue_depth=0, max_queue=64,
                         tenant_stats=None)["rung"] for i in range(4)]
        assert rungs == [3, 2, 1, 0]

    def test_ladder_does_not_flap_inside_the_hysteresis_band(self):
        pol = ControlPolicy(tick_interval_s=0.0, rung_dwell_s=1.0,
                            rung_hysteresis=0.15)
        cp = ControlPlane(pol)
        assert cp.tick(0.0, queue_depth=33, max_queue=64,
                       tenant_stats=None)["rung"] == 1   # occ 0.516
        # oscillate between 0.40 and 0.52 — both above the disengage
        # threshold (0.5 - 0.15): the rung must hold forever
        for i in range(1, 12):
            depth = 26 if i % 2 else 33
            dec = cp.tick(2.0 * i, queue_depth=depth, max_queue=64,
                          tenant_stats=None)
            assert dec["rung"] == 1
        # dropping BELOW the band releases it (dwell long since met)
        assert cp.tick(30.0, queue_depth=8, max_queue=64,
                       tenant_stats=None)["rung"] == 0

    def test_tick_rate_limits_itself(self):
        cp = ControlPlane(ControlPolicy(tick_interval_s=1.0))
        assert cp.tick(0.0, queue_depth=0, max_queue=8,
                       tenant_stats=None) is not None
        assert cp.tick(0.5, queue_depth=0, max_queue=8,
                       tenant_stats=None) is None
        assert cp.tick(1.5, queue_depth=0, max_queue=8,
                       tenant_stats=None) is not None

    def test_degrade_cfg_and_quota_cap(self):
        cp = ControlPlane(ControlPolicy(brownout_max_new=3,
                                        tick_interval_s=0.0))
        cfg = GenerationConfig(max_new_tokens=64, speculative=True)
        # rung 0/1: the client's object passes through untouched
        assert cp.degrade_cfg(cfg) is cfg
        assert cp.quota_cap(4) == 4
        cp.rung = 1
        assert cp.degrade_cfg(cfg) is cfg
        assert cp.quota_cap(4) == 2 and cp.quota_cap(1) == 1
        cp.rung = 2
        out = cp.degrade_cfg(cfg)
        assert out is not cfg and out.max_new_tokens == 3
        assert out.speculative is True        # rung 2 only caps length
        assert cfg.max_new_tokens == 64       # never mutates the input
        cp.rung = 3
        out = cp.degrade_cfg(cfg)
        assert out.max_new_tokens == 3 and out.speculative is False
        # an already-short request is not lengthened
        short = GenerationConfig(max_new_tokens=2)
        assert cp.degrade_cfg(short).max_new_tokens == 2

    def test_elastic_flap_resistance_under_oscillating_load(self):
        pol = ControlPolicy(scale_up_depth=4.0, scale_down_depth=0.5,
                            scale_signals=3, scale_cooldown_s=10.0)
        ec = ElasticController(pol, min_replicas=1, max_replicas=4)
        # load oscillating across both thresholds every tick: each
        # flip resets the opposite streak — NO scale event, ever
        decisions = [ec.decide(float(t), routable=2,
                               queue_depth=(20 if t % 2 == 0 else 0))
                     for t in range(24)]
        assert decisions == [0] * 24

    def test_elastic_sustained_signal_fires_once_per_cooldown(self):
        pol = ControlPolicy(scale_up_depth=4.0, scale_down_depth=0.5,
                            scale_signals=3, scale_cooldown_s=10.0)
        ec = ElasticController(pol, min_replicas=1, max_replicas=4)
        ups = [ec.decide(float(t), routable=2, queue_depth=20)
               for t in range(10)]
        # streak completes on the third agreeing tick; the cooldown
        # then blocks every further verdict inside the window
        assert ups == [0, 0, 1, 0, 0, 0, 0, 0, 0, 0]
        # the streak kept accumulating through the cooldown, so a
        # STILL-sustained signal fires the instant the window opens
        ups2 = [ec.decide(13.0 + t, routable=3, queue_depth=30)
                for t in range(3)]
        assert ups2 == [1, 0, 0]
        # bounds: never above max_replicas, never below min_replicas
        assert [ec.decide(40.0 + t, routable=4, queue_depth=99)
                for t in range(4)] == [0] * 4
        down = ElasticController(pol, min_replicas=2)
        assert [down.decide(float(t), routable=2, queue_depth=0)
                for t in range(6)] == [0] * 6
        # a hot burn forces the up side even with an empty queue
        burn = ElasticController(pol, min_replicas=1, max_replicas=4)
        assert [burn.decide(float(t), routable=1, queue_depth=0,
                            burn_max=5.0)
                for t in range(3)] == [0, 0, 1]


class TestPenaltyBand:
    """Satellite: queue priority aging must not resurrect a shed
    tenant's entries past the burn window — deprioritized entries age
    WITHIN the penalty band."""

    def test_aging_stays_in_band_until_window_expires(self):
        from paddle_tpu.serving import RequestHandle, RequestQueue
        q = RequestQueue(max_size=16, age_after_s=0.01)
        now = time.monotonic()
        hot = RequestHandle(1, np.arange(3), 3, _greedy(4),
                            priority=0, tenant="hot")
        cold = RequestHandle(2, np.arange(3), 3, _greedy(4),
                             priority=0, tenant="cold")
        q.penalize("hot", 8, now + 30.0)
        q.put(hot)
        q.put(cold)
        eff = {h.id: e for e, _, h in q._heap}
        assert eff[1] == 8 and eff[2] == 0     # band applies at put
        # a huge aging credit: the cold tenant ages freely, the shed
        # tenant clamps strictly above base — it can NEVER reach
        # parity with healthy tenants while the window is open
        q.reap(now + 1.0)                      # credit ~100 levels
        eff = {h.id: e for e, _, h in q._heap}
        assert eff[2] < 0
        assert eff[1] == 1                     # base + 1, not base
        head = q.pop_if(lambda h: True)
        assert head is cold
        q.put(cold)
        # window expiry sweeps the penalty and normal aging resumes
        q.reap(now + 31.0)
        eff = {h.id: e for e, _, h in q._heap}
        assert eff[1] < 0
        # unpenalize() releases early, restoring base before aging
        q2 = RequestQueue(max_size=4)
        h3 = RequestHandle(3, np.arange(3), 3, _greedy(4),
                           priority=1, tenant="hot")
        q2.penalize("hot", 8, now + 30.0)
        q2.put(h3)
        assert q2._heap[0][0] == 9
        q2.unpenalize("hot")
        assert q2._heap[0][0] == 1


class TestOverloadControl:
    """Integration: the control plane wired into the Server — shed
    429s with Retry-After, trace/metric/healthz observability, the
    shed-storm flight dump, and brownout degradation hitting only
    FUTURE admissions."""

    @pytest.fixture()
    def tr(self, tmp_path):
        from paddle_tpu import tracing
        tracing.clear()
        tracing.enable(dump_dir=str(tmp_path))
        yield tracing
        tracing.disable()
        tracing.clear()

    def test_shed_rejects_with_retry_after_and_traces(self, mon, tr):
        srv, eng, _ = faulty_server(
            None, max_batch=2, segment_steps=2,
            control_policy=ControlPolicy(tick_interval_s=0.0))
        try:
            # open a shed window directly (production opens it from
            # the burn-rate tick; the submit path is what's under
            # test). Window sized so it cannot lazily expire while the
            # cold request below decodes on a loaded box; written
            # under the control lock — the gap tick iterates this dict
            with srv.control._lock:
                srv.control._shed_until["hot"] = (
                    time.monotonic() + 300.0)
            with pytest.raises(RequestRejected,
                               match="fast-burn") as ei:
                srv.submit(np.arange(4, dtype=np.int32), _greedy(4),
                           tenant="hot")
            assert ei.value.reason == "shed"
            assert 0 < ei.value.retry_after_s <= 300.0
            # other tenants are untouched
            h = srv.submit(np.arange(4, dtype=np.int32), _greedy(4),
                           tenant="cold")
            assert len(h.result(timeout=120)) == 4
            # observability: trace event, counter, and the /healthz
            # control block all tell the same story
            shed_ev = [e for e in tr.events()
                       if e["phase"] == "control.shed"]
            assert shed_ev and shed_ev[-1]["tenant"] == "hot"
            assert shed_ev[-1]["reason"] == "burn_rate"
            snap = monitor.snapshot()["metrics"]
            s = snap["paddle_tpu_serving_sheds_total"]["samples"][0]
            assert s["labels"]["tenant"] == "hot"
            assert s["labels"]["reason"] == "burn_rate"
            assert s["value"] == 1
            ctl = srv.load()["control"]
            assert ctl["sheds"] == {"hot": {"burn_rate": 1}}
            assert ctl["shed_active"] == ["hot"]
            # the window expires: the tenant is admittable again
            with srv.control._lock:
                srv.control._shed_until["hot"] = (
                    time.monotonic() - 0.1)
            h = srv.submit(np.arange(4, dtype=np.int32), _greedy(3),
                           tenant="hot")
            assert len(h.result(timeout=120)) == 3
            assert srv.drain(timeout=120)
            _assert_no_leaks(eng)
        finally:
            srv.shutdown(drain=False)

    def test_http_429_retry_after_and_healthz_control_block(self):
        from urllib.error import HTTPError
        from urllib.request import Request, urlopen
        srv, eng, _ = faulty_server(
            None, max_batch=2, segment_steps=2,
            control_policy=ControlPolicy())
        httpd = serve_http(srv)
        port = httpd.server_address[1]
        try:
            with srv.control._lock:
                srv.control._shed_until["hot"] = (
                    time.monotonic() + 300.0)
            body = json.dumps({"prompt": [1, 2], "max_new_tokens": 2,
                               "tenant": "hot"}).encode()
            with pytest.raises(HTTPError) as ei:
                urlopen(Request(f"http://127.0.0.1:{port}/generate",
                                data=body), timeout=10)
            assert ei.value.code == 429
            ra = ei.value.headers.get("Retry-After")
            assert ra is not None and 1 <= int(ra) <= 300
            err = json.load(ei.value)
            assert err["reason"] == "shed"
            assert 0 < err["retry_after_s"] <= 300.0
            # /healthz carries the control block
            with urlopen(f"http://127.0.0.1:{port}/healthz",
                         timeout=10) as r:
                hb = json.loads(r.read())
            assert hb["control"]["rung"] == 0
            assert hb["control"]["rung_action"] == "off"
            assert hb["control"]["sheds"]["hot"]["burn_rate"] >= 1
            assert hb["control"]["shed_active"] == ["hot"]
        finally:
            httpd.shutdown()
            srv.shutdown(drain=False)

    def test_queue_full_429_derives_retry_after_from_depth(self):
        """The pre-existing queue_full 429 now also answers with a
        Retry-After — derived from backlog depth, not a burn window."""
        from urllib.error import HTTPError
        from urllib.request import Request, urlopen
        import types
        srv = Server(types.SimpleNamespace(max_len=64), start=False,
                     max_queue=1)
        httpd = serve_http(srv)
        port = httpd.server_address[1]
        try:
            srv.submit(np.arange(3, dtype=np.int32), _greedy(2))
            body = json.dumps({"prompt": [1],
                               "max_new_tokens": 2}).encode()
            with pytest.raises(HTTPError) as ei:
                urlopen(Request(f"http://127.0.0.1:{port}/generate",
                                data=body), timeout=10)
            assert ei.value.code == 429
            err = json.load(ei.value)
            assert err["reason"] == "queue_full"
            assert err["retry_after_s"] > 0
            assert int(ei.value.headers["Retry-After"]) >= 1
        finally:
            httpd.shutdown()
            srv.shutdown(drain=False)

    def test_shed_storm_dumps_once_per_window(self, tr):
        """A shed STORM leaves exactly one flight dump per window —
        same density trigger + re-arm discipline as the preemption
        storm (driven synthetically through _note_shed)."""
        import types
        srv = Server(types.SimpleNamespace(max_len=64), start=False,
                     control_policy=ControlPolicy())
        srv.SHED_STORM = 3
        try:
            for _ in range(3):
                srv._note_shed("hot", "burn_rate")
            dumps = srv.fault_stats()["flight_dumps"]
            assert len(dumps) == 1
            doc = json.load(open(dumps[0]))
            assert doc["otherData"]["reason"] == "shed_storm"
            storm = [e for e in doc["traceEvents"]
                     if e["name"] == "control.shed_storm"]
            assert storm and storm[-1]["args"]["count"] == 3
            sheds = [e for e in doc["traceEvents"]
                     if e["name"] == "control.shed"]
            assert len(sheds) == 3
            # within the window, further sheds do NOT re-dump
            srv._note_shed("hot", "burn_rate")
            assert len(srv.fault_stats()["flight_dumps"]) == 1
        finally:
            srv.shutdown(drain=False)

    def test_brownout_degrades_future_admissions_only(self, tr):
        """Rung 2 engaged mid-flight: the already-admitted request
        keeps its full budget (rung transitions are bitwise-neutral
        for running work); the next admission is capped — and the
        handle's cfg carries the cap, so a preemption would replay the
        DEGRADED budget."""
        # dwell sized so the empty-queue gap tick can never disengage
        # the hand-set rung before the capped submit lands (disengage
        # needs now - _rung_since >= rung_dwell_s)
        pol = ControlPolicy(brownout_max_new=3, tick_interval_s=0.0,
                            rung_dwell_s=3600.0)
        srv, eng, _ = faulty_server(None, max_batch=2,
                                    segment_steps=2,
                                    control_policy=pol)
        try:
            h1 = srv.submit(np.arange(1, 5, dtype=np.int32),
                            _greedy(8))
            deadline = time.monotonic() + 60
            while h1.status == "queued":
                assert time.monotonic() < deadline, "never admitted"
                time.sleep(0.005)
            with srv.control._lock:  # engage (test seam; production
                #                      engages via the gap tick)
                srv.control.rung = 2
                srv.control._rung_since = time.monotonic()
            h2 = srv.submit(np.arange(2, 7, dtype=np.int32),
                            _greedy(8))
            assert len(h1.result(timeout=120)) == 8   # untouched
            assert len(h2.result(timeout=120)) == 3   # capped
            assert h2.cfg.max_new_tokens == 3
            assert srv.drain(timeout=120)
            _assert_no_leaks(eng)
        finally:
            srv.shutdown(drain=False)


class TestNetworkFaultPlan:
    """Satellite: the RemoteReplica wire seam — bounded delay /
    connection drop / mid-stream half-close under the same
    deterministic FaultPlan discipline, in a site namespace separate
    from the engine seams."""

    def test_namespace_and_actions(self):
        assert set(NET_SITES) == {"generate", "kv_import"}
        plan = NetworkFaultPlan()
        plan.delay_at("generate", nth=1, seconds=0.01)
        plan.drop_at("generate", nth=2)
        plan.half_close_at("generate", nth=3, after=2)
        t0 = time.monotonic()
        assert plan.fire("generate") is None      # delay, then clean
        assert time.monotonic() - t0 >= 0.01
        with pytest.raises(ConnectionResetError, match="drop"):
            plan.fire("generate")
        assert plan.fire("generate") == {"action": "half_close",
                                         "after": 2}
        assert plan.fire("generate") is None      # rules retired
        assert plan.injected == [("generate", 1, "delay"),
                                 ("generate", 2, "drop"),
                                 ("generate", 3, "half_close")]
        assert plan.calls == {"generate": 4, "kv_import": 0}
        # the namespaces never cross: engine sites are invalid here
        with pytest.raises(ValueError, match="unknown site"):
            plan.drop_at("decode")
        with pytest.raises(ValueError, match="unknown site"):
            FaultPlan().raise_at("generate")
        # delays are releasable, like hangs
        slow = NetworkFaultPlan().delay_at("kv_import", seconds=30)
        t = threading.Timer(0.05, slow.release_hangs)
        t.start()
        t0 = time.monotonic()
        slow.fire("kv_import")
        assert time.monotonic() - t0 < 5
        t.join()

    def test_drop_and_half_close_against_live_replica(self):
        """End to end over a real socket: a dropped /generate surfaces
        as the replica-unreachable error (what the router failovers
        on); a mid-stream half-close tears the stream after exactly N
        relayed tokens, the handle resolves FAILED (never hangs), and
        the server reclaims the sheared request's capacity."""
        from paddle_tpu.serving import RemoteReplica
        srv, eng, _ = faulty_server(None, max_batch=2,
                                    segment_steps=2)
        httpd = serve_http(srv)
        port = httpd.server_address[1]
        # wire hardening OFF: this test pins the RAW fault surface the
        # retry/resume layers are built on (a retried drop succeeds
        # and a half-close resumes — covered by test_wire_hardening)
        rep = RemoteReplica(f"http://127.0.0.1:{port}",
                            wire_retries=0, max_resumes=0)
        plan = NetworkFaultPlan()
        rep.fault_plan = plan
        try:
            assert rep.wait_ready(timeout=120)
            plan.drop_at("generate", nth=1)
            with pytest.raises(RuntimeError, match="unreachable"):
                rep.submit(np.arange(4, dtype=np.int32), _greedy(4))
            # call 2: clean — the plan injects exactly where told
            h = rep.submit(np.arange(4, dtype=np.int32), _greedy(4))
            assert len(h.result(timeout=120)) == 4
            plan.half_close_at("generate", nth=3, after=2)
            h = rep.submit(np.arange(4, dtype=np.int32), _greedy(6))
            with pytest.raises(RequestFailed, match="stream"):
                h.result(timeout=120)
            assert len(h.tokens_so_far()) == 2
            assert plan.injected == [
                ("generate", 1, "drop"), ("generate", 3, "half_close")]
            # the server side reclaims the sheared request (broken-
            # pipe guard): capacity back to full
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if (eng.free_slots() == eng.max_batch
                        and eng.alloc.free_pages == eng.num_pages):
                    break
                time.sleep(0.02)
            _assert_no_leaks(eng)
            # the kv_import seam counts and injects the same way (the
            # endpoint itself is exercised by the remote suite)
            plan.drop_at("kv_import", nth=1)
            with pytest.raises(ConnectionResetError):
                rep.import_kv_raw(b"\x00" * 16)
            assert plan.calls["kv_import"] == 1
        finally:
            rep.close()
            httpd.shutdown()
            srv.shutdown(drain=False)


class TestElasticFleet:
    """Tentpole (elastic actuator): scale-down drains — never fails an
    in-flight handle — parks the slot as ``scaled_down``, and scale-up
    revives it from its own spec; every event traced."""

    @pytest.fixture()
    def tr(self, tmp_path):
        from paddle_tpu import tracing
        tracing.clear()
        tracing.enable(dump_dir=str(tmp_path))
        yield tracing
        tracing.disable()
        tracing.clear()

    def test_scale_down_never_fails_inflight_then_revives(self, tr):
        from paddle_tpu.serving import ReplicaSpec, Router

        def factory():
            model, _ = tiny_model()
            return paged_engine(model, max_batch=2)

        spec = ReplicaSpec(factory,
                           server_kwargs={"segment_steps": 2,
                                          "idle_wait_s": 0.005})
        r = Router(spec, replicas=2, monitor_interval_s=0.05)
        try:
            assert r.wait_ready(timeout=600)
            hs = [r.submit(np.arange(1, 6, dtype=np.int32),
                           _greedy(12)) for _ in range(4)]
            assert r.scale_to(1, timeout=600) == 1
            for h in hs:                       # the PR 9 bar: every
                #                                in-flight handle lands
                assert len(h.result(timeout=600)) == 12
            snap = r.load()
            assert len(snap["scaled_down"]) == 1
            assert snap["replicas"][snap["scaled_down"][0]][
                "status"] == "scaled_down"
            # parked capacity does not read as a degraded fleet
            assert snap["status"] == "ok"
            # the shrunken fleet still serves
            h = r.submit(np.arange(3, dtype=np.int32), _greedy(4))
            assert len(h.result(timeout=120)) == 4
            # revive: back to 2, the revived slot takes traffic
            assert r.scale_to(2, timeout=600) == 2
            assert r.load()["scaled_down"] == []
            hs = [r.submit(np.arange(3, dtype=np.int32), _greedy(4))
                  for _ in range(4)]
            for h in hs:
                assert len(h.result(timeout=120)) == 4
            ev = [e for e in tr.events()
                  if e["phase"] == "control.scale"]
            assert [e["action"] for e in ev] == ["down", "up"]
        finally:
            r.shutdown(drain=False)

    def test_elastic_knob_validation(self):
        from paddle_tpu.serving import ReplicaSpec, Router

        def factory():
            model, _ = tiny_model()
            return paged_engine(model)

        spec = ReplicaSpec(factory)
        with pytest.raises(ValueError, match="elastic"):
            Router(spec, replicas=2, elastic=object(), start=False)
        with pytest.raises(ValueError, match="elastic_interval_s"):
            Router(spec, replicas=2, elastic=ControlPolicy(),
                   elastic_interval_s=0, start=False)


@pytest.mark.slow
class TestChaosSoak:
    def test_serve_bench_under_injected_faults(self, mon, capsys):
        """The chaos soak: serve_bench drives open-loop load with
        seeded engine faults injected at the decode seam; the run
        completes, reports the fault/restart/recovery BENCH records,
        and every arrival is accounted for (survived + failed +
        rejected == requests)."""
        import importlib.util
        import os

        spec = importlib.util.spec_from_file_location(
            "serve_bench", os.path.join(
                os.path.dirname(__file__), "..", "tools",
                "serve_bench.py"))
        sb = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sb)
        assert sb.main([
            "--rate", "30", "--requests", "24", "--max-new", "8",
            "--prompt-len", "3:12", "--fault-rate", "0.3",
            "--fault-site", "decode", "--fault-kind", "engine",
            "--max-restarts", "1000", "--restart-backoff", "0.01",
            "--seed", "3"]) == 0
        text = capsys.readouterr().out
        recs = {}
        for line in text.splitlines():
            try:
                r = json.loads(line)
            except json.JSONDecodeError:
                continue
            recs[r["metric"]] = r["value"]
        assert "serve_faults_injected" in recs
        assert "serve_restarts" in recs
        assert recs["serve_requests_survived"] \
            + recs["serve_requests_failed"] \
            + recs["serve_rejected"] == 24
        if recs["serve_restarts"]:
            assert "serve_recovery_p50" in recs
