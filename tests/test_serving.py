"""paddle_tpu.serving — online continuous-batching serving layer.

Covers the ISSUE-2 acceptance demo end to end on CPU: a Server over a
toy paged engine takes >= 8 concurrent requests with mixed prompt
lengths and PER-REQUEST GenerationConfigs, completes them interleaved
(continuous batching), streams tokens before completion, reclaims
capacity on cancellation, applies queue-full backpressure, and exports
TTFT / queue-depth via the monitor — plus the engine-level capacity
probe, cancellation, per-request-config threading, deadline, drain and
HTTP front-end contracts.
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
from paddle_tpu.inference.generation import (CausalLMEngine,
                                             GenerationConfig)
from paddle_tpu.models import LlamaForCausalLM, llama_config
from paddle_tpu.serving import (DeadlineExpired, QueueFull,
                                RequestCancelled, RequestFailed,
                                RequestRejected, Server, serve_http)


def tiny_model(layers=1, seed=0):
    paddle.seed(seed)
    cfg = llama_config("tiny", num_hidden_layers=layers)
    return LlamaForCausalLM(cfg), cfg


paged_engine = functools.partial(
    engine_helpers.paged_engine, max_batch=3, num_pages=24, page_size=8,
    max_pages=8)


@pytest.fixture()
def mon():
    monitor.enable()
    monitor.reset()
    yield monitor
    monitor.reset()
    monitor.disable()


def _prompts(rng, vocab, lens):
    return [rng.randint(0, vocab, (n,)).astype(np.int32) for n in lens]


class TestGenerationConfigValidation:
    """A malformed online request must be rejected at admission, not
    crash a shared decode segment mid-flight."""

    @pytest.mark.parametrize("kw", [
        {"max_new_tokens": 0}, {"max_new_tokens": -3},
        {"max_new_tokens": 2.0}, {"max_new_tokens": True},
        {"temperature": 0}, {"temperature": -0.5},
        {"temperature": float("nan")},
        {"top_k": -1}, {"top_k": 2.5},
        {"top_p": 0}, {"top_p": 0.0}, {"top_p": 1.5}, {"top_p": -0.1},
        {"eos_token_id": -2}, {"eos_token_id": 1.5},
    ])
    def test_bad_values_raise(self, kw):
        with pytest.raises(ValueError, match=next(iter(kw))):
            GenerationConfig(**kw)

    @pytest.mark.parametrize("kw", [
        {"max_new_tokens": 2 ** 31}, {"top_k": 2 ** 40},
        {"eos_token_id": 2 ** 31},
    ])
    def test_beyond_int32_rejected(self, kw):
        """Engine state is int32 on device: an oversized field must be
        rejected at construction — it used to pass validation and then
        overflow MID-admission, leaking the popped slot."""
        with pytest.raises(ValueError, match=next(iter(kw))):
            GenerationConfig(**kw)

    def test_good_values_normalize(self):
        cfg = GenerationConfig(max_new_tokens=np.int64(8),
                               temperature=1, top_k=np.int32(5),
                               top_p=1, eos_token_id=np.int64(3))
        assert (cfg.max_new_tokens, cfg.top_k, cfg.eos_token_id) == (8, 5, 3)
        assert isinstance(cfg.temperature, float)
        assert GenerationConfig().eos_token_id is None


class TestRequestQueue:
    """Ordering + bounded-size + reap semantics, no engine needed."""

    def _h(self, rid, priority=0, deadline=None):
        from paddle_tpu.serving import RequestHandle
        return RequestHandle(rid, [1], 1,
                             GenerationConfig(max_new_tokens=2),
                             priority=priority, deadline=deadline)

    def test_priority_then_fifo(self):
        from paddle_tpu.serving import RequestQueue
        q = RequestQueue(8)
        for h in (self._h(0, 5), self._h(1, 0), self._h(2, 0),
                  self._h(3, 2)):
            q.put(h)
        order = []
        while q.depth:
            order.append(q.pop_if(lambda h: True).id)
        # lower priority value first; FIFO within a priority
        assert order == [1, 2, 3, 0]

    def test_bounded_put_raises(self):
        from paddle_tpu.serving import RequestQueue
        q = RequestQueue(2)
        q.put(self._h(0))
        q.put(self._h(1))
        with pytest.raises(QueueFull):
            q.put(self._h(2))

    def test_reap_removes_deep_entries(self):
        from paddle_tpu.serving import RequestQueue
        q = RequestQueue(8)
        live = self._h(0, 0)
        expired = self._h(1, 3, deadline=time.monotonic() - 1)
        cancelled = self._h(2, 5)
        cancelled._cancel_requested = True
        for h in (live, expired, cancelled):
            q.put(h)
        dead = q.reap(time.monotonic())
        assert {h.id for h in dead} == {1, 2}
        assert q.depth == 1
        assert q.pop_if(lambda h: True).id == 0

    def test_pop_if_defers_on_false(self):
        from paddle_tpu.serving import RequestQueue
        q = RequestQueue(4)
        q.put(self._h(0))
        assert q.pop_if(lambda h: False) is None
        assert q.depth == 1


class TestCapacityProbe:
    """Public free_slots()/can_admit(): the scheduler path is probe +
    defer; add_request raising is the programmer-error path."""

    def test_dense_probe_and_loud_add(self):
        model, cfg = tiny_model()
        eng = engine_helpers.paged_engine(model, max_batch=2, max_len=32)
        gc = GenerationConfig(max_new_tokens=4, eos_token_id=None)
        assert eng.free_slots() == 2
        assert eng.can_admit(5, gc)
        # over max_len: probe says no (deferral would never help, and
        # add_request raises loudly for callers that skip the probe)
        assert not eng.can_admit(30, gc)
        with pytest.raises(ValueError, match="max_len"):
            eng.add_request(np.arange(30, dtype=np.int32), gc)
        rng = np.random.RandomState(0)
        for p in _prompts(rng, cfg.vocab_size, [4, 4]):
            eng.add_request(p, gc)
        assert eng.free_slots() == 0
        assert not eng.can_admit(4, gc)      # no free slot -> defer
        with pytest.raises(RuntimeError, match="free slot"):
            eng.add_request(np.arange(4, dtype=np.int32), gc)

    def test_paged_probe_sees_pool_pressure(self):
        model, cfg = tiny_model()
        # 6 pages * 8 = 48 tokens; each request reserves
        # ceil((18+6)/8) = 3 pages
        eng = paged_engine(model, max_batch=3, num_pages=6, page_size=8,
                           max_pages=6)
        gc = GenerationConfig(max_new_tokens=6, eos_token_id=None)
        assert eng.can_admit(18, gc)
        rng = np.random.RandomState(1)
        eng.add_request(rng.randint(0, cfg.vocab_size, (18,))
                        .astype(np.int32), gc)
        eng.add_request(rng.randint(0, cfg.vocab_size, (18,))
                        .astype(np.int32), gc)
        # slots free, pool full: probe defers, add_request is loud
        assert eng.free_slots() == 1
        assert not eng.can_admit(18, gc)
        with pytest.raises(RuntimeError, match="exhausted"):
            eng.add_request(rng.randint(0, cfg.vocab_size, (18,))
                            .astype(np.int32), gc)


class TestEngineCancellation:
    def test_cancel_mid_decode_releases_slot_and_pages(self, mon):
        model, cfg = tiny_model()
        eng = paged_engine(model, max_batch=2, num_pages=12)
        gc = GenerationConfig(max_new_tokens=30, eos_token_id=None)
        rng = np.random.RandomState(2)
        rid = eng.add_request(rng.randint(0, cfg.vocab_size, (6,))
                              .astype(np.int32), gc)
        eng.decode_segment(2)
        assert eng.partial_tokens(rid) is not None
        partial = eng.cancel_request(rid)
        # admission token + 2 segment tokens, slot AND pages reclaimed
        assert len(partial) == 3
        assert eng.free_slots() == 2
        assert eng.alloc.free_pages == eng.num_pages
        # a cancelled request never surfaces as finished
        assert rid not in eng.collect_finished()
        assert eng.partial_tokens(rid) is None
        # idempotent / unknown rid
        assert eng.cancel_request(rid) is None
        ev = {s["labels"]["event"]: s["value"]
              for s in monitor.snapshot()["metrics"]
              ["paddle_tpu_requests_total"]["samples"]}
        assert ev.get("cancelled") == 1

    def test_failed_admission_leaks_no_capacity(self):
        """add_request raising mid-admission (after the slot pop) must
        restore the slot and any page reservation."""
        model, cfg = tiny_model()
        eng = paged_engine(model, max_batch=2, num_pages=12)
        gc = GenerationConfig(max_new_tokens=4, eos_token_id=None)
        # force a failure AFTER capacity was claimed
        orig = eng._admit_state
        eng._admit_state = lambda *a: (_ for _ in ()).throw(
            RuntimeError("injected admit fault"))
        with pytest.raises(RuntimeError, match="injected"):
            eng.add_request(np.arange(6, dtype=np.int32), gc)
        eng._admit_state = orig
        assert eng.free_slots() == 2
        assert eng.alloc.free_pages == eng.num_pages
        # the engine still works afterwards
        rid = eng.add_request(np.arange(6, dtype=np.int32), gc)
        while eng.decode_segment(4):
            pass
        assert len(eng.collect_finished()[rid]) == 4

    def test_capacity_freed_for_next_request(self):
        model, cfg = tiny_model()
        # pool fits ONE reservation at a time
        eng = paged_engine(model, max_batch=2, num_pages=3, page_size=8,
                           max_pages=4)
        gc = GenerationConfig(max_new_tokens=10, eos_token_id=None)
        rng = np.random.RandomState(3)
        p1, p2 = _prompts(rng, cfg.vocab_size, [12, 12])
        rid = eng.add_request(p1, gc)
        assert not eng.can_admit(12, gc)
        eng.cancel_request(rid)
        assert eng.can_admit(12, gc)
        rid2 = eng.add_request(p2, gc)
        while eng.decode_segment(4, gc):
            pass
        assert len(eng.collect_finished()[rid2]) == 10


class TestPerRequestConfigs:
    """Per-request GenerationConfig threading: one compiled segment
    program serves a mixed greedy/sampled/eos batch, and the greedy
    request stays bitwise-parity with the dense engine."""

    def test_mixed_configs_single_program(self, mon):
        model, cfg = tiny_model(layers=2)
        rng = np.random.RandomState(4)
        p_greedy, p_samp, p_eos = _prompts(rng, cfg.vocab_size,
                                           [5, 9, 7])

        dense = CausalLMEngine(model, max_batch=1, max_len=64)
        gc_greedy = GenerationConfig(max_new_tokens=10, do_sample=False,
                                     eos_token_id=None)
        want = dense.generate(p_greedy[None], gc_greedy)[0, 5:]
        # an eos id the eos-request actually emits mid-stream
        probe = dense.generate(p_eos[None], GenerationConfig(
            max_new_tokens=10, eos_token_id=None))[0, 7:]
        eos = int(probe[3])

        eng = engine_helpers.paged_engine(model, max_batch=3, max_len=64)
        r1 = eng.add_request(p_greedy, gc_greedy)
        r2 = eng.add_request(p_samp, GenerationConfig(
            max_new_tokens=6, do_sample=True, temperature=0.7, top_k=9,
            top_p=0.9, seed=11, eos_token_id=None))
        r3 = eng.add_request(p_eos, GenerationConfig(
            max_new_tokens=10, eos_token_id=eos))
        while eng.decode_segment(3):
            pass
        outs = eng.collect_finished()
        np.testing.assert_array_equal(outs[r1], want)
        assert len(outs[r2]) == 6
        # the eos request stops at ITS eos; the greedy one ignores it
        o3 = list(outs[r3])
        assert o3[:4] == [int(t) for t in probe[:3]] + [eos]
        # ONE cb_segment compile across every config mix (the sampling
        # parameters are data, not trace constants)
        misses = monitor.jit_miss_by_fn()
        assert misses.get("cb_segment") == 1, misses

    def test_per_request_seed_threads_into_decode(self):
        """The request's seed drives ITS sampled trajectory (folded into
        every decode step's noise key), not just the admission token:
        same seed reproduces, different seed diverges."""
        model, cfg = tiny_model()
        rng = np.random.RandomState(5)
        p = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)

        def run(seed):
            eng = engine_helpers.paged_engine(model, max_batch=1, max_len=64)
            rid = eng.add_request(p, GenerationConfig(
                max_new_tokens=16, do_sample=True, temperature=3.0,
                seed=seed, eos_token_id=None))
            while eng.decode_segment(4):
                pass
            return list(eng.collect_finished()[rid])

        assert run(1) == run(1)
        assert run(1) != run(2)


def _server(model_layers=1, **kw):
    model, cfg = tiny_model(layers=model_layers)
    defaults = dict(max_batch=3, num_pages=24, page_size=8, max_pages=8)
    eng_kw = {k: kw.pop(k) for k in list(kw)
              if k in ("max_batch", "num_pages", "page_size",
                       "max_pages")}
    eng = paged_engine(model, **{**defaults, **eng_kw})
    return Server(eng, **kw), eng, cfg


def _slow_segments(eng, s=0.01):
    """Pace the engine's segments so a 48-token request holds its slot
    for half a second: the client sees a first token only once the first
    segment is dispatched (its compile done), after which a tiny model on
    the CPU would finish the rest inside a 50 ms deadline."""
    ds = eng.decode_segment

    def slow(*a, **kw):
        time.sleep(s)
        return ds(*a, **kw)

    eng.decode_segment = slow


class TestServerOnline:
    def test_acceptance_demo_end_to_end(self, mon):
        """ISSUE-2 acceptance: >= 8 concurrent requests, mixed prompt
        lengths and per-request configs, interleaved completion,
        streaming before completion, TTFT/queue-depth in the export."""
        srv, eng, cfg = _server(max_queue=16, segment_steps=3)
        try:
            rng = np.random.RandomState(0)
            spec = [(5, 20), (9, 4), (3, 8), (12, 6), (4, 12), (7, 4),
                    (2, 16), (6, 5)]
            handles = []
            for i, (plen, mx) in enumerate(spec):
                p = rng.randint(0, cfg.vocab_size, (plen,)) \
                    .astype(np.int32)
                gc = GenerationConfig(max_new_tokens=mx,
                                      do_sample=(i % 3 == 0),
                                      temperature=0.9, seed=i,
                                      eos_token_id=None)
                handles.append(srv.submit(p, gc))

            # stream the FIRST (longest) request while the rest run
            seen = []
            def consume():
                for tok in handles[0].stream(timeout=60):
                    seen.append((tok, handles[0].status))
            t = threading.Thread(target=consume)
            t.start()
            outs = [h.result(timeout=120) for h in handles]
            t.join(60)

            # every request respected ITS OWN budget
            assert [len(o) for o in outs] == [mx for _, mx in spec]
            # interleaved (continuous-batched) completion: the 20-token
            # request 0 finished AFTER later-submitted short requests
            finished_before_0 = [i for i in range(1, 8)
                                 if handles[i].finish_ts
                                 < handles[0].finish_ts]
            assert finished_before_0, "no interleaving observed"
            # streamed tokens arrived BEFORE completion
            assert any(s == "running" for _, s in seen)
            assert [tok for tok, _ in seen] == [int(x) for x in outs[0]]
            # TTFT / queue-depth series visible via the monitor export
            snap = monitor.snapshot()["metrics"]
            ttft = snap["paddle_tpu_serving_ttft_seconds"]["samples"][0]
            assert ttft["count"] >= 8
            assert ttft["labels"]["server"] == srv.monitor_server
            assert "paddle_tpu_serving_queue_depth" in snap
            prom = monitor.render_prometheus()
            assert "paddle_tpu_serving_ttft_seconds_bucket" in prom
            assert "paddle_tpu_serving_queue_depth" in prom
        finally:
            srv.shutdown(drain=False)

    def test_cancel_reclaims_capacity_for_queued(self, mon):
        """One cancellation must free a slot (and pages) that a QUEUED
        request then takes — the acceptance demo's reclaim leg."""
        srv, eng, cfg = _server(max_batch=2, num_pages=10,
                                max_queue=8, segment_steps=2)
        try:
            rng = np.random.RandomState(1)
            long_cfg = GenerationConfig(max_new_tokens=56,
                                        eos_token_id=None)
            h1 = srv.submit(rng.randint(0, cfg.vocab_size, (6,))
                            .astype(np.int32), long_cfg)
            h2 = srv.submit(rng.randint(0, cfg.vocab_size, (6,))
                            .astype(np.int32), long_cfg)
            # both slots occupied; this one has to queue
            h3 = srv.submit(rng.randint(0, cfg.vocab_size, (4,))
                            .astype(np.int32),
                            GenerationConfig(max_new_tokens=5,
                                             eos_token_id=None))
            # wait until h1 is actually running (first token streamed)
            next(iter(h1.stream(timeout=60)))
            assert h3.status == "queued"
            h1.cancel()
            out3 = h3.result(timeout=120)
            assert len(out3) == 5
            with pytest.raises(RequestCancelled):
                h1.result(timeout=60)
            assert len(h1.tokens_so_far()) >= 1   # partials retained
            ev = {s["labels"]["event"]: s["value"]
                  for s in monitor.snapshot()["metrics"]
                  ["paddle_tpu_serving_requests_total"]["samples"]}
            assert ev.get("cancelled") == 1
            h2.cancel()
        finally:
            srv.shutdown(drain=False)

    def test_queue_full_rejection(self, mon):
        srv, eng, cfg = _server(max_batch=1, num_pages=24, max_queue=2,
                                segment_steps=2)
        try:
            rng = np.random.RandomState(2)
            gc = GenerationConfig(max_new_tokens=40, eos_token_id=None)
            hs = [srv.submit(rng.randint(0, cfg.vocab_size, (4,))
                             .astype(np.int32), gc)]
            next(iter(hs[0].stream(timeout=60)))   # slot occupied
            for _ in range(2):                     # fill the queue
                hs.append(srv.submit(
                    rng.randint(0, cfg.vocab_size, (4,))
                    .astype(np.int32), gc))
            with pytest.raises(QueueFull) as ei:
                srv.submit(rng.randint(0, cfg.vocab_size, (4,))
                           .astype(np.int32), gc)
            assert ei.value.reason == "queue_full"
            ev = {s["labels"]["event"]: s["value"]
                  for s in monitor.snapshot()["metrics"]
                  ["paddle_tpu_serving_requests_total"]["samples"]}
            assert ev.get("rejected_queue_full") == 1
            for h in hs:
                h.cancel()
        finally:
            srv.shutdown(drain=False)

    def test_deadline_expired_never_admits(self, mon):
        srv, eng, cfg = _server(max_batch=1, num_pages=24,
                                segment_steps=2)
        _slow_segments(eng)
        try:
            rng = np.random.RandomState(3)
            h1 = srv.submit(rng.randint(0, cfg.vocab_size, (4,))
                            .astype(np.int32),
                            GenerationConfig(max_new_tokens=48,
                                             eos_token_id=None))
            next(iter(h1.stream(timeout=60)))      # slot occupied
            h2 = srv.submit(rng.randint(0, cfg.vocab_size, (4,))
                            .astype(np.int32),
                            GenerationConfig(max_new_tokens=4,
                                             eos_token_id=None),
                            timeout_s=0.05)
            with pytest.raises(DeadlineExpired):
                h2.result(timeout=60)
            assert h2.engine_rid is None           # never admitted
            assert h2.tokens_so_far() == []
            ev = {s["labels"]["event"]: s["value"]
                  for s in monitor.snapshot()["metrics"]
                  ["paddle_tpu_serving_requests_total"]["samples"]}
            assert ev.get("expired") == 1
            h1.cancel()
        finally:
            srv.shutdown(drain=False)

    def test_drain_finishes_inflight_rejects_new(self):
        srv, eng, cfg = _server(segment_steps=3)
        try:
            rng = np.random.RandomState(4)
            hs = [srv.submit(rng.randint(0, cfg.vocab_size, (n,))
                             .astype(np.int32),
                             GenerationConfig(max_new_tokens=6,
                                              eos_token_id=None))
                  for n in (5, 8, 3, 6)]
            assert srv.drain(timeout=120)
            with pytest.raises(RequestRejected) as ei:
                srv.submit(np.arange(3, dtype=np.int32),
                           GenerationConfig(max_new_tokens=2))
            assert ei.value.reason == "draining"
            for h in hs:
                assert h.status == "finished"
                assert len(h.result(timeout=1)) == 6
        finally:
            srv.shutdown(drain=False)

    def test_scheduler_death_fails_handles_not_hangs(self):
        """If the loop dies (engine bug, XLA error), every outstanding
        handle must reach a terminal state — clients blocked in
        result() would otherwise hang forever — and healthz-facing
        status must say 'failed'. max_restarts=0 disables supervised
        recovery so the first engine fault IS the death (the recovery
        path has its own suite: test_serving_faults.py)."""
        srv, eng, cfg = _server(segment_steps=2, max_restarts=0)
        try:
            def boom(*a, **kw):
                raise RuntimeError("injected engine fault")
            eng.decode_segment = boom
            h = srv.submit(np.arange(4, dtype=np.int32),
                           GenerationConfig(max_new_tokens=8,
                                            eos_token_id=None))
            with pytest.raises(RequestFailed, match="scheduler died"):
                h.result(timeout=60)
            assert srv.status == "failed"
            # a dead server rejects instead of queueing into the void
            with pytest.raises(RequestRejected, match="scheduler died"):
                srv.submit(np.arange(3, dtype=np.int32),
                           GenerationConfig(max_new_tokens=2))
        finally:
            srv.shutdown(drain=False)

    def test_never_fitting_request_fails_fast(self):
        # pool holds 2 pages = 16 tokens total; prompt 20 fits max_len
        # (32) but can never reserve -> FAILED, not wedged-forever
        srv, eng, cfg = _server(max_batch=2, num_pages=2, page_size=8,
                                max_pages=4)
        try:
            h = srv.submit(np.arange(20, dtype=np.int32) % cfg.vocab_size,
                           GenerationConfig(max_new_tokens=4,
                                            eos_token_id=None))
            with pytest.raises(RequestFailed, match="never"):
                h.result(timeout=60)
            # prompt too long for max_len rejects AT SUBMIT
            with pytest.raises(ValueError, match="max_len"):
                srv.submit(np.arange(40, dtype=np.int32),
                           GenerationConfig(max_new_tokens=4))
        finally:
            srv.shutdown(drain=False)


class _PushAtOnce(Server):
    """The order before ISSUE 37: every token and terminal state handed
    to its handle the moment the scheduler owes it."""

    def _push_delta(self, h, toks):
        super()._push_delta(h, toks)
        self._flush()

    def _finish_later(self, h, status, error=None):
        super()._finish_later(h, status, error)
        self._flush()


class TestPushAfterDispatch:
    """ISSUE 37: what a segment's collection owes the handles (and what
    the gap owes them: first tokens, cancellations) is handed over after
    the next segment's dispatch, or at once where none follows."""

    def _streamed(self, server_cls):
        model, cfg = tiny_model()
        srv = server_cls(paged_engine(model, max_batch=2), max_queue=16,
                         segment_steps=3, start=False)
        rng = np.random.RandomState(11)
        spec = [(5, 7), (9, 4), (3, 11), (7, 1), (4, 6)]
        hs = [srv.submit(p, GenerationConfig(max_new_tokens=n,
                                             eos_token_id=None))
              for p, (_, n) in zip(_prompts(rng, cfg.vocab_size,
                                            [n for n, _ in spec]), spec)]
        got = [[] for _ in hs]
        readers = [threading.Thread(target=lambda h=h, out=out:
                                    out.extend(h.stream(timeout=120)))
                   for h, out in zip(hs, got)]
        for t in readers:
            t.start()
        srv._thread.start()
        try:
            for t in readers:
                t.join(timeout=180)
            assert srv.drain(timeout=60)
        finally:
            srv.shutdown(drain=False)
        return got, [h.status for h in hs], [len(h.tokens_so_far())
                                             for h in hs]

    def test_streams_equal_the_order_of_pushes_at_once(self):
        """Greedy: every handle streams the same tokens, to the same
        status, whether the pushes wait for the next dispatch or not;
        one request of budget 1 retires at its admission."""
        got, status, n = self._streamed(Server)
        want, want_status, want_n = self._streamed(_PushAtOnce)
        assert got == want
        assert status == want_status == ["finished"] * 5
        assert n == want_n == [7, 4, 11, 1, 6]

    def test_streams_whole_under_thread_switches(self):
        """More client threads than cores, the interpreter switching
        threads every 10 us, and a third of the requests cancelled
        mid-stream: every stream ends, holds exactly the handle's
        tokens, and drain() returns only once all are terminal."""
        import os
        import sys

        model, cfg = tiny_model()
        srv = Server(paged_engine(model, max_batch=3), max_queue=64,
                     segment_steps=2)
        n = max(12, 2 * (os.cpu_count() or 1))
        rng = np.random.RandomState(17)
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            hs = [srv.submit(p, GenerationConfig(
                      max_new_tokens=int(rng.randint(1, 12)),
                      eos_token_id=None))
                  for p in _prompts(rng, cfg.vocab_size,
                                    rng.randint(2, 9, n))]
            got = [[] for _ in hs]

            def read(i):
                for t in hs[i].stream(timeout=120):
                    got[i].append(t)
                    if i % 3 == 0:
                        hs[i].cancel()

            readers = [threading.Thread(target=read, args=(i,))
                       for i in range(n)]
            for t in readers:
                t.start()
            for t in readers:
                t.join(timeout=180)
            assert not any(t.is_alive() for t in readers)
            assert srv.drain(timeout=60)
            for h, g in zip(hs, got):
                assert h.done and g == h.tokens_so_far()
                if h.status == "finished":
                    assert len(g) == h.cfg.max_new_tokens
                else:
                    assert h.status == "cancelled"
        finally:
            sys.setswitchinterval(old)
            srv.shutdown(drain=False)

    def test_cancelled_running_request_finishes_once(self):
        model, cfg = tiny_model()
        srv, eng, _ = _server(segment_steps=2)
        _slow_segments(eng)
        p = np.arange(5, dtype=np.int32)
        try:
            h = srv.submit(p, GenerationConfig(max_new_tokens=40,
                                               eos_token_id=None))
            next(iter(h.stream(timeout=60)))
            calls = []
            real = h._finish
            h._finish = lambda *a: (calls.append(a[0]), real(*a))
            h.cancel()
            with pytest.raises(RequestCancelled):
                h.result(timeout=60)
            assert srv.drain(timeout=60)
            assert calls == ["cancelled"]
            # what it streamed is a prefix of the uncancelled run
            dense = CausalLMEngine(model, max_batch=1, max_len=64)
            full = dense.generate(p[None], GenerationConfig(
                max_new_tokens=40, eos_token_id=None))[0, len(p):]
            toks = h.tokens_so_far()
            assert 1 <= len(toks) < 40
            assert toks == [int(t) for t in full[:len(toks)]]
            assert eng.free_slots() == eng.max_batch
        finally:
            srv.shutdown(drain=False)

    def test_cancel_while_its_finish_is_owed(self):
        """A client cancels a request the engine has already retired
        but whose finish the scheduler still owes: the handle is
        finished once, as finished, with every token."""
        srv, eng, cfg = _server(segment_steps=4, start=False)
        try:
            h = srv.submit(np.arange(4, dtype=np.int32),
                           GenerationConfig(max_new_tokens=3,
                                            eos_token_id=None))
            assert srv._step(False)     # admit, segment, collect: retired
            assert h.status == "running" and srv._pending
            calls = []
            real = h._finish
            h._finish = lambda *a: (calls.append(a[0]), real(*a))
            h.cancel()                  # not terminal yet: flagged
            assert not srv._step(False)  # nothing live: the idle flush
            assert calls == ["finished"] and not srv._pending
            assert len(h.result(timeout=1)) == 3
            assert eng.free_slots() == eng.max_batch
        finally:
            srv.shutdown(drain=False)


class TestHTTPFrontend:
    def test_roundtrip_health_metrics_and_streaming(self, mon):
        srv, eng, cfg = _server(max_queue=8, segment_steps=2)
        httpd = serve_http(srv)
        port = httpd.server_address[1]
        from urllib.request import Request, urlopen
        try:
            # healthz
            with urlopen(f"http://127.0.0.1:{port}/healthz",
                         timeout=30) as r:
                health = json.load(r)
            assert health["status"] == "ok"
            assert health["free_slots"] == 3
            # non-streaming round trip
            body = json.dumps({"prompt": [1, 2, 3],
                               "max_new_tokens": 5}).encode()
            with urlopen(Request(
                    f"http://127.0.0.1:{port}/generate", data=body),
                    timeout=120) as r:
                out = json.load(r)
            assert len(out["tokens"]) == out["n_tokens"] == 5
            assert out["ttft_s"] > 0
            # streaming round trip: ndjson token lines then done line
            import http.client
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=120)
            conn.request("POST", "/generate", json.dumps(
                {"prompt": [4, 5, 6], "max_new_tokens": 8,
                 "stream": True}), {"Content-Type": "application/json"})
            resp = conn.getresponse()
            lines, stamps = [], []
            while True:
                line = resp.readline()
                if not line:
                    break
                lines.append(json.loads(line))
                stamps.append(time.monotonic())
            conn.close()
            assert [ln["token"] for ln in lines[:-1]] \
                and len(lines) == 9
            assert lines[-1] == {"done": True, "status": "finished",
                                 "n_tokens": 8,
                                 "request_id": lines[-1]["request_id"]}
            # tokens arrived incrementally, not as one post-hoc blob
            assert stamps[-1] > stamps[0]
            # /metrics re-exports the monitor registry
            with urlopen(f"http://127.0.0.1:{port}/metrics",
                         timeout=30) as r:
                prom = r.read().decode()
            assert "paddle_tpu_serving_ttft_seconds_bucket" in prom
        finally:
            httpd.shutdown()
            srv.shutdown(drain=False)

    def test_error_codes(self):
        from urllib.error import HTTPError
        from urllib.request import Request, urlopen

        srv, eng, cfg = _server()
        _slow_segments(eng)
        httpd = serve_http(srv)
        port = httpd.server_address[1]
        url = f"http://127.0.0.1:{port}/generate"
        try:
            # malformed config -> 400 before anything touches the engine
            for bad in ({"prompt": [1], "temperature": 0},
                        {"prompt": [1], "max_new_tokens": 0},
                        {"prompt": [1], "top_p": 2},
                        {"prompt": []}, {"prompt": "abc"}, {}):
                with pytest.raises(HTTPError) as ei:
                    urlopen(Request(url, data=json.dumps(bad).encode()),
                            timeout=30)
                assert ei.value.code == 400
            with pytest.raises(HTTPError) as ei:
                urlopen(f"http://127.0.0.1:{port}/nope", timeout=30)
            assert ei.value.code == 404
            # streaming request that expires before its first token ->
            # a real 504, not a 200 that apologizes in the trailer
            rng = np.random.RandomState(9)
            blocker = [srv.submit(rng.randint(0, cfg.vocab_size, (4,))
                                  .astype(np.int32),
                                  GenerationConfig(max_new_tokens=48,
                                                   eos_token_id=None))
                       for _ in range(3)]
            next(iter(blocker[0].stream(timeout=60)))
            with pytest.raises(HTTPError) as ei:
                urlopen(Request(url, data=json.dumps(
                    {"prompt": [1, 2], "max_new_tokens": 4,
                     "stream": True, "timeout_s": 0.05}).encode()),
                        timeout=60)
            assert ei.value.code == 504
            for h in blocker:
                h.cancel()
            # draining -> 503 with reason
            srv.drain(timeout=60)
            with pytest.raises(HTTPError) as ei:
                urlopen(Request(url, data=json.dumps(
                    {"prompt": [1], "max_new_tokens": 2}).encode()),
                        timeout=30)
            assert ei.value.code == 503
            assert json.load(ei.value)["reason"] == "draining"
        finally:
            httpd.shutdown()
            srv.shutdown(drain=False)


@pytest.mark.slow
class TestServeBenchSoak:
    def test_open_loop_soak(self, mon, capsys, tmp_path):
        """serve_bench drives a live Server open-loop and reports
        TTFT/TPOT/throughput percentiles (the PERF.md methodology)."""
        import importlib.util
        import os

        tools_dir = os.path.join(os.path.dirname(__file__), "..",
                                 "tools")

        def load(name):
            spec = importlib.util.spec_from_file_location(
                name, os.path.join(tools_dir, f"{name}.py"))
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod

        sb = load("serve_bench")
        out = tmp_path / "soak.jsonl"
        assert sb.main(["--rate", "30", "--requests", "24",
                        "--max-new", "8", "--prompt-len", "3:12",
                        "--monitor-out", str(out)]) == 0
        text = capsys.readouterr().out
        assert "serve_ttft_p50" in text and "serve_throughput" in text
        assert out.exists()
        mr = load("monitor_report")
        with open(out) as f:
            rendered = mr.render(mr.load_jsonl(f), serving=True)
        assert "paddle_tpu_serving_ttft_seconds" in rendered
