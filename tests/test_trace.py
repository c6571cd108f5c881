"""Request-lifecycle tracing + flight recorder suite (ISSUE 9).

Covers ``paddle_tpu.tracing`` end to end on CPU:

- the RECORDER: near-zero disabled path (no events, shared null span),
  bounded ring with tail-preserving reconfiguration, begin-time-ordered
  timelines keyed by rid (batch-wide ``rids`` events fan out to every
  carried request), Chrome-trace export through the profiler's shared
  writer, flight dumps with reason metadata, flag sync
  (``FLAGS_enable_trace``);
- SERVER integration: a request's timeline shows
  queue → admit (with the prefill bucket) → segments → finish in
  order; chunked admissions record one event per prefill chunk; THE
  acceptance scenario — a preempted-and-replayed request's timeline
  shows queue → admit → segments → preempt → replay → admit → finish,
  surviving the engine-rid change;
- the HTTP debug surface: ``GET /trace?rid=`` returns the timeline,
  bare ``/trace`` the newest events, and a disabled recorder is an
  honest 404;
- the serve_bench TTFT decomposition (queue/prefill/gap shares sum to
  the server-side TTFT) and the ``monitor_report --trace`` phase
  table / slowest-requests view;
- the span TREE (ISSUE 25): ids and parents across nested spans, across
  threads and for instants; the old four keys of an event; no profiler
  annotation at a span site while tracing is off; under a CPU
  ``jax.profiler`` session every ring span is in ``/host:CPU`` by id;
  behind ``Server`` the loop's ``step`` > ``gap`` > ``admit`` > the five
  ``engine.*`` children nest by parent id, and ``engine.segment``
  carries the context lengths the test knows;
- the segment's host phases (ISSUE 36): ``engine.dispatch``,
  ``engine.wait``, ``engine.collect`` as the three children of every
  ``engine.segment`` (and of the device-mode ``engine.spec_segment``),
  ``engine.tables`` before it with ``changed`` over known sequences,
  the two children of ``engine.first_token``, ``collect``'s ``pushed``,
  and nothing recorded or built with tracing off.

The flight-recorder triggers (engine fault / stall / preemption storm)
are exercised where the faults are injected — the chaos suite
(``tests/test_serving_faults.py`` ``TestFlightRecorder``); the
monitor-registry retirement regression lives in ``tests/test_monitor.py``.
"""
import glob
import json
import os
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import tracing as trace
from paddle_tpu.inference.generation import (GenerationConfig,
                                             PagedContinuousBatchingEngine)
from paddle_tpu.serving import Server, serve_http

_MODEL = None


def tiny_model():
    """ONE tiny llama shared by the whole module (jit programs are
    keyed on shapes — same page_size/bucket shapes below keep the
    suite to a handful of compiles)."""
    global _MODEL
    if _MODEL is None:
        paddle.seed(0)
        from paddle_tpu.models import LlamaForCausalLM, llama_config
        cfg = llama_config("tiny", num_hidden_layers=1)
        _MODEL = (LlamaForCausalLM(cfg), cfg)
    return _MODEL


def paged_engine(model, max_batch=4, num_pages=64, page_size=4,
                 max_pages=8, **kw):
    kw.setdefault("debug_pages", True)
    return PagedContinuousBatchingEngine(
        model, max_batch=max_batch, num_pages=num_pages,
        page_size=page_size, max_pages=max_pages, **kw)


def _greedy(n):
    return GenerationConfig(max_new_tokens=n, eos_token_id=None)


def _prompts(cfg, n, plen=6, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (plen,)).astype(np.int32)
            for _ in range(n)]


@pytest.fixture()
def tr(tmp_path):
    """Tracing armed for one test, ring cleared both ways, dumps into
    the test's tmp dir."""
    trace.clear()
    trace.enable(dump_dir=str(tmp_path))
    yield trace
    trace.disable()
    trace.clear()
    trace.configure(capacity=trace.DEFAULT_CAPACITY)


class TestRecorder:
    def test_disabled_is_noop(self):
        trace.disable()
        trace.clear()
        trace.event("x", rid=1)
        assert trace.events() == []
        # the disabled span is THE shared null object: no allocation
        assert trace.span("z", rid=1) is trace.NULL_SPAN
        with trace.span("z"):
            pass
        assert trace.events() == []
        # no black box was recording -> no dump to write
        assert trace.dump("whatever") is None

    def test_flag_sync(self):
        paddle.set_flags({"FLAGS_enable_trace": True})
        assert trace.enabled()
        paddle.set_flags({"FLAGS_enable_trace": False})
        assert not trace.enabled()
        trace.enable()
        assert trace.enabled()
        assert paddle.get_flags("FLAGS_enable_trace")[
            "FLAGS_enable_trace"]
        trace.disable()

    def test_ring_bound_and_reconfigure(self, tr):
        trace.configure(capacity=4)
        for i in range(10):
            trace.event("e", rid=i)
        evs = trace.events()
        assert [e["rid"] for e in evs] == [6, 7, 8, 9]
        # shrinking keeps the newest tail
        trace.configure(capacity=2)
        assert [e["rid"] for e in trace.events()] == [8, 9]
        with pytest.raises(ValueError):
            trace.configure(capacity=0)

    def test_reconfigure_smaller_twice_rebuilds(self, tr):
        """Regression (ISSUE 16 satellite): a second configure() with a
        SMALLER capacity must rebuild the ring — newest tail kept,
        subsequent recording bounded by the new capacity — and a
        same-capacity call must be an idempotent no-op (events
        untouched)."""
        trace.configure(capacity=8)
        for i in range(8):
            trace.event("e", rid=i)
        trace.configure(capacity=4)       # first shrink
        assert [e["rid"] for e in trace.events()] == [4, 5, 6, 7]
        trace.configure(capacity=2)       # second, smaller again
        assert [e["rid"] for e in trace.events()] == [6, 7]
        trace.event("e", rid=99)          # the NEW bound is live
        assert [e["rid"] for e in trace.events()] == [7, 99]
        trace.configure(capacity=2)       # same capacity: no-op
        assert [e["rid"] for e in trace.events()] == [7, 99]
        trace.configure(capacity=16)      # growing keeps everything
        assert [e["rid"] for e in trace.events()] == [7, 99]

    def test_timeline_order_and_rids_fanout(self, tr):
        trace.event("queue.enqueue", rid="s:1")
        with trace.span("admit", rid="s:1", plen=6, bucket=8):
            pass
        # batch-wide event carrying both requests
        with trace.span("segment", rids=("s:1", "s:2"), steps=4):
            pass
        trace.event("finish", rid="s:2", status="finished")
        t1 = trace.timeline("s:1")
        assert [e["phase"] for e in t1] == ["queue.enqueue", "admit",
                                           "segment"]
        assert t1[1]["bucket"] == 8 and t1[1]["dur_ns"] >= 0
        t2 = trace.timeline("s:2")
        assert [e["phase"] for e in t2] == ["segment", "finish"]
        # timelines sort by BEGIN time even though spans land in the
        # ring at their end
        assert all(a["ts_ns"] <= b["ts_ns"]
                   for a, b in zip(t1, t1[1:]))

    def test_export_chrome_and_dump(self, tr, tmp_path):
        trace.event("queue.enqueue", rid="s:1", depth=2)
        with trace._lock:   # a span of a known duration
            trace._ring.append((1_000, 2_000_000, "s:1", "admit",
                                {"bucket": 16}, 1, 0))
        p = trace.export_chrome(str(tmp_path / "t.json"))
        doc = json.load(open(p))
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        assert {e["name"] for e in evs} == {"queue.enqueue", "admit"}
        span = next(e for e in evs if e["name"] == "admit")
        assert span["ph"] == "X" and abs(span["dur"] - 2000) < 1
        assert span["args"]["rid"] == "s:1"
        inst = next(e for e in evs if e["name"] == "queue.enqueue")
        assert inst["ph"] == "i" and inst["args"]["depth"] == 2
        # the flight dump carries its reason and lands in dump_dir
        d = trace.dump("unit reason")
        assert os.path.dirname(d) == str(tmp_path)
        doc2 = json.load(open(d))
        assert doc2["otherData"]["reason"] == "unit reason"
        assert len(doc2["traceEvents"]) == 2


class TestServerTimeline:
    def test_lifecycle_order_and_bucket(self, tr):
        model, mcfg = tiny_model()
        eng = paged_engine(model)
        srv = Server(eng, segment_steps=4)
        hs = [srv.submit(p, _greedy(8)) for p in _prompts(mcfg, 2)]
        for h in hs:
            h.result(timeout=120)
        tl = hs[0].timeline()
        ph = [e["phase"] for e in tl]
        i = ph.index
        assert (i("queue.enqueue") < i("queue.dequeue") < i("admit")
                < i("segment") < i("finish"))
        admit = tl[i("admit")]
        assert admit["plen"] == 6 and admit["bucket"] == 16  # 6 -> 16
        assert not admit["replay"]
        assert tl[i("finish")]["status"] == "finished"
        # server-side lookup by PUBLIC request id matches the handle's
        assert srv.request_timeline(hs[0].id) == tl
        # the two requests' timelines are distinct but share segments
        tl2 = hs[1].timeline()
        assert tl2[0]["rid"] != tl[0]["rid"]
        # engine-level prefill events recorded the bucket choice too
        assert any(e["phase"] == "engine.prefill" and e["bucket"] == 16
                   for e in trace.events())
        srv.shutdown()

    def test_chunked_admission_traces_each_chunk(self, tr):
        model, mcfg = tiny_model()
        eng = paged_engine(model, num_pages=64, max_pages=16,
                           prefill_chunk=8)
        srv = Server(eng, segment_steps=4)
        p = _prompts(mcfg, 1, plen=20)[0]
        h = srv.submit(p, _greedy(6))
        h.result(timeout=120)
        ph = [e["phase"] for e in h.timeline()]
        assert "admit.begin" in ph
        # 20 tokens @ chunk 8 -> 3 chunks, each its own gap event
        assert ph.count("prefill_chunk") == 3
        assert "admit.done" in ph
        assert (ph.index("admit.begin")
                < ph.index("prefill_chunk")
                < ph.index("admit.done") < ph.index("finish"))
        srv.shutdown()

    def test_preempted_and_replayed_timeline(self, tr):
        """THE acceptance scenario: a preempted-and-replayed request's
        timeline shows queue → admit → segments → preempt → replay →
        (re-)admit → finish IN ORDER, keyed by the handle id — the
        engine rid changed at replay and the timeline must not care."""
        model, mcfg = tiny_model()
        prompts = _prompts(mcfg, 4)
        # 4 x (6 + 20) tokens = 28 worst-case pages; 14 forces pressure
        eng = paged_engine(model, num_pages=14,
                           admission_mode="optimistic",
                           kv_watermark=1.0)
        srv = Server(eng, segment_steps=4, max_preemptions=50)
        hs = [srv.submit(p, _greedy(20)) for p in prompts]
        for h in hs:
            h.result(timeout=180)
        assert eng.alloc.preemptions >= 1
        victims = [h for h in hs if h._preempts > 0]
        assert victims
        h = victims[0]
        ph = [e["phase"] for e in h.timeline()]
        i = ph.index
        assert (i("queue.enqueue") < i("admit") < i("preempt")
                < i("replay") < i("finish"))
        # a decode segment ran between the first admission and the
        # preemption, and the replay re-admitted (a SECOND admit, with
        # replay=True, after the replay marker)
        assert "segment" in ph[i("admit"):i("preempt")]
        admits = [j for j, p_ in enumerate(ph) if p_ == "admit"]
        assert len(admits) >= 2 and admits[-1] > i("replay")
        tl = h.timeline()
        assert tl[admits[-1]]["replay"] is True
        assert tl[i("finish")]["status"] == "finished"
        srv.shutdown()

    def test_http_trace_endpoint(self, tr):
        model, mcfg = tiny_model()
        eng = paged_engine(model)
        srv = Server(eng, segment_steps=4)
        httpd = serve_http(srv, port=0)
        port = httpd.server_address[1]
        try:
            h = srv.submit(_prompts(mcfg, 1)[0], _greedy(6))
            h.result(timeout=120)
            doc = json.load(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/trace?rid={h.id}",
                timeout=10))
            assert doc["request_id"] == h.id
            phases = [e["phase"] for e in doc["events"]]
            assert "admit" in phases and phases[-1] == "finish"
            # bare /trace: the newest buffered events
            doc2 = json.load(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/trace", timeout=10))
            assert doc2["n"] > 0
            # malformed rid is a 400, not a crash
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/trace?rid=abc",
                    timeout=10)
            assert ei.value.code == 400
            # disabled recorder is an honest 404 with the enable hint
            trace.disable()
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/trace?rid={h.id}",
                    timeout=10)
            assert ei.value.code == 404
            assert "FLAGS_enable_trace" in json.load(ei.value)["error"]
        finally:
            httpd.shutdown()
            srv.shutdown()


def _by_id(evs):
    return {e["span.id"]: e for e in evs if e["span.id"]}


def _chain(ev, spans):
    """Phases from ``ev`` up to its root, by parent id."""
    out = [ev["phase"]]
    while ev["span.parent"]:
        ev = spans[ev["span.parent"]]
        out.append(ev["phase"])
    return out


# a cold one-shot admission of the paged engine: the claim, then ONE
# program (mini cache, prefill and install), then the first token
ENGINE_CHILDREN = ("engine.reserve", "engine.prefill",
                   "engine.first_token")


class TestSpanTree:
    def test_nested_ids_and_parents(self, tr):
        with trace.span("a") as a:
            with trace.span("b"):
                with trace.span("c"):
                    pass
            with trace.span("d"):
                pass
        evs = {e["phase"]: e for e in trace.events()}
        ids = [evs[k]["span.id"] for k in "abcd"]
        assert len(set(ids)) == 4 and all(i > 0 for i in ids)
        assert evs["a"]["span.parent"] == 0
        assert evs["b"]["span.parent"] == evs["a"]["span.id"]
        assert evs["c"]["span.parent"] == evs["b"]["span.id"]
        # a sibling opened after b closed hangs under a, not under b
        assert evs["d"]["span.parent"] == evs["a"]["span.id"]
        # what is known only at the end is set before the span closes
        with trace.span("e", steps=2) as e:
            e.set(emitted=7)
        got = trace.events()[-1]
        assert got["steps"] == 2 and got["emitted"] == 7
        assert a is not trace.NULL_SPAN

    def test_parents_do_not_cross_threads(self, tr):
        inner = threading.Event()
        leave = threading.Event()

        def other():
            with trace.span("t2.root"):
                with trace.span("t2.child"):
                    inner.set()
                    assert leave.wait(10)

        th = threading.Thread(target=other)
        with trace.span("t1.root"):
            th.start()
            assert inner.wait(10)
            # opened while the other thread's spans are open
            with trace.span("t1.child"):
                pass
            leave.set()
            th.join(10)
        assert not th.is_alive()
        evs = {e["phase"]: e for e in trace.events()}
        assert evs["t1.root"]["span.parent"] == 0
        assert evs["t2.root"]["span.parent"] == 0
        assert evs["t1.child"]["span.parent"] == evs["t1.root"]["span.id"]
        assert evs["t2.child"]["span.parent"] == evs["t2.root"]["span.id"]
        assert len({e["span.id"] for e in evs.values()}) == 4

    def test_instant_parent(self, tr):
        trace.event("outside")
        with trace.span("s"):
            trace.event("inside", rid="s:1")
        evs = {e["phase"]: e for e in trace.events()}
        assert evs["outside"]["span.id"] == 0
        assert evs["outside"]["span.parent"] == 0
        assert evs["inside"]["span.id"] == 0
        assert evs["inside"]["span.parent"] == evs["s"]["span.id"]

    @pytest.mark.parametrize("surface", ["events", "timeline"])
    def test_old_keys_stay(self, tr, surface):
        """Every caller of PR 8's shape keeps working: the four keys
        are there, the two new ones hold a dot, and no attribute can
        shadow either."""
        trace.event("queue.enqueue", rid="s:1", depth=2)
        with trace.span("admit", rid="s:1", plen=6, ts_ns=1):
            pass
        got = (trace.events() if surface == "events"
               else trace.timeline("s:1"))
        assert [e["phase"] for e in got] == ["queue.enqueue", "admit"]
        for e in got:
            assert {"phase", "rid", "ts_ns", "dur_ns"} <= set(e)
            assert {"span.id", "span.parent"} <= set(e)
            assert e["rid"] == "s:1"
        assert got[0]["depth"] == 2 and got[0]["dur_ns"] == 0
        assert got[1]["plen"] == 6 and got[1]["ts_ns"] > 1
        assert not hasattr(trace, "record")

    def test_off_constructs_no_annotation(self, monkeypatch):
        """With tracing off a span site builds nothing: not a span, not
        a profiler annotation — through a whole served request."""
        import jax.profiler

        made = []

        class Counting:
            def __init__(self, name, **kw):
                made.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
        monkeypatch.setattr(trace, "_annotation", None)
        trace.disable()
        trace.clear()
        assert trace.span("gap") is trace.NULL_SPAN
        trace.NULL_SPAN.set(emitted=1)
        model, mcfg = tiny_model()
        srv = Server(paged_engine(model), segment_steps=4)
        try:
            srv.submit(_prompts(mcfg, 1)[0], _greedy(6)).result(
                timeout=120)
        finally:
            srv.shutdown()
        assert made == [] and trace.events() == []
        # and on, the same site does (the fake proves the seam is live)
        trace.enable()
        try:
            with trace.span("gap", rid="s:1"):
                pass
        finally:
            trace.disable()
            trace.clear()
        assert made == ["pt:gap"]

    def test_ring_spans_in_profiler_host_plane(self, tr, tmp_path):
        """One clock: under a ``jax.profiler`` session (python tracer
        off, as the benchmark runs it) every ring span of the session is
        in ``/host:CPU`` under its id, as long as the ring says within
        1 ms, its parent beside it; a root names the process."""
        import jax
        from jax.profiler import ProfileData

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        model, mcfg = tiny_model()
        srv = Server(paged_engine(model), segment_steps=4)
        try:
            srv.submit(_prompts(mcfg, 1)[0], _greedy(6)).result(
                timeout=120)            # programs compiled
            trace.clear()
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                srv.submit(_prompts(mcfg, 1, seed=1)[0],
                           _greedy(6)).result(timeout=120)
            finally:
                jax.profiler.stop_trace()
            ring = _by_id(trace.events())
        finally:
            srv.shutdown()
        path = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
        host = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("pt:"):
                        stats = dict(ev.stats)
                        host[stats["id"]] = (ev, stats)
        assert len(ring) >= 10
        for sid, e in ring.items():
            assert sid in host, e
            ev, stats = host[sid]
            assert ev.name == "pt:" + e["phase"]
            assert stats["parent"] == e["span.parent"]
            assert abs(ev.duration_ns - e["dur_ns"]) < 1e6
            if e["rid"] is not None:
                assert stats["rid"] == e["rid"]
            if not e["span.parent"]:
                assert stats["pid"] == os.getpid()
        # the profiler's clock orders the spans as the ring's does
        steps = sorted((e for e in ring.values() if e["phase"] == "step"),
                       key=lambda e: e["ts_ns"])
        on_host = [host[e["span.id"]][0].start_ns for e in steps]
        assert on_host == sorted(on_host) and len(steps) >= 2


class TestServingTree:
    def test_step_gap_admit_children_nest(self, tr):
        model, mcfg = tiny_model()
        srv = Server(paged_engine(model), segment_steps=4)
        try:
            h = srv.submit(_prompts(mcfg, 1)[0], _greedy(9))
            h.result(timeout=120)
        finally:
            srv.shutdown()
        evs = trace.events()
        spans = _by_id(evs)
        admit = next(e for e in evs if e["phase"] == "admit")
        assert _chain(admit, spans) == ["admit", "gap", "step"]
        kids = [e for e in sorted(spans.values(), key=lambda e: e["ts_ns"])
                if e["span.parent"] == admit["span.id"]]
        assert tuple(e["phase"] for e in kids) == ENGINE_CHILDREN
        assert sum(e["dur_ns"] for e in kids) <= admit["dur_ns"]
        pre = kids[1]
        assert (pre["plen"], pre["bucket"], pre["cached"],
                pre["fused"]) == (6, 16, 0, 1)
        # the instants of an admission hang under the iteration too
        deq = next(e for e in evs if e["phase"] == "queue.dequeue")
        assert _chain(deq, spans)[1:] == ["gap", "step"]
        # every iteration: gap, then segment > engine.segment, then
        # collect, all children of ONE step
        for st in (e for e in spans.values() if e["phase"] == "step"):
            seq = [e["phase"] for e in
                   sorted(spans.values(), key=lambda e: e["ts_ns"])
                   if e["span.parent"] == st["span.id"]]
            assert seq[0] == "gap"
            if "segment" in seq:
                assert seq[seq.index("segment") + 1] == "collect"
        segs = [e for e in spans.values() if e["phase"] == "engine.segment"]
        assert segs and all(
            spans[e["span.parent"]]["phase"] == "segment" for e in segs)
        # the idle wait has no span: nothing but step is a root here
        roots = {e["phase"] for e in spans.values()
                 if not e["span.parent"]}
        assert roots == {"step"}

    def test_segment_counters_match_known_lengths(self, tr):
        """``ctx_tokens`` is the sum over live rows of prompt + tokens
        generated before the segment; one request of 6 + 9 behind the
        server, then two rows of different lengths on the engine."""
        model, mcfg = tiny_model()
        srv = Server(paged_engine(model), segment_steps=4)
        try:
            srv.submit(_prompts(mcfg, 1)[0], _greedy(9)).result(
                timeout=120)
        finally:
            srv.shutdown()
        segs = sorted((e for e in trace.events()
                       if e["phase"] == "engine.segment"),
                      key=lambda e: e["ts_ns"])
        # admission samples token 1; two segments of 4 make the other 8
        assert [(e["rows"], e["ctx_tokens"], e["steps"], e["emitted"])
                for e in segs] == [(1, 7, 4, 4), (1, 11, 4, 4)]
        trace.clear()
        eng = paged_engine(model)
        try:
            a, b = (_prompts(mcfg, 1, plen=6)[0],
                    _prompts(mcfg, 1, plen=9, seed=1)[0])
            eng.add_request(a, _greedy(12))
            eng.decode_segment(4)                  # a: 6 + 1
            eng.add_request(b, _greedy(3))
            eng.decode_segment(4)                  # a: 6 + 5, b: 9 + 1
            eng.decode_segment(4)                  # b retired at 3
        finally:
            eng.close()
        segs = [e for e in trace.events()
                if e["phase"] == "engine.segment"]
        assert [(e["rows"], e["ctx_tokens"], e["emitted"])
                for e in segs] == [(1, 7, 4), (2, 21, 6), (1, 15, 3)]
        # driven without a scheduler the engine's spans are roots
        assert all(e["span.parent"] == 0 for e in segs)

    def test_segment_page_counters(self, tr):
        """``pages_live``: the pages the live rows' contexts span at the
        segment's start (what ``paged_decode`` walks); ``pages_table``:
        every entry of the table (what the grid kernel it replaced
        walked). Pages of 4 tokens, a table of 4 rows x 8."""
        model, mcfg = tiny_model()
        eng = paged_engine(model)
        try:
            eng.add_request(_prompts(mcfg, 1, plen=6)[0], _greedy(12))
            eng.decode_segment(4)                  # 6 + 1 = 7: 2 pages
            eng.add_request(_prompts(mcfg, 1, plen=9, seed=1)[0],
                            _greedy(3))
            eng.decode_segment(4)                  # 11 and 10: 3 + 3
            eng.decode_segment(4)                  # 15: 4; b retired
        finally:
            eng.close()
        segs = [e for e in trace.events()
                if e["phase"] == "engine.segment"]
        assert [(e["pages_live"], e["pages_table"]) for e in segs] == [
            (2, 32), (6, 32), (4, 32)]

    def test_segment_kv_pages_copied(self, tr):
        """``kv_pages_copied``: the pages the first step's ``paged_decode``
        copies, whole compute blocks a live row. Pages of 64 tokens over
        4 KV heads make a block of 4 pages (256 tokens)."""
        from paddle_tpu.ops.paged_attention import _pages_per_block

        model, mcfg = tiny_model()
        assert _pages_per_block(64, mcfg.num_key_value_heads) == 4
        eng = paged_engine(model, num_pages=32, page_size=64, max_pages=8)
        try:
            eng.add_request(_prompts(mcfg, 1, plen=300)[0], _greedy(12))
            eng.decode_segment(4)           # 301: 5 pages, 2 blocks
            eng.add_request(_prompts(mcfg, 1, plen=9, seed=1)[0],
                            _greedy(3))
            eng.decode_segment(4)           # 305 and 10: 2 + 1 blocks
            eng.decode_segment(4)           # b retired
        finally:
            eng.close()
        segs = [e for e in trace.events()
                if e["phase"] == "engine.segment"]
        assert [(e["pages_live"], e["kv_pages_copied"]) for e in segs] == [
            (5, 8), (6, 12), (5, 8)]

    def test_chunked_and_warm_prefill_spans(self, tr):
        """A chunked admission's programs are children of its
        ``prefill_chunk`` spans (the claim and the mini under
        ``admit.begin``); a prefix-cache hit's tail program reports a
        numeric bucket and what it did not compute."""
        model, mcfg = tiny_model()
        eng = paged_engine(model, num_pages=64, max_pages=16,
                           prefill_chunk=8)
        srv = Server(eng, segment_steps=4)
        try:
            srv.submit(_prompts(mcfg, 1, plen=20)[0],
                       _greedy(6)).result(timeout=120)
        finally:
            srv.shutdown()
        evs = trace.events()
        spans = _by_id(evs)
        pres = sorted((e for e in evs if e["phase"] == "engine.prefill"),
                      key=lambda e: e["ts_ns"])
        assert [(e["plen"], e["bucket"], e["cached"], e["fused"])
                for e in pres] == [
            (8, 8, 0, 0), (16, 8, 8, 0), (20, 8, 16, 0)]
        assert all(_chain(e, spans)[:4] == [
            "engine.prefill", "prefill_chunk", "gap", "step"]
            for e in pres)
        begin = next(e for e in evs if e["phase"] == "admit.begin")
        assert [e["phase"] for e in
                sorted(spans.values(), key=lambda e: e["ts_ns"])
                if e["span.parent"] == begin["span.id"]] == [
            "engine.reserve", "engine.mini_cache"]
        last_chunk = spans[pres[-1]["span.parent"]]
        assert [e["phase"] for e in
                sorted(spans.values(), key=lambda e: e["ts_ns"])
                if e["span.parent"] == last_chunk["span.id"]] == [
            "engine.prefill", "engine.install", "engine.first_token"]

        trace.clear()
        eng = paged_engine(model, num_pages=64, max_pages=16,
                           prefix_cache=True)
        try:
            p = _prompts(mcfg, 1, plen=20)[0]
            eng.add_request(p, _greedy(2))
            eng.add_request(p, _greedy(2))         # the same prompt: warm
        finally:
            eng.close()
        cold, warm = [e for e in trace.events()
                      if e["phase"] == "engine.prefill"]
        assert (cold["plen"], cold["bucket"], cold["cached"],
                cold["fused"]) == (20, 32, 0, 1)
        # a model whose layout names no row block runs the bucket's rows
        assert cold["rows_run"] == 32 and warm["rows_run"] == warm["bucket"]
        assert all(e["rows_run"] == e["bucket"] for e in pres)
        assert warm["plen"] == 20 and warm["cached"] > 0
        assert warm["fused"] == 0
        # the warm hit keeps its separate mini cache and install
        phases = [e["phase"] for e in trace.events()
                  if e["phase"].startswith("engine.")]
        # (the ring keeps a span when it ends: children before parent)
        assert phases == [
            "engine.reserve", "engine.prefill",
            "engine.dispatch", "engine.wait", "engine.first_token",
            "engine.mini_cache", "engine.prefill", "engine.reserve",
            "engine.install",
            "engine.dispatch", "engine.wait", "engine.first_token"]
        assert isinstance(warm["bucket"], int)
        assert warm["plen"] - warm["cached"] <= warm["bucket"]


# the host's three phases of a decode segment (ISSUE 36); an
# admission's ``engine.first_token`` holds the first two
SEGMENT_CHILDREN = ("engine.dispatch", "engine.wait", "engine.collect")
NEW_SITES = SEGMENT_CHILDREN + ("engine.tables",)
# behind a server (ISSUE 37) what the handles are owed is handed over
# between the dispatch and the wait
SERVED_SEGMENT_CHILDREN = ("engine.dispatch", "push", "engine.wait",
                           "engine.collect")


def _children(parent, spans):
    return sorted((e for e in spans.values()
                   if e["span.parent"] == parent["span.id"]),
                  key=lambda e: e["ts_ns"])


def _assert_three_children(seg, spans, want=SEGMENT_CHILDREN):
    """Exactly dispatch, wait, collect (behind a server, the push
    between the first two): in that order, disjoint, inside the
    parent."""
    kids = _children(seg, spans)
    assert tuple(e["phase"] for e in kids) == want
    t = seg["ts_ns"]
    for e in kids:
        assert e["ts_ns"] >= t
        t = e["ts_ns"] + e["dur_ns"]
    assert t <= seg["ts_ns"] + seg["dur_ns"]


def _tables_of(evs):
    return [e for e in sorted(evs, key=lambda e: e["ts_ns"])
            if e["phase"] == "engine.tables"]


class TestSegmentCycle:
    """ISSUE 36: the host's cycle between two decode segments, a span a
    piece, and the counters that size ROADMAP S12's leads."""

    @pytest.mark.parametrize("behind_server", [True, False])
    def test_segment_children_and_tables(self, tr, behind_server):
        model, mcfg = tiny_model()
        if behind_server:
            srv = Server(paged_engine(model), segment_steps=4)
            try:
                srv.submit(_prompts(mcfg, 1)[0], _greedy(9)).result(
                    timeout=120)
            finally:
                srv.shutdown()
        else:
            eng = paged_engine(model)
            try:
                eng.add_request(_prompts(mcfg, 1)[0], _greedy(9))
                eng.decode_segment(4)
                eng.decode_segment(4)
            finally:
                eng.close()
        evs = trace.events()
        spans = _by_id(evs)
        segs = [e for e in _by_start(spans)
                if e["phase"] == "engine.segment"]
        tabs = _tables_of(evs)
        assert len(segs) == 2 and len(tabs) == 2
        for seg, tab in zip(segs, tabs):
            _assert_three_children(seg, spans, SERVED_SEGMENT_CHILDREN
                                   if behind_server else SEGMENT_CHILDREN)
            # the upload is a sibling that ends before the segment opens
            assert tab["span.parent"] == seg["span.parent"]
            assert tab["ts_ns"] + tab["dur_ns"] <= seg["ts_ns"]
            parent = spans.get(tab["span.parent"])
            assert (parent["phase"] == "segment" if behind_server
                    else parent is None)
            assert _children(tab, spans) == []
            assert tab["changed"] in (0, 1)
        # the parent keeps what its readers read; of the children only
        # the dispatch carries a counter
        for seg in segs:
            d, w, c = (e for e in _children(seg, spans)
                       if e["phase"] != "push")
            assert seg["emitted"] == 4 and "args" not in seg
            assert {"steps", "rows", "ctx_tokens", "pages_live"} <= set(seg)
            assert d["args"] > 0
            assert set(w) - {"phase"} == set(c) - {"phase"}
            assert set(d) - set(w) == {"args"}

    def test_first_token_children(self, tr):
        model, mcfg = tiny_model()
        eng = paged_engine(model)
        try:
            eng.add_request(_prompts(mcfg, 1)[0], _greedy(2))
        finally:
            eng.close()
        spans = _by_id(trace.events())
        first = next(e for e in spans.values()
                     if e["phase"] == "engine.first_token")
        kids = _children(first, spans)
        assert [e["phase"] for e in kids] == ["engine.dispatch",
                                              "engine.wait"]
        assert kids[0]["ts_ns"] + kids[0]["dur_ns"] <= kids[1]["ts_ns"]
        assert (kids[1]["ts_ns"] + kids[1]["dur_ns"]
                <= first["ts_ns"] + first["dur_ns"])
        # ``args`` belongs to the segment's dispatch alone
        assert all("args" not in e for e in kids)

    def test_changed_reserved_mode(self, tr):
        """``changed``: the device does not hold this table. An
        admission uploads its own, so the segment after one reads 0; a
        retirement takes the row's pages out of the host's table alone,
        so the segment after one reads 1; then nothing."""
        model, mcfg = tiny_model()
        eng = paged_engine(model)
        try:
            eng.add_request(_prompts(mcfg, 1)[0], _greedy(24))
            eng.decode_segment(4)
            eng.decode_segment(4)
            eng.add_request(_prompts(mcfg, 1, plen=9, seed=1)[0],
                            _greedy(3))
            eng.decode_segment(4)                  # b retires inside it
            eng.decode_segment(4)
            eng.decode_segment(4)
        finally:
            eng.close()
        assert [e["changed"] for e in _tables_of(trace.events())] == [
            0, 0, 0, 1, 0]

    def test_changed_optimistic_mode(self, tr):
        """Pages of 4: a prompt of 6 claims 3 pages (6 + one page), so
        segments of 2 steps stay inside them up to position 12 and the
        next crosses a page edge: the growth re-check claims a page the
        device has not seen."""
        model, mcfg = tiny_model()
        eng = paged_engine(model, admission_mode="optimistic")
        try:
            eng.add_request(_prompts(mcfg, 1)[0], _greedy(14))
            for _ in range(4):
                eng.decode_segment(2)
        finally:
            eng.close()
        assert [e["changed"] for e in _tables_of(trace.events())] == [
            0, 0, 0, 1]

    def test_changed_is_unknown_after_tracing_was_off(self, tr):
        """What was sent is kept only while tracing is on: an upload
        with tracing off drops it, so the first traced segment reads 1
        (the tracer cannot say the device holds this table) and the
        next 0; a fresh state is an upload like any other."""
        trace.disable()
        model, mcfg = tiny_model()
        eng = paged_engine(model)
        try:
            eng.add_request(_prompts(mcfg, 1)[0], _greedy(24))
            eng.decode_segment(4)
            assert eng._tables_sent is None
            trace.enable()
            eng.decode_segment(4)
            eng.decode_segment(4)
            eng.reset_state()
            eng.add_request(_prompts(mcfg, 1)[0], _greedy(24))
            eng.decode_segment(4)
        finally:
            eng.close()
        assert [e["changed"] for e in _tables_of(trace.events())] == [
            1, 0, 0]

    def test_args_is_the_leaf_count_of_the_call(self, tr):
        import jax

        model, mcfg = tiny_model()
        eng = paged_engine(model)
        seen = []
        try:
            fn = eng._segment_fn(4)

            def counting(*args):
                seen.append(len(jax.tree_util.tree_leaves(args)))
                return fn(*args)

            eng._segment_cache[4] = counting
            eng.add_request(_prompts(mcfg, 1)[0], _greedy(9))
            eng.decode_segment(4)
            eng.decode_segment(4)
        finally:
            eng.close()
        spans = _by_id(trace.events())
        got = [e["args"] for e in _by_start(spans)
               if e["phase"] == "engine.dispatch" and "args" in e]
        assert got == seen and len(seen) == 2
        # parameters, pools and page table, the per-slot vectors, the
        # live mask, seed and counter: every one an array leaf
        n_params = len(jax.tree_util.tree_leaves(eng.params))
        assert seen[0] > n_params + 2 + len(eng.samp)

    @pytest.mark.parametrize("budgets, want", [
        ((9,), [1, 1]), ((9, 5), [2, 1])])
    def test_collect_counts_pushed(self, tr, budgets, want):
        """Requests queued before the loop starts are admitted in one
        gap (each admission pushes its first token itself); segments of
        4: every live handle gets a delta after a segment, its last
        one included."""
        model, mcfg = tiny_model()
        srv = Server(paged_engine(model), segment_steps=4, start=False)
        try:
            hs = [srv.submit(p, _greedy(n)) for p, n in
                  zip(_prompts(mcfg, len(budgets)), budgets)]
            srv._thread.start()
            for h in hs:
                h.result(timeout=120)
        finally:
            srv.shutdown()
        got = [e["pushed"] for e in _by_start(_by_id(trace.events()))
               if e["phase"] == "collect"]
        assert got == want

    def test_off_records_nothing_and_builds_no_span(self, monkeypatch):
        """With tracing off every new site gets the shared null span and
        the ring stays empty: through an admission, two segments (plain
        and device-speculative) and a server's collection."""
        trace.disable()
        trace.clear()
        sites = []
        real = trace.span

        def spying(phase, *a, **kw):
            out = real(phase, *a, **kw)
            sites.append((phase, out))
            return out

        monkeypatch.setattr(trace, "span", spying)
        model, mcfg = tiny_model()
        eng = paged_engine(model)
        try:
            eng.add_request(_prompts(mcfg, 1)[0], _greedy(6))
            eng.decode_segment(4)
            assert eng._tables_sent is None
        finally:
            eng.close()
        spec = paged_engine(model, draft_k=2, spec_mode="device")
        try:
            spec.add_request(_prompts(mcfg, 1)[0], GenerationConfig(
                max_new_tokens=6, eos_token_id=None, speculative=True))
            spec.decode_segment(4)
        finally:
            spec.close()
        srv = Server(paged_engine(model), segment_steps=4)
        try:
            srv.submit(_prompts(mcfg, 1)[0], _greedy(6)).result(
                timeout=120)
        finally:
            srv.shutdown()
        assert trace.events() == []
        assert {p for p, _ in sites} >= set(NEW_SITES) | {"collect"}
        assert all(out is trace.NULL_SPAN for _, out in sites)

    def test_spec_device_segment_has_the_three_children(self, tr):
        model, mcfg = tiny_model()
        eng = paged_engine(model, draft_k=2, spec_mode="device")
        try:
            eng.add_request(_prompts(mcfg, 1)[0], GenerationConfig(
                max_new_tokens=9, eos_token_id=None, speculative=True))
            eng.decode_segment(4)
        finally:
            eng.close()
        spans = _by_id(trace.events())
        seg = next(e for e in spans.values()
                   if e["phase"] == "engine.spec_segment")
        assert seg["mode"] == "device"
        _assert_three_children(seg, spans)
        d, w, c = _children(seg, spans)
        assert d["args"] > 0 and "args" not in seg
        assert len(_tables_of(spans.values())) == 1


class TestPushSpan:
    """ISSUE 37: the scheduler's hand-over of what the handles are owed
    is one ``push`` span (``handles``, ``after_dispatch``), after the
    next segment's dispatch where one follows."""

    def test_first_token_owed_across_an_admission(self, tr):
        """Two requests admitted in one gap: the first one's first token
        is handed over once the second admission's programs are on the
        device, not after that admission's wait."""
        spans, _ = self._served((5, 5))
        firsts = [e for e in _by_start(spans)
                  if e["phase"] == "engine.first_token"]
        assert len(firsts) == 2
        push = [e for e in _children(firsts[1], spans)
                if e["phase"] == "push"]
        assert [(e["after_dispatch"], e["handles"]) for e in push] == [
            (1, 1)]
        assert self._instants_under(push[0]) == ["first_token"]
        assert not [e for e in _children(firsts[0], spans)
                    if e["phase"] == "push"]

    def _served(self, budgets, steps=4, max_batch=4):
        model, mcfg = tiny_model()
        srv = Server(paged_engine(model, max_batch=max_batch),
                     segment_steps=steps, start=False)
        calls = []
        ds = srv.engine.decode_segment
        srv.engine.decode_segment = \
            lambda *a, **kw: (calls.append(1), ds(*a, **kw))[1]
        try:
            hs = [srv.submit(p, _greedy(n)) for p, n in
                  zip(_prompts(mcfg, len(budgets)), budgets)]
            srv._thread.start()
            for h in hs:
                h.result(timeout=120)
            n_seg = len(calls)
            assert srv.drain(timeout=60)
            assert len(calls) == n_seg
        finally:
            srv.shutdown()
        return _by_id(trace.events()), n_seg

    @staticmethod
    def _instants_under(span):
        return [e["phase"] for e in trace.events()
                if e["span.parent"] == span["span.id"]
                and not e["span.id"]]

    def test_push_lies_between_dispatch_and_wait(self, tr):
        spans, _ = self._served((9, 5, 13, 2))
        pushes = [e for e in _by_start(spans) if e["phase"] == "push"]
        after = [e for e in pushes if e["after_dispatch"] == 1]
        assert after and all(e["handles"] >= 1 for e in pushes)
        for p in after:
            # a segment's dispatch, or an admission's
            seg = spans[p["span.parent"]]
            assert seg["phase"] in ("engine.segment", "engine.first_token")
            d, w = (next(e for e in _children(seg, spans)
                         if e["phase"] == name)
                    for name in ("engine.dispatch", "engine.wait"))
            assert d["ts_ns"] + d["dur_ns"] <= p["ts_ns"]
            assert p["ts_ns"] + p["dur_ns"] <= w["ts_ns"] + w["dur_ns"]
            # the handles' own events hang under the push
            assert set(self._instants_under(p)) <= {"first_token",
                                                    "finish"}
        # a segment of live rows always hands something over
        for seg in (e for e in spans.values()
                    if e["phase"] == "engine.segment"):
            assert [e["phase"] for e in _children(seg, spans)].count(
                "push") == 1

    def test_last_finish_at_the_idle_flush(self, tr):
        """One request of 1 + 2 x 4 tokens: two segments; the first
        token and the first segment's tokens go after a dispatch, the
        last segment's tokens and the finish at the idle flush, which
        needs no segment after it."""
        spans, n_seg = self._served((9,))
        assert n_seg == 2
        pushes = [e for e in _by_start(spans) if e["phase"] == "push"]
        assert [(e["after_dispatch"], e["handles"]) for e in pushes] == [
            (1, 1), (1, 1), (0, 1)]
        last = pushes[-1]
        assert spans[last["span.parent"]]["phase"] == "step"
        assert self._instants_under(last) == ["finish"]
        assert self._instants_under(pushes[0]) == ["first_token"]

    @pytest.mark.parametrize("mode", ["plain", "device", "host"])
    def test_engine_calls_on_dispatch_once(self, tr, mode):
        """``decode_segment(on_dispatch=)``: called once a segment,
        after the program was handed over and before the wait; never
        without a live slot."""
        model, mcfg = tiny_model()
        kw = {} if mode == "plain" else {"draft_k": 2, "spec_mode": mode}
        eng = paged_engine(model, **kw)
        seen = []
        try:
            eng.add_request(_prompts(mcfg, 1)[0], GenerationConfig(
                max_new_tokens=6, eos_token_id=None,
                speculative=mode != "plain"))
            while eng.decode_segment(
                    4, on_dispatch=lambda: (seen.append(1),
                                            trace.event("cb"))):
                pass
            n = len(seen)
            assert n >= 1
            assert eng.decode_segment(4, on_dispatch=seen.append) == 0
            assert len(seen) == n
            # an admission calls it once, before its one pull
            eng.add_request(_prompts(mcfg, 1)[0], _greedy(2),
                            on_dispatch=lambda: seen.append(trace.event(
                                "cb.admit")))
            assert len(seen) == n + 1
        finally:
            eng.close()
        spans = _by_id(trace.events())
        cbs = [e for e in trace.events() if e["phase"] == "cb"]
        assert len(cbs) == n
        adm = next(e for e in trace.events() if e["phase"] == "cb.admit")
        first = spans[adm["span.parent"]]
        assert first["phase"] == "engine.first_token"
        d, w = _children(first, spans)
        assert d["ts_ns"] + d["dur_ns"] <= adm["ts_ns"] <= w["ts_ns"]
        if mode == "host":
            return              # host speculation keeps its span alone
        for cb in cbs:
            seg = spans[cb["span.parent"]]
            d, w, _c = _children(seg, spans)
            assert d["ts_ns"] + d["dur_ns"] <= cb["ts_ns"] <= w["ts_ns"]


def _by_start(spans):
    return sorted(spans.values(), key=lambda e: e["ts_ns"])


def _tools():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    os.pardir, "tools"))
    try:
        import monitor_report
        import serve_bench
    finally:
        sys.path.pop(0)
    return serve_bench, monitor_report


class TestToolViews:
    def test_ttft_decomposition_sums_to_ttft(self, tr):
        """The serve_bench decomposition's three shares sum to the
        server-side TTFT per request (synthetic events with known
        spacing)."""
        serve_bench, _ = _tools()
        import time as _t

        t0 = _t.perf_counter_ns()
        with trace._lock:   # hand-build deterministic timestamps
            trace._ring.append((t0, 0, "s:1", "queue.enqueue", None,
                                0, 0))
            trace._ring.append((t0 + 10_000_000, 0, "s:1",
                                "queue.dequeue", None, 0, 0))
            trace._ring.append((t0 + 10_000_000, 30_000_000, "s:1",
                                "admit", None, 1, 0))
            trace._ring.append((t0 + 50_000_000, 0, "s:1",
                                "first_token", None, 0, 0))
        # a preempted request's REPLAY re-admission lands after the
        # first token (ring order is end-time order) and must NOT
        # inflate the prefill share
        with trace._lock:
            trace._ring.append((t0 + 90_000_000, 40_000_000, "s:1",
                                "admit", {"replay": True}, 2, 0))
        qs, ps, gs = serve_bench._ttft_decomposition()
        assert qs == [pytest.approx(0.010)]
        assert ps == [pytest.approx(0.030)]
        assert gs == [pytest.approx(0.010)]       # 50 - 10 - 30 ms

    def test_monitor_report_trace_view(self, tr, tmp_path):
        _, monitor_report = _tools()
        model, mcfg = tiny_model()
        eng = paged_engine(model)
        srv = Server(eng, segment_steps=4)
        hs = [srv.submit(p, _greedy(6)) for p in _prompts(mcfg, 2)]
        for h in hs:
            h.result(timeout=120)
        srv.shutdown()
        p = trace.export_chrome(str(tmp_path / "run.json"))
        out = monitor_report.render_trace(json.load(open(p)), top=2)
        assert "PHASE" in out and "admit" in out and "segment" in out
        assert "top 2 slowest requests" in out
        assert "dominant:" in out
        # the CLI route works end to end
        assert monitor_report.main(["--trace", p, "--top", "1"]) == 0
