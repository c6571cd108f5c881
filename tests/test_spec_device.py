"""Device-resident speculative decoding (ISSUE 18).

The tentpole contract, CPU-verified:

- DEVICE DRAFTS, SAME TOKENS: ``spec_mode="device"`` moves the n-gram
  proposer onto the chip (`propose_device`, the fixed-shape twin of
  ``NgramIndex.propose``) and fuses propose→verify→accept→KV-write for
  a whole segment into ONE compiled ``lax.scan`` program — emitted
  tokens stay bitwise identical to host-mode spec AND to plain greedy
  decode, because acceptance only ever decides HOW MANY of the model's
  own picks ship, never WHICH;
- ZERO PER-STEP HOST SYNCS: the fused segment reads back once per
  segment like the plain path — ``spec_stats()["host_syncs"]`` is
  structurally 0 in device mode (host mode counts one per verify
  forward), and the ledger shows ONE ``cb_spec_device_segment``
  program with dispatches == segments, not steps;
- FULL-MATRIX COMPOSITION: MHA+GQA × int8 KV × LoRA mix
  × TP, prefix warm hits with CoW, optimistic-admission preemption and
  engine-restart replay (the history ring rebuilds from
  prompt+generated exactly like the host proposer), all under
  ``debug_pages=True`` and leak-free;
- ZERO POST-WARMUP COMPILES: Server warmup pre-compiles the fused
  program keyed on ``(n_steps, draft_k, spec_draft)`` alone.
"""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import monitor
from engine_helpers import paged_engine
from paddle_tpu.inference.generation import (GenerationConfig,
                                             PagedContinuousBatchingEngine)
from paddle_tpu.inference.ngram import NgramIndex, propose_device
from paddle_tpu.models import LlamaForCausalLM, llama_config
from paddle_tpu.monitor import ledger
from paddle_tpu.serving import Server


def tiny_model(layers=2, kv_heads=None, seed=0):
    paddle.seed(seed)
    cfg = llama_config("tiny", num_hidden_layers=layers,
                       num_key_value_heads=kv_heads)
    return LlamaForCausalLM(cfg), cfg


def make_adapter(model, seed, targets=("q", "v"), rank=2, scale=0.6):
    _, shapes = model.lora_shapes(targets)
    rng = np.random.default_rng(seed)
    return {t: (rng.standard_normal((rank, d_in)).astype(np.float32)
                * scale,
                rng.standard_normal((d_out, rank)).astype(np.float32)
                * scale)
            for t, (d_in, d_out) in shapes.items()}


@pytest.fixture()
def mon():
    monitor.enable()
    monitor.reset()
    yield monitor
    monitor.reset()
    monitor.disable()


@pytest.fixture()
def led():
    monitor.enable()
    monitor.reset()
    ledger.reset()
    ledger.enable()
    yield ledger
    ledger.disable()
    ledger.reset()
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


class TestProposeDeviceUnit:
    """propose_device is the EXACT windowed twin of NgramIndex.propose
    — same longest-suffix-first / most-recent-tie / pad-with-tail
    semantics, as a fixed-shape jax computation."""

    def test_recent_continuation_and_miss(self):
        H = 16
        rows = np.zeros((2, H), np.int32)
        ctx = [1, 2, 3, 9, 1, 2, 3]
        rows[0, :len(ctx)] = ctx            # suffix [1,2,3] seen at 0
        rows[1, :3] = [4, 5, 6]             # total miss -> tail token
        out = np.asarray(propose_device(
            rows, np.array([len(ctx), 3], np.int32), 3, 3))
        assert out[0].tolist() == NgramIndex(3).propose(ctx, 3)
        assert out[0, :2].tolist() == [9, 1]
        assert out[1].tolist() == [6, 6, 6]

    @pytest.mark.parametrize("k", [3, 6])
    def test_fuzz_matches_host_index_exact(self, k):
        """Every context that fits the window drafts IDENTICALLY to
        the host proposer — small vocab forces real n-gram collisions,
        lengths sweep the window edges."""
        H, n_max, cases = 64, 3, 48
        rng = np.random.RandomState(7 + k)
        ctxs, rows, lens = [], np.zeros((cases, H), np.int32), []
        for i in range(cases):
            L = int(rng.randint(2, H + 1))
            ctx = rng.randint(0, 6, (L,)).astype(np.int32)
            ctxs.append([int(t) for t in ctx])
            rows[i, :L] = ctx
            lens.append(L)
        out = np.asarray(propose_device(
            rows, np.asarray(lens, np.int32), k, n_max))
        for i, ctx in enumerate(ctxs):
            want = NgramIndex(n_max).propose(ctx, k)
            assert out[i].tolist() == want, (i, ctx)

    def test_fixed_shape_output(self):
        out = propose_device(np.zeros((3, 8), np.int32),
                             np.array([2, 5, 8], np.int32), 4, 2)
        assert out.shape == (3, 4) and out.dtype == np.int32


class TestKnobs:
    def test_engine_validation(self):
        model, _ = tiny_model(layers=1)
        kw = dict(max_batch=1, max_len=64, draft_k=4)
        with pytest.raises(ValueError, match="spec_mode"):
            paged_engine(model, spec_mode="gpu", **kw)
        with pytest.raises(ValueError, match="spec_draft"):
            paged_engine(model, spec_draft="eagle", **kw)
        for bad in (7, True, 2.5, "128"):
            with pytest.raises(ValueError, match="spec_history"):
                paged_engine(model, spec_history=bad, **kw)
        eng = paged_engine(model, spec_mode="device", **kw)
        assert eng.spec_mode == "device"
        assert eng.spec_draft == "ngram" and eng.spec_history == 128

    def test_paged_passthrough(self):
        model, _ = tiny_model(layers=1)
        eng = PagedContinuousBatchingEngine(
            model, max_batch=1, num_pages=8, page_size=8, max_pages=4,
            draft_k=3, spec_mode="device", spec_draft="self",
            spec_history=64)
        assert (eng.spec_mode, eng.spec_draft, eng.spec_history) == \
            ("device", "self", 64)

    def test_server_mirror_knob(self):
        model, _ = tiny_model(layers=1)
        eng = paged_engine(model, max_batch=1, max_len=64, draft_k=3)
        with pytest.raises(ValueError, match="spec_mode"):
            Server(eng, start=False, spec_mode="turbo")
        assert eng.spec_mode == "host"       # rejected before mutation
        srv = Server(eng, start=False, spec_mode="device")
        assert eng.spec_mode == "device"
        srv.shutdown(drain=False)


class TestBitwiseParity:
    """Device-mode emitted tokens == host-mode == plain decode, per
    slot, across head layouts."""

    @pytest.mark.parametrize("kv_heads", [None, 2],
                             ids=["mha", "gqa"])
    def test_paged_device_vs_plain(self, kv_heads):
        model, _ = tiny_model(kv_heads=kv_heads)
        kw = dict(max_batch=2, num_pages=24, page_size=8, max_pages=16,
                  debug_pages=True)
        ref = _run(PagedContinuousBatchingEngine(model, **kw),
                   [REP, RND], [_greedy(24), _greedy(24)])
        host = _run(PagedContinuousBatchingEngine(model, draft_k=6, **kw),
                    [REP, RND], [_spec(24), _spec(24)])
        eng = PagedContinuousBatchingEngine(
            model, draft_k=6, spec_mode="device", **kw)
        out = _run(eng, [REP, RND], [_spec(24), _spec(24)])
        for a, b, c in zip(ref, host, out):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)
        st = eng.spec_stats()
        assert st["accepted"] > 0           # drafts did real work
        assert st["emitted"] == st["slot_steps"] + st["accepted"]
        assert st["host_syncs"] == 0
        assert eng.alloc.free_pages == eng.num_pages

    def test_self_draft_parity(self):
        """spec_draft="self" (verify-window logits as next drafts)
        changes the draft SOURCE only — greedy parity is structural."""
        model, _ = tiny_model()
        ref = _run(paged_engine(model, max_batch=2, max_len=128),
                   [REP, RND], [_greedy(20), _greedy(20)])
        eng = paged_engine(
            model, max_batch=2, max_len=128, draft_k=4,
            spec_mode="device", spec_draft="self")
        out = _run(eng, [REP, RND], [_spec(20), _spec(20)])
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)
        st = eng.spec_stats()
        assert st["emitted"] == st["slot_steps"] + st["accepted"]

    def test_budget_smaller_than_draft_window(self):
        model, _ = tiny_model()
        ref = _run(paged_engine(model, max_batch=1, max_len=128),
                   [REP], [_greedy(3)])
        eng = paged_engine(
            model, max_batch=1, max_len=128, draft_k=6,
            spec_mode="device")
        out = _run(eng, [REP], [_spec(3)])
        np.testing.assert_array_equal(ref[0], out[0])
        assert len(out[0]) == 3

    def test_near_max_len_stops_clean(self):
        model, _ = tiny_model()
        ref = _run(paged_engine(model, max_batch=1, max_len=32),
                   [REP], [_greedy(8)])
        eng = paged_engine(
            model, max_batch=1, max_len=32, draft_k=6,
            spec_mode="device")
        out = _run(eng, [REP], [_spec(8)])
        np.testing.assert_array_equal(ref[0], out[0])

    def test_eos_mid_accepted_draft_truncates(self):
        """eos landing inside an accepted window truncates ON DEVICE
        (the fused program's per-step mask) — bitwise vs plain."""
        model, _ = tiny_model()
        probe = paged_engine(model, max_batch=1, max_len=128)
        free = _run(probe, [REP], [_greedy(24)])[0]
        eos = int(free[7])
        kw = dict(max_new_tokens=24, eos_token_id=eos)
        ref = _run(paged_engine(model, max_batch=1, max_len=128),
                   [REP], [GenerationConfig(**kw)])[0]
        eng = paged_engine(
            model, max_batch=1, max_len=128, draft_k=6,
            spec_mode="device")
        out = _run(eng, [REP],
                   [GenerationConfig(speculative=True, **kw)])[0]
        np.testing.assert_array_equal(ref, out)
        assert out[-1] == eos and len(out) < 24
        # the slot retired cleanly — engine is idle and reusable
        assert eng.free_slots() == 1
        out2 = _run(eng, [RND], [_spec(6)])[0]
        assert len(out2) == 6

    def test_int8_kv_parity(self):
        """Quantized paged KV: device-mode spec matches the SAME
        engine config decoded plain (int8 changes numerics vs bf16,
        never spec-vs-plain agreement)."""
        model, _ = tiny_model()
        kw = dict(max_batch=2, num_pages=24, page_size=8, max_pages=16,
                  kv_dtype="int8", debug_pages=True)
        ref = _run(PagedContinuousBatchingEngine(model, **kw),
                   [REP, RND], [_greedy(20), _greedy(20)])
        eng = PagedContinuousBatchingEngine(
            model, draft_k=6, spec_mode="device", **kw)
        out = _run(eng, [REP, RND], [_spec(20), _spec(20)])
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)
        assert eng.alloc.free_pages == eng.num_pages

    def test_lora_mix_parity(self):
        """A base + adapter mix in one device-mode batch: per-slot
        adapter vectors ride the fused program unchanged."""
        model, _ = tiny_model()
        kw = dict(max_batch=2, num_pages=32, page_size=8, max_pages=8,
                  lora_capacity=2, lora_rank=4, lora_targets=("q", "v"),
                  debug_pages=True)
        params = make_adapter(model, 11)
        ref_eng = PagedContinuousBatchingEngine(model, **kw)
        ref_eng.load_adapter("a1", params)
        ref = _run(ref_eng, [REP, REP],
                   [_greedy(12, adapter="a1"), _greedy(12)])
        eng = PagedContinuousBatchingEngine(
            model, draft_k=4, spec_mode="device", **kw)
        eng.load_adapter("a1", params)
        out = _run(eng, [REP, REP],
                   [_spec(12, adapter="a1"), _spec(12)])
        np.testing.assert_array_equal(ref[0], out[0])
        np.testing.assert_array_equal(ref[1], out[1])
        # the adapter actually changed the base row's trajectory
        assert list(ref[0]) != list(ref[1])


class TestComposition:
    """THE acceptance scenario: paged int8 KV + prefix warm hit + LoRA
    + optimistic admission with a pool sized to force preemption, all
    speculating in device mode under debug_pages — bitwise vs plain,
    leak-free (preempt-replay rebuilds the history ring from
    prompt+generated exactly like the host proposer)."""

    def test_full_matrix_pressure_bitwise(self):
        model, _ = tiny_model()
        kw = dict(kv_dtype="int8", lora_capacity=2, lora_rank=4,
                  lora_targets=("q", "v"), debug_pages=True)
        params = make_adapter(model, 11)
        big = PagedContinuousBatchingEngine(
            model, max_batch=2, num_pages=32, page_size=8,
            max_pages=16, **kw)
        big.load_adapter("a1", params)
        ref = _run(big, [REP, REP[:20]],
                   [_greedy(24, adapter="a1"), _greedy(24)])
        # 10 pages = 80 tokens for two requests needing (24+24)+(20+24)
        # worst case — optimistic admission with spec growth forces
        # preemption mid-decode
        eng = PagedContinuousBatchingEngine(
            model, max_batch=2, num_pages=10, page_size=8,
            max_pages=16, admission_mode="optimistic", draft_k=6,
            spec_mode="device", prefix_cache=True, **kw)
        eng.load_adapter("a1", params)
        srv = Server(eng, segment_steps=4, max_preemptions=10,
                     speculative=True, idle_wait_s=0.005)
        try:
            h1 = srv.submit(REP, _greedy(24, adapter="a1"))
            h2 = srv.submit(REP[:20], _greedy(24))
            np.testing.assert_array_equal(ref[0], h1.result(timeout=180))
            np.testing.assert_array_equal(ref[1], h2.result(timeout=180))
            assert eng.alloc.preemptions >= 1, \
                "pool was sized to force at least one preemption"
            # warm re-run of the first prompt hits the prefix cache
            # and still matches bitwise
            h3 = srv.submit(REP, _greedy(24, adapter="a1"))
            np.testing.assert_array_equal(ref[0], h3.result(timeout=180))
            assert eng.alloc.prefix_hits >= 1
            assert srv.drain(timeout=60)
        finally:
            srv.shutdown(drain=False)
        assert (eng.alloc.free_pages + eng.alloc.cached_pages
                == eng.num_pages)
        assert eng.spec_stats()["host_syncs"] == 0


class TestRestartReplay:
    """PR 4 composition: an engine-scoped fault mid-decode — replay
    re-prefills prompt + generated and re-seeds the device history
    ring from the full context, greedy parity holds."""

    def test_device_spec_through_restart_bitwise(self):
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
            draft_k=6, spec_mode="device", debug_pages=True)
        srv = Server(FaultyEngine(raw, plan), segment_steps=3,
                     restart_backoff_s=0.01, speculative=True)
        try:
            out = srv.submit(REP, _greedy(20)).result(timeout=180)
            np.testing.assert_array_equal(ref[0], out)
            assert srv.restarts == 1
            assert srv.drain(timeout=60)
        finally:
            srv.shutdown(drain=False)
        assert raw.free_slots() == raw.max_batch
        assert raw.alloc.free_pages == raw.num_pages


class TestZeroCompiles:
    def test_warmup_precompiles_fused_segment(self, mon):
        """Server warmup compiles the fused device-segment program;
        a real speculating request then pays ZERO further compiles of
        it — and zero host syncs."""
        model, _ = tiny_model()
        eng = PagedContinuousBatchingEngine(
            model, max_batch=2, num_pages=24, page_size=8, max_pages=8,
            spec_mode="device")
        srv = Server(eng, segment_steps=3, warmup=True, draft_k=4,
                     speculative=True)
        try:
            assert srv.wait_ready(120) and srv.status == "ok"
            pre = monitor.jit_miss_by_fn()
            assert pre.get("cb_spec_device_segment", 0) >= 1, pre
            out = srv.submit(REP, _greedy(12)).result(timeout=120)
            assert len(out) == 12
            post = monitor.jit_miss_by_fn()
            assert (post.get("cb_spec_device_segment")
                    == pre.get("cb_spec_device_segment")), (pre, post)
            st = eng.spec_stats()
            assert st["forwards"] > 0          # it DID speculate
            assert st["host_syncs"] == 0
        finally:
            srv.shutdown(drain=False)

    def test_program_keys_on_steps_and_k_only(self, mon):
        """Two segment widths compile two programs; rerunning either
        reuses its first compile (per-request state never keys it)."""
        model, _ = tiny_model(layers=1)
        eng = paged_engine(
            model, max_batch=1, max_len=64, draft_k=3,
            spec_mode="device")
        for _ in range(2):
            _run(eng, [REP[:8]], [_spec(10)], steps=4)
        _run(eng, [REP[:8]], [_spec(6)], steps=2)
        misses = monitor.jit_miss_by_fn()
        assert misses.get("cb_spec_device_segment") == 2, misses


class TestLedgerDispatches:
    def test_one_program_dispatches_equal_segments(self, led):
        """The ledger sees ONE cb_spec_device_segment program whose
        dispatch count equals the number of SEGMENTS run — the fused
        loop never dispatches per verify step."""
        model, _ = tiny_model(layers=1)
        eng = paged_engine(
            model, max_batch=2, max_len=128, draft_k=4,
            spec_mode="device")
        eng.add_request(REP, _spec(16))
        eng.add_request(RND, _spec(16))
        segs = 0
        while True:
            segs += 1
            if not eng.decode_segment(3):
                break
        recs = [r for r in ledger.profile()["programs"].values()
                if r["name"] == "cb_spec_device_segment"]
        assert len(recs) == 1, recs
        assert recs[0]["dispatches"] == segs
        assert recs[0]["compiles"] == 1


class TestStatsAndSyncs:
    def test_host_and_device_accounting_agree(self):
        """Same workload, both modes: identical speculative accounting
        (equal acceptance — the drafts are the same), differing ONLY
        in host_syncs: one per verify forward vs structurally zero."""
        model, _ = tiny_model()
        outs, stats = {}, {}
        for mode in ("host", "device"):
            eng = paged_engine(
                model, max_batch=2, max_len=128, draft_k=4,
                spec_mode=mode)
            outs[mode] = _run(eng, [REP, RND], [_spec(12), _spec(12)])
            stats[mode] = eng.spec_stats()
        for a, b in zip(outs["host"], outs["device"]):
            np.testing.assert_array_equal(a, b)
        h, d = stats["host"], stats["device"]
        for key_ in ("proposed", "accepted", "forwards", "slot_steps",
                     "emitted", "acceptance_rate",
                     "tokens_per_forward"):
            assert h[key_] == d[key_], (key_, h, d)
        assert h["host_syncs"] == h["forwards"] > 0
        assert d["host_syncs"] == 0
        assert h["host_syncs_per_token"] > 0.0
        assert d["host_syncs_per_token"] == 0.0
        assert d["emitted"] == d["slot_steps"] + d["accepted"]

    def test_identity_survives_reset_state(self):
        model, _ = tiny_model()
        eng = paged_engine(
            model, max_batch=2, max_len=128, draft_k=4,
            spec_mode="device")
        _run(eng, [REP, RND], [_spec(12), _spec(12)])
        st = eng.spec_stats()
        eng.reset_state()
        assert eng._spec == {}          # proposers die with the slots
        st2 = eng.spec_stats()
        assert st2["emitted"] == st["emitted"]
        assert st2["emitted"] == st2["slot_steps"] + st2["accepted"]
        # and the engine decodes again post-reset, still device mode
        out = _run(eng, [REP], [_spec(6)])
        assert len(out[0]) == 6
        assert eng.spec_stats()["host_syncs"] == 0


@pytest.mark.skipif(
    jax.device_count() < 4,
    reason="tensor-parallel tests need >= 4 devices (run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
class TestTensorParallel:
    """The history ring replicates across the mesh — TP=2 device-mode
    spec is bitwise vs TP=1 plain (same pinned seed, TP changes
    placement, never values)."""

    def _engine(self, tp, **kw):
        paddle.seed(0)
        cfg = llama_config("tiny", num_hidden_layers=1)
        model = LlamaForCausalLM(cfg)
        kw.setdefault("max_batch", 2)
        kw.setdefault("num_pages", 32)
        kw.setdefault("page_size", 8)
        kw.setdefault("max_pages", 8)
        kw.setdefault("debug_pages", True)
        return PagedContinuousBatchingEngine(model, tp_degree=tp, **kw)

    def test_tp2_device_spec_bitwise(self):
        ref = _run(self._engine(1), [REP, RND],
                   [_greedy(16), _greedy(16)])
        eng = self._engine(2, draft_k=4, spec_mode="device")
        out = _run(eng, [REP, RND], [_spec(16), _spec(16)])
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)
        st = eng.spec_stats()
        assert st["accepted"] > 0 and st["host_syncs"] == 0
        assert eng.alloc.free_pages == eng.num_pages
