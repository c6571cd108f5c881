"""Multi-tenant LoRA serving suite (ISSUE 13): one engine, many
fine-tunes.

Covers the batched-adapter contract on CPU:

- :class:`~paddle_tpu.serving.adapters.AdapterRegistry` lifecycle:
  load/unload/acquire/release, UNLOAD DEFERRAL while live slots
  reference the index, index recycling, capacity/rank/shape
  validation, resident snapshot;
- BITWISE PARITY (greedy): a mixed-adapter batch produces exactly the
  tokens of each adapter run alone (dense + paged, MHA + GQA) through
  ONE compiled segment program, and base-model rows on a LoRA-enabled
  engine are bitwise what a LoRA-free engine produces (index 0's
  zero rows gather an exact 0.0 delta);
- the MERGED-WEIGHTS oracle: a single adapter's output matches a model
  whose projection weights were merged with ``W + (B A)^T * alpha/r``
  (allclose — fp summation order differs by construction);
- ONE-compiled-program invariant: post-``warmup`` a mixed-adapter run
  (hot load included) pays ZERO monitored jit compiles;
- per-adapter PREFIX-CACHE NAMESPACES: cross-adapter warm hits are
  zero (generation-salted chain hashes), same-adapter hits still fire
  with bitwise warm-vs-cold parity, and reloading a name never hits
  the old weights' pages;
- composition with the serving stack: preempt-replay under forced
  optimistic pressure (adapter_idx survives replay), PR 4 engine
  restart replay, PR 7 speculative decoding, kv_dtype="int8" — all
  ``debug_pages=True``, leak-free;
- per-tenant quotas: a tenant over quota DEFERS while other tenants
  admit past it;
- the HTTP surface: strict unknown-field 400 (the typo'd ``adaptor``
  case), ``adapter`` round-trip, ``POST /adapters/load|unload``,
  registry state in ``/healthz``;
- router adapter affinity: requests prefer replicas with the adapter
  resident.
"""
import functools
import json
import threading
import time

import jax
import numpy as np
import pytest

import engine_helpers
import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference.generation import (CausalLMEngine, EngineFault,
                                             GenerationConfig, _lora_rows)
from paddle_tpu.serving import AdapterRegistry, Server
from paddle_tpu.serving.queue import RequestQueue

_MODELS = {}


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


def make_adapter(model, seed, targets=("q", "v"), rank=2, scale=0.6):
    """Seeded numpy (A, B) factors per target, sized from the model's
    lora_shapes hook. ``scale`` is large enough that adapter outputs
    actually diverge from base on the untrained tiny model."""
    _, shapes = model.lora_shapes(targets)
    rng = np.random.default_rng(seed)
    return {t: (rng.standard_normal((rank, d_in)).astype(np.float32)
                * scale,
                rng.standard_normal((d_out, rank)).astype(np.float32)
                * scale)
            for t, (d_in, d_out) in shapes.items()}


paged_engine = functools.partial(
    engine_helpers.paged_engine, max_batch=4, num_pages=64, page_size=4,
    max_pages=8, debug_pages=True, lora_capacity=3, lora_rank=4,
    lora_targets=("q", "v"))


def _greedy(n, adapter=None, eos=None):
    return GenerationConfig(max_new_tokens=n, adapter=adapter,
                            eos_token_id=eos)


def _run_one(eng, ids, n=6, adapter=None, seg=4):
    rid = eng.add_request(np.asarray(ids, np.int32),
                          _greedy(n, adapter))
    while eng.decode_segment(seg):
        pass
    return list(dict(eng.collect_finished())[rid])


def _assert_no_leaks(eng):
    assert eng.free_slots() == eng.max_batch
    assert eng.alloc.used_pages == 0
    assert (eng.alloc.free_pages + eng.alloc.cached_pages
            == eng.num_pages)
    eng.alloc.check()


PROMPT = list(range(1, 9))


def _merged_clone(params, scale):
    """A fresh seeded clone of ``tiny_model(4)`` with the adapter merged
    into its weights: W' = W + (B A)^T * scale (scale = alpha / r)."""
    paddle.seed(0)
    from paddle_tpu.models import LlamaForCausalLM, llama_config
    merged = LlamaForCausalLM(llama_config(
        "tiny", num_hidden_layers=1, num_key_value_heads=4))
    layer = merged.model.layers[0]
    projs = {"q": layer.self_attn.q_proj, "v": layer.self_attn.v_proj,
             "gate": layer.mlp.gate_proj}
    for t, (a, b) in params.items():
        w = projs[t].weight
        w.set_value(np.asarray(w.value) + (b @ a).T * scale)
    return merged


def _prefill_logits(eng, model, aidx=0):
    """PROMPT's last-position logits from the engine's prefill forward
    under bank row ``aidx``, padded to its bucket."""
    ids = np.zeros((1, 16), np.int32)
    ids[0, :len(PROMPT)] = PROMPT
    logits, _ = jax.jit(lambda p, bank: eng._fwd_prefill(
        p, ids, model.init_cache(1, 16),
        lora=_lora_rows(bank, aidx, ids)))(eng.params, eng._bank())
    return np.asarray(logits[:, len(PROMPT) - 1])


# -- registry lifecycle ------------------------------------------------------
class TestAdapterRegistry:
    def _reg(self, capacity=2, rank=4):
        return AdapterRegistry(capacity, rank, ("q",), 1,
                               {"q": (8, 8)}, np.float32, "eng-test")

    def _ab(self, r=2, seed=0):
        rng = np.random.default_rng(seed)
        return (rng.standard_normal((r, 8)).astype(np.float32),
                rng.standard_normal((8, r)).astype(np.float32))

    def test_load_acquire_release_unload(self):
        reg = self._reg()
        idx = reg.load("a", {"q": self._ab()})
        assert idx == 1 and "a" in reg
        assert reg.acquire("a") == idx
        reg.release(idx)
        assert reg.unload("a") is True      # freed immediately
        assert "a" not in reg
        assert reg.resident()["free"] == 2

    def test_unload_defers_while_referenced(self):
        reg = self._reg()
        idx = reg.load("a", {"q": self._ab()})
        reg.acquire("a")
        assert reg.unload("a") is False     # deferred
        with pytest.raises(ValueError, match="unknown adapter"):
            reg.acquire("a")                # new requests rejected
        assert reg.resident()["draining"] == ["a"]
        reg.release(idx)                    # last live ref completes it
        assert reg.resident() == {"capacity": 2, "resident": 0,
                                  "free": 2, "adapters": [],
                                  "draining": []}

    def test_index_recycled_and_salt_fresh(self):
        reg = self._reg()
        i1 = reg.load("a", {"q": self._ab()})
        s1 = reg.salt(i1)
        reg.unload("a")
        i2 = reg.load("a", {"q": self._ab(seed=1)})
        assert i2 == i1                     # recycled
        assert reg.salt(i2) != s1           # but a FRESH namespace
        assert reg.salt(0) == b""           # base keeps the bare root

    def test_validation(self):
        reg = self._reg()
        reg.load("a", {"q": self._ab()})
        with pytest.raises(ValueError, match="already loaded"):
            reg.load("a", {"q": self._ab()})
        with pytest.raises(ValueError, match="not in the"):
            reg.load("b", {"nope": self._ab()})
        with pytest.raises(ValueError, match="rank"):
            reg.load("b", {"q": self._ab(r=5)})   # over the bank rank
        with pytest.raises(ValueError, match="B must be"):
            a, b = self._ab()
            reg.load("b", {"q": (a, b[:, :1])})   # rank mismatch
        reg.load("b", {"q": self._ab()})
        with pytest.raises(ValueError, match="registry full"):
            reg.load("c", {"q": self._ab()})

    def test_alpha_folds_into_bank(self):
        reg = self._reg()
        a, b = self._ab()
        reg.load("x", {"q": (a, b)}, alpha=4)   # r=2 -> scale 2.0
        A, B = reg.bank["q"]
        np.testing.assert_allclose(np.asarray(B[0, 1, :, :2]), b * 2.0,
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(A[0, 1, :2]), a,
                                   rtol=1e-6)
        # padded rank rows are zero
        assert not np.asarray(A[0, 1, 2:]).any()

    def test_name_bound_matches_generation_config(self):
        # a name loadable here but unreachable by GenerationConfig
        # would occupy a bank index forever
        reg = self._reg()
        with pytest.raises(ValueError, match="256"):
            reg.load("x" * 300, {"q": self._ab()})
        with pytest.raises(ValueError, match="adapter"):
            GenerationConfig(max_new_tokens=1, adapter="x" * 300)

    def test_release_all_completes_deferred(self):
        reg = self._reg()
        reg.load("a", {"q": self._ab()})
        reg.acquire("a")
        reg.unload("a")
        reg.release_all()                   # engine reset_state path
        assert reg.resident()["free"] == 2


# -- bitwise parity ----------------------------------------------------------
class TestLoraParity:
    @pytest.mark.parametrize("kv_heads", [4, 2])
    def test_mixed_batch_matches_solo_paged(self, kv_heads):
        model, _ = tiny_model(kv_heads)
        eng = paged_engine(model)
        eng.load_adapter("a1", make_adapter(model, 11))
        eng.load_adapter("a2", make_adapter(model, 22, scale=0.9))
        solo = {name: _run_one(eng, PROMPT, adapter=name)
                for name in (None, "a1", "a2")}
        assert solo["a1"] != solo[None] or solo["a2"] != solo[None]
        rids = {name: eng.add_request(np.asarray(PROMPT, np.int32),
                                      _greedy(6, name))
                for name in (None, "a1", "a2")}
        while eng.decode_segment(4):
            pass
        fin = eng.collect_finished()
        for name, rid in rids.items():
            assert list(fin[rid]) == solo[name], name
        _assert_no_leaks(eng)
        eng.close()

    def test_mixed_batch_matches_solo_dense(self):
        """The mixed batch against ``CausalLMEngine``, one request at a
        time: the base row on the model, the adapter row on a clone
        with the adapter's deltas merged into its weights."""
        model, _ = tiny_model(4)
        eng = paged_engine(model, max_batch=3, lora_capacity=2)
        params = make_adapter(model, 11)
        eng.load_adapter("a1", params)
        ids = np.asarray([PROMPT], np.int32)
        solo = {name: list(CausalLMEngine(m, max_batch=1, max_len=32)
                           .generate(ids, _greedy(6))[0, len(PROMPT):])
                for name, m in ((None, model),
                                ("a1", _merged_clone(params, 1.0)))}
        rids = {name: eng.add_request(np.asarray(PROMPT, np.int32),
                                      _greedy(6, name))
                for name in (None, "a1")}
        while eng.decode_segment(4):
            pass
        fin = eng.collect_finished()
        for name, rid in rids.items():
            assert list(fin[rid]) == solo[name], name
        eng.close()

    def test_base_rows_bitwise_vs_lora_free_engine(self):
        model, _ = tiny_model(4)
        plain = paged_engine(model, lora_capacity=0)
        ref = _run_one(plain, PROMPT)
        eng = paged_engine(model)
        eng.load_adapter("a1", make_adapter(model, 11))
        assert _run_one(eng, PROMPT) == ref   # delta gathered at row 0
        #                                       is exactly 0.0
        plain.close()
        eng.close()

    def test_merged_weights_oracle(self):
        """One adapter through the batched gather == the same deltas
        merged into the projection weights (allclose: the low-rank
        product and the merged matmul sum in different orders)."""
        model, cfg = tiny_model(4)
        params = make_adapter(model, 33, targets=("q", "v", "gate"),
                              rank=2, scale=0.3)
        eng = paged_engine(model, lora_capacity=1,
                           lora_targets=("q", "v", "gate"))
        eng.load_adapter("m", params, alpha=4)   # scale 2.0
        got = _prefill_logits(eng, model, aidx=1)
        merged = _merged_clone(params, 2.0)
        eng2 = paged_engine(merged, lora_capacity=0)
        want = _prefill_logits(eng2, merged)
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)
        eng.close()
        eng2.close()

    def test_rank_padding_exact(self):
        """An r=2 adapter in an r=4 bank decodes bitwise like the same
        adapter in an r=2 bank — zero-padded factor rows contribute an
        exact 0."""
        model, _ = tiny_model(4)
        params = make_adapter(model, 44, rank=2)
        wide = paged_engine(model, lora_rank=4)
        narrow = paged_engine(model, lora_rank=2)
        wide.load_adapter("p", params)
        narrow.load_adapter("p", params)
        assert (_run_one(wide, PROMPT, adapter="p")
                == _run_one(narrow, PROMPT, adapter="p"))
        wide.close()
        narrow.close()


# -- one compiled program ----------------------------------------------------
class TestOneProgram:
    def test_zero_compiles_post_warmup(self):
        """warmup() pre-compiles the widened programs; afterwards a hot
        adapter load + a mixed-adapter batch pay ZERO monitored jit
        compiles — the whole point of the bank-as-argument design."""
        monitor.enable()
        model, _ = tiny_model(4)
        eng = paged_engine(model, prefill_chunk=8)
        eng.warmup(segment_steps=4)

        def misses():
            return monitor.jit_miss_by_fn()

        before = misses()
        eng.load_adapter("a1", make_adapter(model, 11))
        eng.load_adapter("a2", make_adapter(model, 22))
        for name in (None, "a1", "a2"):
            eng.add_request(np.asarray(PROMPT, np.int32),
                            _greedy(6, name))
        while eng.decode_segment(4):
            pass
        eng.collect_finished()
        after = misses()
        assert after == before, (before, after)
        _assert_no_leaks(eng)
        eng.close()


# -- hot load / unload through the serving gap -------------------------------
class TestHotLoadUnload:
    def test_server_load_unload_deferred(self):
        model, _ = tiny_model(4)
        eng = paged_engine(model)
        srv = Server(eng, segment_steps=2)
        try:
            srv.load_adapter("hot", make_adapter(model, 55))
            ref = list(srv.submit(np.asarray(PROMPT, np.int32),
                                  _greedy(8, "hot")).result(30))
            h = srv.submit(np.asarray(PROMPT, np.int32),
                           _greedy(24, "hot"))
            it = h.stream(timeout=30)
            next(it)                      # request is live in a slot
            assert srv.unload_adapter("hot") is False   # defers
            with pytest.raises(Exception):
                # new submissions naming it fail at admission
                srv.submit(np.asarray(PROMPT, np.int32),
                           _greedy(4, "hot")).result(30)
            assert list(h.result(60))[:8] == ref[:8]    # live request
            #                                             unharmed
            deadline = time.monotonic() + 10
            while (srv.engine.adapters.resident()["free"] == 2
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert srv.engine.adapters.resident()["free"] == 3
            # the freed index recycles for a hot load mid-serving
            srv.load_adapter("hot2", make_adapter(model, 66))
            assert "hot2" in srv.engine.adapters
        finally:
            srv.shutdown()
            _assert_no_leaks(eng)
            eng.close()

    def test_admin_needs_lora_engine(self):
        model, _ = tiny_model(4)
        eng = paged_engine(model, lora_capacity=0)
        srv = Server(eng, start=False)
        with pytest.raises(RuntimeError, match="lora_capacity"):
            srv.load_adapter("x", {})
        srv.shutdown()
        eng.close()


# -- per-adapter prefix-cache namespaces -------------------------------------
class TestPrefixSalting:
    def test_cross_adapter_hit_zero_same_adapter_hits(self):
        model, _ = tiny_model(4)
        eng = paged_engine(model, num_pages=64, prefix_cache=True)
        eng.load_adapter("s1", make_adapter(model, 71))
        eng.load_adapter("s2", make_adapter(model, 72))
        prompt = list(range(1, 13))        # 3 full pages
        cold = _run_one(eng, prompt, adapter="s1")
        assert eng.alloc.prefix_hits == 0
        # SAME prompt, different adapter: provably zero warm hits
        _run_one(eng, prompt, adapter="s2")
        assert eng.alloc.prefix_hits == 0
        _run_one(eng, prompt)              # base namespace: also cold
        assert eng.alloc.prefix_hits == 0
        # same adapter again: warm hit fires, bitwise parity
        warm = _run_one(eng, prompt, adapter="s1")
        assert eng.alloc.prefix_hits == 1
        assert warm == cold
        _assert_no_leaks(eng)
        eng.close()

    def test_reload_same_name_never_hits_old_pages(self):
        """Unload + reload of the SAME name gets a fresh generation
        salt: pages cached under the old weights can never serve the
        new ones (they would be silently wrong KV)."""
        model, _ = tiny_model(4)
        eng = paged_engine(model, num_pages=64, prefix_cache=True)
        eng.load_adapter("r", make_adapter(model, 81))
        prompt = list(range(1, 13))
        _run_one(eng, prompt, adapter="r")
        eng.unload_adapter("r")
        eng.load_adapter("r", make_adapter(model, 82))   # new weights
        _run_one(eng, prompt, adapter="r")
        assert eng.alloc.prefix_hits == 0
        _assert_no_leaks(eng)
        eng.close()

    def test_base_namespace_still_warm(self):
        model, _ = tiny_model(4)
        eng = paged_engine(model, num_pages=64, prefix_cache=True)
        eng.load_adapter("b1", make_adapter(model, 91))
        prompt = list(range(1, 13))
        cold = _run_one(eng, prompt)
        warm = _run_one(eng, prompt)
        assert eng.alloc.prefix_hits == 1 and warm == cold
        _assert_no_leaks(eng)
        eng.close()


# -- composition with the serving stack --------------------------------------
class TestCompose:
    def test_preempt_replay_keeps_adapter(self):
        """Forced optimistic pressure: preempted adapter requests
        replay — with their adapter_idx — bitwise identical to an
        unpressured run."""
        model, _ = tiny_model(4)
        roomy = paged_engine(model, num_pages=64)
        roomy.load_adapter("p1", make_adapter(model, 101))
        refs = [_run_one(roomy, PROMPT, n=10, adapter=a)
                for a in ("p1", "p1", None)]
        roomy.close()
        tight = paged_engine(model, num_pages=12,
                             admission_mode="optimistic")
        tight.load_adapter("p1", make_adapter(model, 101))
        srv = Server(tight, segment_steps=4, max_preemptions=10)
        try:
            hs = [srv.submit(np.asarray(PROMPT, np.int32),
                             _greedy(10, a))
                  for a in ("p1", "p1", None)]
            outs = [list(h.result(120)) for h in hs]
            assert outs == refs
            assert tight.alloc.preemptions >= 1   # pressure really hit
        finally:
            srv.shutdown()
            _assert_no_leaks(tight)
            tight.close()

    def test_engine_restart_replays_adapter(self):
        """A decode-seam EngineFault mid-run: the supervised restart
        replays the adapter request bitwise (the registry — bank and
        name map — survives reset_state)."""
        from paddle_tpu.testing.faults import FaultPlan, FaultyEngine

        model, _ = tiny_model(4)
        clean = paged_engine(model)
        clean.load_adapter("f1", make_adapter(model, 111))
        ref = _run_one(clean, PROMPT, n=10, adapter="f1")
        clean.close()
        eng = paged_engine(model)
        eng.load_adapter("f1", make_adapter(model, 111))
        plan = FaultPlan().raise_at(
            "decode", nth=2, exc=EngineFault("injected"))
        srv = Server(FaultyEngine(eng, plan), segment_steps=4,
                     max_restarts=3, restart_backoff_s=0.01)
        try:
            h = srv.submit(np.asarray(PROMPT, np.int32),
                           _greedy(10, "f1"))
            assert list(h.result(120)) == ref
            assert srv.restarts == 1
        finally:
            srv.shutdown()
            _assert_no_leaks(eng)
            eng.close()

    def test_spec_decode_with_adapter(self):
        """PR 7 composition: a speculating adapter request through the
        widened verify program is bitwise its plain-decode self."""
        model, _ = tiny_model(4)
        rep = (PROMPT * 3)[:20]            # repetitive: accepting case
        eng = paged_engine(model, max_pages=16, num_pages=96,
                           draft_k=4)
        eng.load_adapter("sp", make_adapter(model, 121))
        plain = _run_one(eng, rep, n=12, adapter="sp")
        rid = eng.add_request(
            np.asarray(rep, np.int32),
            GenerationConfig(max_new_tokens=12, adapter="sp",
                             speculative=True))
        while eng.decode_segment(4):
            pass
        spec = list(dict(eng.collect_finished())[rid])
        assert spec == plain
        assert eng.spec_stats()["forwards"] >= 1
        _assert_no_leaks(eng)
        eng.close()

    def test_int8_kv_with_adapters(self):
        """kv_dtype="int8" composition: a mixed-adapter batch through
        quantized pools matches its solo runs (solo vs mixed stays
        bitwise — both read the same quantized pipeline), leak-free
        under the scale-aware validator."""
        model, _ = tiny_model(4)
        eng = paged_engine(model, kv_dtype="int8")
        eng.load_adapter("q1", make_adapter(model, 131))
        solo = {a: _run_one(eng, PROMPT, adapter=a)
                for a in (None, "q1")}
        rids = {a: eng.add_request(np.asarray(PROMPT, np.int32),
                                   _greedy(6, a))
                for a in (None, "q1")}
        while eng.decode_segment(4):
            pass
        fin = eng.collect_finished()
        for a, rid in rids.items():
            assert list(fin[rid]) == solo[a], a
        _assert_no_leaks(eng)
        eng.close()


# -- per-tenant quotas -------------------------------------------------------
class TestTenantQuotas:
    def test_over_quota_defers_without_starving_others(self):
        """Tenant A's second request defers at its quota while tenant
        B — queued BEHIND it — admits and finishes; A's second admits
        once A's first retires."""
        model, _ = tiny_model(4)
        eng = paged_engine(model, max_batch=4)
        eng.load_adapter("A", make_adapter(model, 141))
        eng.load_adapter("B", make_adapter(model, 142))
        srv = Server(eng, segment_steps=2, tenant_quotas=1)
        try:
            a1 = srv.submit(np.asarray(PROMPT, np.int32),
                            _greedy(20, "A"))
            it = a1.stream(timeout=30)
            next(it)                       # A1 occupies A's one slot
            a2 = srv.submit(np.asarray(PROMPT, np.int32),
                            _greedy(4, "A"))
            b1 = srv.submit(np.asarray(PROMPT, np.int32),
                            _greedy(4, "B"))
            b1.result(60)                  # B passes the deferred A2
            assert a2.status == "queued"   # A over quota: still waiting
            a1.result(120)
            a2.result(60)                  # admits once A1 retired
        finally:
            srv.shutdown()
            _assert_no_leaks(eng)
            eng.close()

    def test_quota_dict_and_untracked_tenants(self):
        model, _ = tiny_model(4)
        eng = paged_engine(model, max_batch=4)
        srv = Server(eng, segment_steps=2,
                     tenant_quotas={"X": 1}, start=False)
        # dict caps only named tenants; base/None is untracked
        h = type("H", (), {"tenant": None})
        assert srv._tenant_ok(h)
        h2 = type("H2", (), {"tenant": "Y"})
        assert srv._tenant_ok(h2)
        srv.shutdown()
        eng.close()

    def test_quota_validation(self):
        model, _ = tiny_model(4)
        eng = paged_engine(model, lora_capacity=0)
        with pytest.raises(ValueError, match="tenant_quotas"):
            Server(eng, tenant_quotas="lots", start=False)
        with pytest.raises(ValueError, match="quota caps"):
            Server(eng, tenant_quotas={"a": 0}, start=False)
        eng.close()

    def test_queue_pop_admittable_skips_only_quota(self):
        q = RequestQueue(8)

        def mk(i, tenant):
            from paddle_tpu.serving.queue import RequestHandle
            return RequestHandle(i, [1], 1, _greedy(2),
                                 tenant=tenant)

        h0, h1, h2 = mk(0, "A"), mk(1, "A"), mk(2, "B")
        for h in (h0, h1, h2):
            q.put(h)
        # capacity-blocked head stops the scan (no bypass)
        assert q.pop_admittable(lambda h: False, lambda h: True) is None
        assert q.depth == 3
        # quota-blocked entries are skipped, FIFO otherwise
        got = q.pop_admittable(lambda h: True,
                               lambda h: h.tenant != "A")
        assert got is h2 and q.depth == 2


# -- HTTP surface ------------------------------------------------------------
class TestHTTPAdapters:
    @pytest.fixture()
    def served(self):
        from paddle_tpu.serving import serve_http

        model, _ = tiny_model(4)
        eng = paged_engine(model)
        srv = Server(eng, segment_steps=4)
        srv.load_adapter("web", make_adapter(model, 151))
        httpd = serve_http(srv)
        yield srv, eng, httpd.server_address[1]
        httpd.shutdown()
        srv.shutdown()
        eng.close()

    def _post(self, port, path, body):
        import http.client
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        c.request("POST", path, json.dumps(body),
                  {"Content-Type": "application/json"})
        r = c.getresponse()
        out = (r.status, json.loads(r.read() or b"{}"))
        c.close()
        return out

    def _get(self, port, path):
        import http.client
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        c.request("GET", path)
        r = c.getresponse()
        out = (r.status, json.loads(r.read() or b"{}"))
        c.close()
        return out

    def test_unknown_field_400_names_field(self, served):
        _, _, port = served
        st, body = self._post(port, "/generate",
                              {"prompt": PROMPT, "adaptor": "web"})
        assert st == 400
        assert "adaptor" in body["error"]          # names the typo
        assert "adapter" in body["error"]          # lists the fix

    def test_adapter_round_trip(self, served):
        srv, eng, port = served
        ref = list(srv.submit(np.asarray(PROMPT, np.int32),
                              _greedy(5, "web")).result(60))
        st, body = self._post(port, "/generate",
                              {"prompt": PROMPT, "max_new_tokens": 5,
                               "adapter": "web"})
        assert st == 200 and body["tokens"] == [int(t) for t in ref]
        # unknown adapter: the request fails with the cause, 500
        st, body = self._post(port, "/generate",
                              {"prompt": PROMPT, "max_new_tokens": 4,
                               "adapter": "nope"})
        assert st == 500 and "nope" in body["error"]

    def test_admin_load_unload_and_healthz(self, served):
        srv, eng, port = served
        model, _ = tiny_model(4)
        p = make_adapter(model, 161)
        weights = {t: {"a": a.tolist(), "b": b.tolist()}
                   for t, (a, b) in p.items()}
        st, body = self._post(port, "/adapters/load",
                              {"name": "adm", "weights": weights})
        assert st == 200 and body["index"] >= 1
        assert "adm" in body["adapters"]["adapters"]
        st, hz = self._get(port, "/healthz")
        assert st == 200 and "adm" in hz["lora"]["adapters"]
        st, body = self._post(port, "/adapters/unload",
                              {"name": "adm"})
        assert st == 200 and body["unloaded"] is True
        # validation errors are 400s
        st, body = self._post(port, "/adapters/load",
                              {"name": "bad"})
        assert st == 400 and "weights" in body["error"]
        st, body = self._post(port, "/adapters/unload",
                              {"name": "ghost"})
        assert st == 400 and "ghost" in body["error"]
        # admin bodies are strict too: a typo'd "aplha" must not
        # silently install scale-1.0 deltas
        st, body = self._post(port, "/adapters/load",
                              {"name": "t", "weights": weights,
                               "aplha": 32})
        assert st == 400 and "aplha" in body["error"]

    def test_admin_on_non_lora_engine_is_400(self):
        from paddle_tpu.serving import serve_http

        model, _ = tiny_model(4)
        eng = paged_engine(model, lora_capacity=0)
        srv = Server(eng, segment_steps=4)
        httpd = serve_http(srv)
        try:
            st, body = self._post(httpd.server_address[1],
                                  "/adapters/load", {"name": "x"})
            # permanently unsupported: 400, never a retryable 503
            assert st == 400 and "lora_capacity" in body["error"]
        finally:
            httpd.shutdown()
            srv.shutdown()
            eng.close()


# -- router adapter affinity -------------------------------------------------
class TestRouterAffinity:
    def test_prefers_adapter_resident_replica(self):
        from paddle_tpu.serving import ReplicaSpec, Router

        def factory():
            paddle.seed(0)
            from paddle_tpu.models import LlamaForCausalLM, llama_config
            m = LlamaForCausalLM(llama_config(
                "tiny", num_hidden_layers=1))
            return paged_engine(m, debug_pages=False)

        spec = ReplicaSpec(factory,
                           server_kwargs={"segment_steps": 4})
        router = Router(spec, replicas=2)
        try:
            # adapter resident on replica 1 ONLY
            model, _ = tiny_model(4)
            router._replicas[1].server.load_adapter(
                "aff", make_adapter(model, 171))
            for _ in range(3):   # affinity beats index-0 tie-breaks
                h = router.submit(np.asarray(PROMPT, np.int32),
                                  _greedy(4, "aff"))
                h.result(60)
                assert h.replica == 1
            # base requests still least-loaded (no affinity pin)
            h = router.submit(np.asarray(PROMPT, np.int32), _greedy(4))
            h.result(60)
        finally:
            router.shutdown()
