"""Tensor-parallel sharded serving (ISSUE 14 acceptance on CPU).

A ``tp_degree=k`` engine runs every serving program — the one-compiled
decode segment, bucketed/chunked prefill, spec verify — under a 1-D
``"mp"`` mesh: weights and KV pools shard on the (kv_)head axis,
per-slot vectors and the page table replicate, and the page
allocator / prefix-cache / CoW host logic is untouched (TP-invariant by
construction). The bar here is BITWISE-GREEDY parity TP=2 and TP=4 vs
TP=1 on the conftest's forced-8-device CPU mesh, across the full
composition matrix (prefix-cache warm hits, int8 KV, speculative
slots, LoRA adapter mixes, preempt-replay, engine restart), with zero
post-warmup compiles and ``debug_pages`` validators green.

Skips CLEANLY when the forced host devices are unavailable (e.g. a
runner that stripped XLA_FLAGS) — TP needs the virtual mesh.
"""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference.generation import (GenerationConfig,
                                             PagedContinuousBatchingEngine)
from paddle_tpu.models import LlamaForCausalLM, llama_config

# the conftest forces an 8-device virtual CPU platform; if a foreign
# runner stripped XLA_FLAGS the mesh cannot exist — skip, don't error
pytestmark = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="tensor-parallel tests need >= 4 devices (run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8)")

CFG = llama_config("tiny", num_hidden_layers=1)
GQA_CFG = llama_config("tiny", num_hidden_layers=1,
                       num_key_value_heads=2)
PROMPT = np.arange(1, 20, dtype=np.int32)
SHORT = np.arange(3, 11, dtype=np.int32)
REP = np.asarray([5, 6, 7, 8] * 6, np.int32)   # n-gram friendly


def paged_engine(tp=1, cfg=CFG, **kw):
    """Fresh seeded model + paged engine; seeds are pinned so TP=1 and
    TP=k arms hold bitwise-identical weights (TP changes placement,
    never values)."""
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    kw.setdefault("max_batch", 2)
    kw.setdefault("num_pages", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_pages", 8)
    kw.setdefault("debug_pages", True)
    return PagedContinuousBatchingEngine(model, tp_degree=tp, **kw)


def drain(eng, prompts, cfgs, steps=4):
    rids = [eng.add_request(p, c) for p, c in zip(prompts, cfgs)]
    while eng.decode_segment(steps):
        pass
    fin = eng.collect_finished()
    return [fin[r].tolist() for r in rids]


def greedy(n, **kw):
    return GenerationConfig(max_new_tokens=n, **kw)


def _assert_no_leaks(eng):
    assert len(eng._free) == eng.max_batch
    assert eng.alloc.used_pages == 0
    eng.alloc.check()


# -- construction-time validation --------------------------------------------
class TestValidation:
    def test_tp_degree_validated(self):
        with pytest.raises(ValueError, match="tp_degree"):
            paged_engine(tp=0)
        with pytest.raises(ValueError, match="tp_degree"):
            paged_engine(tp="two")

    def test_tp_needs_enough_devices(self):
        with pytest.raises(ValueError, match="devices"):
            paged_engine(tp=jax.device_count() + 1)

    def test_tp_must_divide_heads(self):
        # tiny has 4 query heads / 4 kv heads: tp=3 cannot shard them
        with pytest.raises(ValueError, match="divide"):
            paged_engine(tp=3)

    def test_tp1_has_no_mesh(self):
        eng = paged_engine(tp=1)
        assert eng.tp_mesh is None and eng.tp_degree == 1
        assert "tp" not in eng.load()
        eng.close()


# -- bitwise-greedy parity ----------------------------------------------------
class TestParity:
    @pytest.fixture(scope="class")
    def ref_tokens(self):
        eng = paged_engine(tp=1)
        out = drain(eng, [PROMPT, SHORT],
                    [greedy(8), greedy(10, eos_token_id=3)])
        _assert_no_leaks(eng)
        eng.close()
        return out

    @pytest.mark.parametrize("tp", [2, 4])
    def test_mixed_batch_parity(self, tp, ref_tokens):
        """Greedy mixed-length batch: TP=k tokens are bitwise the TP=1
        tokens, and the pools live sharded between segments."""
        eng = paged_engine(tp=tp)
        out = drain(eng, [PROMPT, SHORT],
                    [greedy(8), greedy(10, eos_token_id=3)])
        assert out == ref_tokens
        pools, _ = eng.caches
        spec = pools[0][0].sharding.spec
        assert spec[2] is not None, (
            f"kv pool not head-sharded under tp={tp}: {spec}")
        _assert_no_leaks(eng)
        eng.close()

    def test_gqa_parity_tp2(self):
        """GQA (4 q-heads over 2 kv-heads): both axes divide tp=2 and
        the grouped kernel sees a consistent per-shard ratio."""
        ref = paged_engine(tp=1, cfg=GQA_CFG)
        a = drain(ref, [PROMPT], [greedy(8)])
        ref.close()
        eng = paged_engine(tp=2, cfg=GQA_CFG)
        b = drain(eng, [PROMPT], [greedy(8)])
        eng.close()
        assert a == b

    def test_sampled_rows_ride_along_tp2(self):
        """A sampled slot shares the one program with greedy slots at
        TP=2: the greedy row stays bitwise the TP=1 greedy row (the
        sampled row's trajectory is seed-dependent float sampling —
        not part of the bitwise bar, but it must complete and respect
        its budget)."""
        ref = paged_engine(tp=1)
        a = drain(ref, [PROMPT], [greedy(8)])
        ref.close()
        eng = paged_engine(tp=2)
        out = drain(eng, [PROMPT, SHORT],
                    [greedy(8),
                     greedy(6, do_sample=True, temperature=0.8,
                            top_k=5, seed=7)])
        assert out[0] == a[0]
        assert len(out[1]) == 6
        _assert_no_leaks(eng)
        eng.close()


# -- the composition matrix ---------------------------------------------------
class TestComposition:
    """Every serving capability PRs 3-13 built, running TOGETHER on a
    TP mesh: chunked prefill + prefix-cache warm hits + int8 KV pages
    + speculative slots + a LoRA adapter mix, optimistic admission,
    debug_pages validators on — bitwise vs the identically-knobbed
    TP=1 engine."""

    KNOBS = dict(prefill_chunk=8, prefix_cache=True, kv_dtype="int8",
                 draft_k=4, lora_capacity=2, lora_rank=4,
                 admission_mode="optimistic", num_pages=48)

    @staticmethod
    def adapter(seed, shapes, rank=4):
        g = np.random.default_rng(seed)
        return {t: (g.standard_normal((rank, di)).astype(np.float32)
                    * 0.05,
                    g.standard_normal((do, rank)).astype(np.float32)
                    * 0.05)
                for t, (di, do) in shapes.items()}

    def run_matrix(self, tp):
        eng = paged_engine(tp=tp, **self.KNOBS)
        eng.load_adapter("t1", self.adapter(11, eng.adapters.shapes))
        # cold: base + adapter + speculating slots mixed in one batch
        cold = drain(eng, [PROMPT, REP],
                     [greedy(6, adapter="t1"),
                      greedy(10, speculative=True)])
        # warm: the same prompts re-admit over the cached prefix (the
        # adapter request hits its SALTED namespace, base hits base)
        warm = drain(eng, [PROMPT, REP],
                     [greedy(6, adapter="t1"),
                      greedy(10, speculative=True)])
        hits = eng.alloc.prefix_hits
        _assert_no_leaks(eng)
        eng.close()
        return cold, warm, hits

    @pytest.mark.parametrize("tp", [2, 4])
    def test_full_composition_parity(self, tp):
        ref_cold, ref_warm, ref_hits = self.run_matrix(1)
        assert ref_cold == ref_warm        # warm-hit bitwise contract
        assert ref_hits >= 1
        cold, warm, hits = self.run_matrix(tp)
        assert cold == ref_cold
        assert warm == ref_warm
        assert hits == ref_hits            # hashing is TP-invariant


# -- preempt-replay + restart under pressure ---------------------------------
class TestPressureAndRestart:
    def test_preempt_replay_parity_tp2(self):
        """Optimistic admission on a pool too small for both requests:
        the youngest is preempted and replayed (engine.serve's relief
        loop) — TP=2 results bitwise match TP=1, with >= 1 preemption
        actually forced on both arms."""
        def run(tp):
            eng = paged_engine(tp=tp, max_batch=3, num_pages=8,
                               max_pages=8,
                               admission_mode="optimistic",
                               kv_watermark=1.0)
            outs = eng.serve([PROMPT, SHORT, REP], greedy(20),
                             segment_steps=4)
            pre = eng.alloc.preemptions
            _assert_no_leaks(eng)
            eng.close()
            return [o.tolist() for o in outs], pre

        a, pre1 = run(1)
        b, pre2 = run(2)
        assert pre1 >= 1 and pre2 >= 1, (pre1, pre2)
        assert a == b

    def test_restart_replay_parity_tp2(self):
        """PR 4's supervised-recovery contract on a mesh: reset_state
        rebuilds SHARDED pools + replicated vectors (one shared
        _init_decode_state), and a greedy replay of prompt + emitted
        prefix is bitwise the uninterrupted run."""
        ref = paged_engine(tp=1)
        want = drain(ref, [PROMPT], [greedy(12)])[0]
        ref.close()

        eng = paged_engine(tp=2)
        rid = eng.add_request(PROMPT, greedy(12))
        eng.decode_segment(4)
        prefix = eng.partial_tokens(rid)
        assert 0 < len(prefix) < 12
        eng.reset_state()
        pools, _ = eng.caches
        assert pools[0][0].sharding.spec[2] is not None
        replay = np.concatenate([PROMPT,
                                 np.asarray(prefix, np.int32)])
        out = drain(eng, [replay], [greedy(12 - len(prefix))])[0]
        assert prefix + out == want
        _assert_no_leaks(eng)
        eng.close()


# -- one program / zero post-warmup compiles ----------------------------------
class TestOneProgram:
    def test_zero_compiles_post_warmup_tp2(self):
        """After warmup() on a TP=2 engine with EVERY knob on, a hot
        adapter load + a mixed cold/warm/spec/adapter run pays zero
        monitored jit compiles — the one-program invariant extended to
        the mesh (shardings are committed at construction, so no
        program ever recompiles on a sharding change)."""
        monitor.enable()
        eng = paged_engine(tp=2, **TestComposition.KNOBS)
        eng.warmup(segment_steps=4)

        def misses():
            return monitor.jit_miss_by_fn()

        before = misses()
        eng.load_adapter("a1", TestComposition.adapter(
            11, eng.adapters.shapes))
        drain(eng, [PROMPT, REP],
              [greedy(6, adapter="a1"), greedy(8, speculative=True)])
        drain(eng, [PROMPT], [greedy(6, adapter="a1")])   # warm hit
        after = misses()
        assert after == before, (before, after)
        _assert_no_leaks(eng)
        eng.close()


# -- serving surfaces ---------------------------------------------------------
class TestSurfaces:
    def test_engine_load_surfaces_mesh(self):
        eng = paged_engine(tp=2)
        snap = eng.load()
        assert snap["tp_degree"] == 2
        assert snap["tp"]["degree"] == 2
        assert snap["tp"]["axis"] == "mp"
        assert len(snap["tp"]["devices"]) == 2
        eng.close()

    def test_server_healthz_surfaces_mesh(self):
        import json
        from urllib.request import urlopen

        from paddle_tpu.serving import Server, serve_http

        srv = Server(paged_engine(tp=2), segment_steps=2)
        try:
            assert srv.load()["tp"]["degree"] == 2
            httpd = serve_http(srv, port=0)
            try:
                port = httpd.server_address[1]
                with urlopen(f"http://127.0.0.1:{port}/healthz",
                             timeout=10) as r:
                    body = json.loads(r.read())
                assert body["tp"]["degree"] == 2
                assert body["tp_degree"] == 2
            finally:
                httpd.shutdown()
        finally:
            srv.shutdown(drain=False)


# -- fleet composition: ReplicaSpec devices + failover at TP=2 ----------------
class TestFleet:
    def test_replica_spec_pins_device_subsets(self):
        """An N-replica × TP-k fleet partitions one slice: each
        ReplicaSpec pins its replica's devices, the factory receives
        them, and the engines' meshes are disjoint."""
        from paddle_tpu.serving import ReplicaSpec, Router

        devs = jax.devices()
        seen = {}

        def factory_for(i):
            def factory(devices):
                eng = paged_engine(tp=2, tp_devices=devices)
                seen[i] = [str(d) for d in eng.tp_mesh.devices.flat]
                return eng
            return factory

        specs = [ReplicaSpec(factory_for(i),
                             server_kwargs={"segment_steps": 2,
                                            "idle_wait_s": 0.005},
                             devices=devs[2 * i:2 * i + 2])
                 for i in range(2)]
        r = Router(specs, monitor_interval_s=0.05)
        try:
            r.wait_ready()
            assert seen[0] == [str(d) for d in devs[0:2]]
            assert seen[1] == [str(d) for d in devs[2:4]]
            assert not set(seen[0]) & set(seen[1])
            h = r.submit(PROMPT, greedy(6))
            assert len(h.result(timeout=120).tolist()) == 6
        finally:
            r.shutdown(drain=False)

    def test_replica_spec_devices_validated(self):
        from paddle_tpu.serving import ReplicaSpec

        with pytest.raises(ValueError, match="devices"):
            ReplicaSpec(lambda: None, devices=[])

    def test_midstream_kill_failover_parity_tp2(self):
        """ACCEPTANCE: a TP=2 engine serves under the PR 9 router
        unchanged — the serving replica is killed mid-stream and the
        request migrates with failover replay intact, the client's one
        uninterrupted stream bitwise matching an unfaulted TP=1 run."""
        from paddle_tpu.serving import ReplicaSpec, Router, Server
        from paddle_tpu.testing.faults import FaultPlan, FaultyEngine

        ref = Server(paged_engine(tp=1), segment_steps=2,
                     idle_wait_s=0.005)
        try:
            want = ref.submit(PROMPT, greedy(24)).result(
                timeout=120).tolist()
        finally:
            ref.shutdown(drain=False)

        plans = {}
        builds = {"n": 0}

        def factory(devices):
            i = builds["n"]
            builds["n"] += 1
            eng = paged_engine(tp=2, tp_devices=devices)
            if i < 2:          # first build of each replica slot
                plans[i] = FaultPlan()
                return FaultyEngine(eng, plans[i])
            return eng

        devs = jax.devices()
        specs = [ReplicaSpec(factory,
                             server_kwargs={"segment_steps": 2,
                                            "idle_wait_s": 0.005,
                                            "max_restarts": 0},
                             devices=devs[2 * i:2 * i + 2])
                 for i in range(2)]
        r = Router(specs, monitor_interval_s=0.02,
                   replica_backoff_s=0.05, degraded_poll_s=0.1)
        try:
            h = r.submit(PROMPT, greedy(24))
            stream = h.stream(timeout=120)
            toks = [next(stream)]          # first token pins a replica
            first_rep = h.replica
            plans[first_rep].kill("decode")
            toks.extend(stream)            # SAME iterator keeps going
            assert h.status == "finished"
            assert h.failovers >= 1 and h.replica != first_rep
            assert toks == want
        finally:
            r.shutdown(drain=False)
