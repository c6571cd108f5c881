"""Program-ledger observability suite (ISSUE 16).

Covers ``paddle_tpu.monitor.ledger`` end to end on CPU:

- program identity: ``program_id`` is stable across calls/processes
  (pure function of name + treedef + avals + sharding), and distinct
  shapes/dtypes/static args get distinct ids;
- the LEDGER itself: first compile captures XLA cost analysis (flops,
  bytes accessed, output bytes) plus compile seconds; steady-state
  dispatches feed the merge-exact latency digest (compile dispatches
  are counted but excluded from the digest); the per-program monitor
  series track ``rec.dispatches`` exactly;
- ownership: ``release(owner)`` drops only that owner's programs and
  retires their series; co-owned and ownerless programs survive;
- ``profile()`` / ``merge_profiles()``: derived roofline fields
  (achieved FLOP/s, arithmetic intensity, MFU, bound verdict) against
  the calibrated per-backend peak table, and the cross-replica merge
  is exact (counts add, digests merge bucket-for-bucket);
- the per-backend peak table (``paddle_tpu.device.peaks``) and the
  provenance ``env_stamp`` header;
- ``tools/bench_diff.py``: direction-aware metric classification and
  record loading across the formats it supports;
- SERVER integration: ``GET /profile`` over HTTP, Server.load()'s
  profile block, and THE acceptance scenario — a warmed mixed-feature
  run (chunked prefill + prefix hit + speculative decoding + int8 KV
  + LoRA) in which every compiled serving program appears in the
  ledger with nonzero cost analysis and a dispatch count matching the
  monitored_jit counters.
"""
import functools
import json
import urllib.request

import numpy as np
import pytest

import engine_helpers
import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.device import peaks as peaks_mod
from paddle_tpu.inference.generation import GenerationConfig
from paddle_tpu.monitor import ledger
from paddle_tpu.monitor.provenance import env_stamp
from paddle_tpu.serving import Server, serve_http

_MODEL = None


def tiny_model():
    """ONE tiny llama shared by the whole module (jit programs are
    keyed on shapes — reusing it keeps the suite to a handful of
    compiles)."""
    global _MODEL
    if _MODEL is None:
        paddle.seed(0)
        from paddle_tpu.models import LlamaForCausalLM, llama_config
        cfg = llama_config("tiny", num_hidden_layers=1)
        _MODEL = (LlamaForCausalLM(cfg), cfg)
    return _MODEL


def make_adapter(model, seed, targets=("q", "v"), rank=2, scale=0.6):
    _, shapes = model.lora_shapes(targets)
    rng = np.random.default_rng(seed)
    return {t: (rng.standard_normal((rank, d_in)).astype(np.float32)
                * scale,
                rng.standard_normal((d_out, rank)).astype(np.float32)
                * scale)
            for t, (d_in, d_out) in shapes.items()}


paged_engine = functools.partial(
    engine_helpers.paged_engine, max_batch=4, num_pages=64, page_size=4,
    max_pages=16, debug_pages=True)


@pytest.fixture()
def led():
    """Monitor + ledger armed for one test, both swept clean after."""
    monitor.enable()
    monitor.reset()
    ledger.reset()
    ledger.enable()
    yield ledger
    ledger.disable()
    ledger.reset()
    monitor.reset()
    monitor.disable()


def _series(name):
    """{program-label: value} for one of the ledger's metric names."""
    out = {}
    m = monitor.snapshot()["metrics"].get(name)
    for s in (m or {}).get("samples", []):
        key = s["labels"].get("program", "?")
        out[key] = s.get("value", s.get("count"))
    return out


def _mm(owner=None, label="lg_mm"):
    return monitor.monitored_jit(
        lambda a, b: a @ b, name=label, owner=owner)


# ---------------------------------------------------------------- id


class TestProgramId:
    def test_stable_and_distinct(self):
        a = np.zeros((4, 8), np.float32)
        b = np.zeros((8, 4), np.float32)
        pid1 = ledger.program_id("f", (a, b), {})
        pid2 = ledger.program_id("f", (a + 1, b), {})   # values ignored
        assert pid1 == pid2
        assert pid1.startswith("f:")
        # different shape, dtype, name, or static arg → different id
        assert ledger.program_id("f", (a.T, b), {}) != pid1
        assert ledger.program_id(
            "f", (a.astype(np.int32), b), {}) != pid1
        assert ledger.program_id("g", (a, b), {}) != pid1
        assert ledger.program_id("f", (a, b, 3), {}) != pid1
        assert ledger.program_id("f", (a, b), {"k": 1}) != pid1

    def test_monitored_jit_exposes_variants(self, led):
        f = _mm()
        x = np.eye(8, dtype=np.float32)
        f(x, x)
        f(np.ones((4, 8), np.float32), np.ones((8, 4), np.float32))
        pids = set(f._program_ids.values())
        assert len(pids) == 2
        assert pids == set(ledger.profile()["programs"])


# ------------------------------------------------------------ ledger


class TestLedgerRecord:
    def test_compile_then_dispatch(self, led):
        f = _mm(owner="lg_e0")
        x = np.full((16, 16), 0.5, np.float32)
        f(x, x)                          # compile dispatch
        for _ in range(3):
            f(x, x)                      # steady state
        prof = ledger.profile()
        (pid,) = list(prof["programs"])
        rec = prof["programs"][pid]
        assert rec["name"] == "lg_mm"
        assert rec["compiles"] == 1
        assert rec["dispatches"] == 4
        assert rec["compile_seconds"] > 0
        # cost analysis captured once, nonzero on CPU
        assert rec["flops"] and rec["flops"] > 0
        assert rec["bytes_accessed"] and rec["bytes_accessed"] > 0
        # the digest only sees the 3 steady-state dispatches — the
        # compile wall-clock is charged to compile_seconds instead
        assert rec["summary"]["count"] == 3
        assert rec["total_seconds"] < rec["compile_seconds"]
        # derived roofline fields present and sane
        assert rec["intensity"] > 0
        assert rec["achieved_flops_per_s"] > 0
        assert 0 <= rec["mfu"] <= 1.0
        assert rec["bound"] in ("memory-bound", "compute-bound")

    def test_series_match_dispatches(self, led):
        f = _mm(owner="lg_e0")
        x = np.ones((8, 8), np.float32)
        for _ in range(5):
            f(x, x)
        (pid,) = list(ledger.profile()["programs"])
        assert _series(ledger.DISPATCH_COUNTER)[pid] == 5
        assert _series(ledger.SECONDS_COUNTER)[pid] >= 0
        assert pid in _series(ledger.MFU_GAUGE)
        # and the per-program jit-miss counters split by program id
        miss = {}
        m = monitor.snapshot()["metrics"].get(
            "paddle_tpu_jit_cache_miss_total")
        for s in (m or {}).get("samples", []):
            miss[s["labels"]["program"]] = s["value"]
        assert miss.get(pid) == 1

    def test_disabled_is_invisible(self):
        ledger.disable()
        ledger.reset()
        f = _mm()
        x = np.ones((4, 4), np.float32)
        f(x, x)
        assert ledger.profile()["programs"] == {}


class TestOwnership:
    def test_release_scoped(self, led):
        fa = _mm(owner="lg_a", label="lg_fa")
        fb = _mm(owner="lg_b", label="lg_fb")
        fn = _mm(owner=None, label="lg_fn")
        x = np.ones((8, 8), np.float32)
        fa(x, x); fb(x, x); fn(x, x)
        assert len(ledger.profile()["programs"]) == 3
        assert len(ledger.owned_programs("lg_a")) == 1
        dropped = ledger.release("lg_a")
        assert dropped == 1
        progs = ledger.profile()["programs"]
        names = {r["name"] for r in progs.values()}
        assert names == {"lg_fb", "lg_fn"}          # ownerless survives
        assert ledger.owned_programs("lg_a") == []
        # released program's series are retired too
        live = set(_series(ledger.DISPATCH_COUNTER))
        assert live == set(progs)

    def test_coowned_survives_single_release(self, led):
        f = _mm(owner="lg_a", label="lg_sh")
        x = np.ones((4, 4), np.float32)
        f(x, x)
        (pid,) = list(ledger.profile()["programs"])
        # second owner touches the same program id
        ledger.record(pid, "lg_sh", "lg_b", f._jitted, (x, x), {},
                      1e-4, False)
        assert ledger.release("lg_a") == 0           # still co-owned
        assert pid in ledger.profile()["programs"]
        assert ledger.release("lg_b") == 1
        assert ledger.profile()["programs"] == {}


class TestProfileMerge:
    def test_owner_filter_and_top_k(self, led):
        fa = _mm(owner="lg_a", label="lg_fa")
        fb = _mm(owner="lg_b", label="lg_fb")
        x = np.ones((8, 8), np.float32)
        fa(x, x); fb(x, x)
        only_a = ledger.profile(owners=["lg_a"])
        assert {r["name"] for r in only_a["programs"].values()} \
            == {"lg_fa"}
        prof = ledger.profile(top_k=1)
        assert len(prof["top"]) == 1
        # top_k truncates the ranking only — programs stay complete so
        # cross-replica merges never lose rows
        assert len(prof["programs"]) == 2
        assert prof["peaks"]["peak_flops"] > 0

    def test_merge_is_exact(self, led):
        f = _mm(label="lg_m")
        x = np.ones((8, 8), np.float32)
        f(x, x); f(x, x); f(x, x)
        shard = ledger.profile()
        merged = ledger.merge_profiles([shard, shard, None, {}])
        (pid,) = list(merged["programs"])
        rec, one = merged["programs"][pid], shard["programs"][pid]
        assert rec["dispatches"] == 2 * one["dispatches"]
        assert rec["compiles"] == 2 * one["compiles"]
        assert rec["summary"]["count"] == 2 * one["summary"]["count"]
        assert rec["summary"]["max"] == one["summary"]["max"]
        assert rec["flops"] == one["flops"]
        assert merged["peaks"] == shard["peaks"]


# ---------------------------------------------- peaks + provenance


class TestPeaksAndProvenance:
    def test_cpu_calibration_record(self):
        pk = peaks_mod.peaks()
        for key in ("device_kind", "platform", "peak_flops",
                    "peak_bytes_per_s", "machine_balance", "source"):
            assert key in pk
        assert pk["peak_flops"] > 0
        assert pk["peak_bytes_per_s"] > 0
        assert pk["machine_balance"] == pytest.approx(
            pk["peak_flops"] / pk["peak_bytes_per_s"])

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "123e12")
        pk = peaks_mod.peaks(refresh=True)
        assert pk["peak_flops"] == pytest.approx(123e12)
        monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS")
        assert peaks_mod.peaks(refresh=True)["peak_flops"] != \
            pytest.approx(123e12)

    def test_env_stamp(self):
        st = env_stamp()
        for key in ("jax", "python", "backend", "device_kind",
                    "device_count", "hostname", "pid"):
            assert key in st
        # extras merge into a copy, never the cached stamp
        st2 = env_stamp(extra={"arm": "on"})
        assert st2["arm"] == "on"
        assert "arm" not in env_stamp()


# -------------------------------------------------------- bench_diff


class TestBenchDiff:
    def test_classification_directions(self):
        from tools.bench_diff import classify
        assert classify("serve_tpot_p50_ms", "ms") == "lower"
        assert classify("serve_throughput", "tok/s") == "higher"
        assert classify("bench_mfu", "") == "higher"
        assert classify("compile_seconds", "s") == "lower"

    def test_regression_and_clean_exit(self, tmp_path, capsys):
        from tools.bench_diff import main
        old = tmp_path / "old.jsonl"
        new = tmp_path / "new.jsonl"
        base = [{"metric": "serve_tpot_p50_ms", "value": 10.0,
                 "unit": "ms"},
                {"metric": "serve_throughput", "value": 100.0,
                 "unit": "tok/s"}]
        old.write_text("\n".join(json.dumps(r) for r in base))
        new.write_text("\n".join(json.dumps(r) for r in base))
        assert main([str(old), str(new)]) == 0
        worse = [dict(base[0], value=12.0), base[1]]
        new.write_text("\n".join(json.dumps(r) for r in worse))
        assert main([str(old), str(new)]) == 1
        out = capsys.readouterr().out
        assert "serve_tpot_p50_ms" in out
        # higher-better direction: a throughput DROP regresses too
        slower = [base[0], dict(base[1], value=70.0)]
        new.write_text("\n".join(json.dumps(r) for r in slower))
        assert main([str(old), str(new)]) == 1

    def test_wrapper_and_baseline_formats(self, tmp_path):
        from tools.bench_diff import load_records, main
        tail = "\n".join([
            "noise line",
            json.dumps({"metric": "m1", "value": 1.0, "unit": "s"}),
            json.dumps({"metric": "bench_env", "backend": "cpu"}),
        ])
        wrap = tmp_path / "BENCH_r01.json"
        wrap.write_text(json.dumps(
            {"n": 1, "cmd": "x", "rc": 0, "tail": tail}))
        recs, env = load_records(str(wrap))
        assert [r["metric"] for r in recs] == ["m1"]
        assert env and env.get("backend") == "cpu"
        # --write-baseline → --baseline round trip
        basefile = tmp_path / "baseline.json"
        assert main([str(wrap), "--write-baseline",
                     str(basefile)]) == 0
        assert main([str(wrap), "--baseline", str(basefile)]) == 0
        recs2, _ = load_records(str(basefile))
        assert [r["metric"] for r in recs2] == ["m1"]


# --------------------------------------------------- server surface


def _drain(handles):
    return [h.result(timeout=120) for h in handles]


class TestServerProfile:
    def test_acceptance_mixed_feature_run(self, led):
        """THE acceptance scenario: a warmed mixed-feature run —
        chunked prefill + prefix hit + speculative decoding + int8 KV
        + LoRA — leaves every compiled serving program in the ledger
        with nonzero cost analysis and a dispatch count matching the
        monitored_jit counters."""
        model, cfg = tiny_model()
        eng = paged_engine(model, prefill_chunk=8, prefix_cache=True,
                           kv_dtype="int8", draft_k=4,
                           lora_capacity=2, lora_rank=2,
                           lora_targets=("q", "v"))
        eng.load_adapter("la", make_adapter(model, 7))
        srv = Server(eng, segment_steps=2)
        rng = np.random.RandomState(0)
        shared = rng.randint(0, cfg.vocab_size, (12,)).astype(np.int32)

        def gen(**kw):
            return GenerationConfig(max_new_tokens=6,
                                    eos_token_id=None, **kw)

        try:
            hs = [
                # long prompt → chunked prefill; second one hits the
                # shared-prefix cache
                srv.submit(np.concatenate([shared, shared[:4]]), gen()),
                srv.submit(np.concatenate([shared, shared[2:6]]),
                           gen()),
                srv.submit(shared[:6],
                           gen(speculative=True, draft_k=4)),
                srv.submit(shared[:8], gen(adapter="la")),
            ]
            _drain(hs)
            # warmed: replay the same mix so steady-state dispatches
            # exist beyond the compile calls
            hs = [srv.submit(np.concatenate([shared, shared[:4]]),
                             gen()),
                  srv.submit(shared[:6],
                             gen(speculative=True, draft_k=4)),
                  srv.submit(shared[:8], gen(adapter="la"))]
            _drain(hs)

            prof = srv.profile()
            progs = prof["programs"]
            assert progs, "mixed-feature run registered no programs"
            # the feature mix actually exercised distinct programs
            names = {r["name"] for r in progs.values()}
            assert any("prefill" in n for n in names)
            assert any("spec" in n for n in names)
            counter = _series(ledger.DISPATCH_COUNTER)
            for pid, rec in progs.items():
                assert rec["flops"] and rec["flops"] > 0, \
                    f"{rec['name']}: no cost analysis"
                assert rec["bytes_accessed"] and \
                    rec["bytes_accessed"] > 0
                assert rec["compiles"] >= 1
                assert counter[pid] == rec["dispatches"], \
                    f"{rec['name']}: counter != ledger"
            # Server.load() carries the compact profile block
            load = srv.load()
            assert load["profile"]["programs"] == len(progs)
            assert load["profile"]["top"]
        finally:
            srv.shutdown()
            eng.close()
        # engine retirement swept the ledger and its series
        assert ledger.profile()["programs"] == {}
        assert _series(ledger.DISPATCH_COUNTER) == {}

    def test_http_get_profile(self, led):
        model, cfg = tiny_model()
        eng = paged_engine(model)
        srv = Server(eng, segment_steps=2)
        httpd = serve_http(srv, port=0)
        try:
            h = srv.submit(np.arange(6, dtype=np.int32) % cfg.vocab_size,
                           GenerationConfig(max_new_tokens=4,
                                            eos_token_id=None))
            h.result(timeout=120)
            port = httpd.server_address[1]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/profile") as r:
                doc = json.loads(r.read())
            assert doc["programs"]
            assert doc["peaks"]["peak_flops"] > 0
        finally:
            httpd.shutdown()
            srv.shutdown()
            eng.close()
