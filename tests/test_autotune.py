"""Kernel autotune cache tests (reference analog: test/legacy_test/
test_switch_autotune.py + phi/kernels/autotune/cache_test.cc)."""
import json
import os

import numpy as np
import pytest

from paddle_tpu.framework.flags import set_flags
from paddle_tpu.ops import autotune


@pytest.fixture(autouse=True)
def _isolated_cache():
    # a fresh process-wide table; nothing is persisted without a path
    old = autotune._GLOBAL
    autotune._GLOBAL = autotune.AutoTuneCache()
    autotune._loaded[0] = True
    yield
    autotune._GLOBAL = old


class TestCache:
    def test_lookup_miss_then_hit(self):
        c = autotune.AutoTuneCache()
        assert c.lookup("op", (1, 2)) is None
        c.record("op", (1, 2), {"block": 128})
        assert c.lookup("op", (1, 2)) == {"block": 128}
        assert c.stats["hits"] == 1 and c.stats["misses"] == 1

    def test_persistence_roundtrip(self, tmp_path):
        p = str(tmp_path / "t.json")
        c = autotune.AutoTuneCache(path=p)
        c.record("flash", ("sq", 2048), {"block_q": 1024, "ms": 0.9})
        c.save()
        c2 = autotune.AutoTuneCache(path=p)
        assert c2.load()
        assert c2.lookup("flash", ("sq", 2048))["block_q"] == 1024

    def test_flag_gates_lookup(self):
        autotune.record("op", (3,), {"x": 1})
        set_flags({"FLAGS_use_autotune": False})
        try:
            assert autotune.lookup("op", (3,)) is None
        finally:
            set_flags({"FLAGS_use_autotune": True})
        assert autotune.lookup("op", (3,)) == {"x": 1}


class TestTune:
    def test_tune_picks_fastest_and_records(self):
        import time

        calls = []

        def runner(cfg):
            calls.append(cfg["n"])
            time.sleep(0.001 * cfg["n"])

        best = autotune.tune("toy", ("s", 1), [{"n": 3}, {"n": 1}, {"n": 2}],
                             runner, warmup=0, iters=1, save=False)
        assert best["n"] == 1
        assert autotune.lookup("toy", ("s", 1))["n"] == 1

    def test_tune_skips_failing_candidates(self):
        def runner(cfg):
            if cfg["n"] == 1:
                raise RuntimeError("does not fit VMEM")

        best = autotune.tune("toy2", ("s", 2), [{"n": 1}, {"n": 2}],
                             runner, warmup=0, iters=1, save=False)
        assert best["n"] == 2

    def test_tune_all_fail_raises(self):
        def runner(cfg):
            raise RuntimeError("boom")

        with pytest.raises(RuntimeError, match="no candidate"):
            autotune.tune("toy3", ("s",), [{"n": 1}], runner, save=False)


class TestFlashIntegration:
    def test_kernel_consults_cache(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.flash_attention_kernel import flash_attention_bhsd

        # record a signature-matching config with a recognizable block size
        sig = autotune.flash_signature(128, 128, 32, True, "float32")
        autotune.record("flash_attention", sig,
                        {"block_q": 64, "block_k": 64, "ms": 0.1})
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(1, 2, 128, 32), jnp.float32)
        k = jnp.asarray(rng.randn(1, 2, 128, 32), jnp.float32)
        v = jnp.asarray(rng.randn(1, 2, 128, 32), jnp.float32)
        out = flash_attention_bhsd(q, k, v, causal=True)
        assert out.shape == q.shape
        assert autotune.get_cache().stats["hits"] >= 1

    def test_tune_flash_end_to_end_cpu(self):
        # interpret-mode is slow; tiniest shapes, fwd only, 2 candidates
        best = autotune.tune_flash(1, 1, 128, 16, causal=True,
                                   dtype="float32",
                                   candidates=((128, 128), (64, 64)),
                                   grad=False)
        assert "block_q" in best and "ms" in best
        assert autotune.lookup(
            "flash_attention",
            autotune.flash_signature(128, 128, 16, True,
                                     "float32")) is not None
