"""``chip_smoke.py`` off the chip, and the rules it rests on.

On the CPU the script itself must FAIL (that is its contract: no TPU, no
``"ok": true``), while each of its phases, imported and called at ``tiny``
width, passes — so a broken path or argument is found here and not on
chip time. Beside it: the peak table and ``TPUPlace`` never invent a
device, the compile cache is placed by one rule, and the code is written
for the one JAX that is installed.
"""
import os
import subprocess
import sys

import jax
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.device import compile_cache, peaks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_script_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.fixture
def _monitor_off_after():
    was = monitor.enabled()
    yield
    if not was:
        monitor.disable()


TINY = dict(preset="tiny", layers=2, dtype="float32", kernels=False)
TINY_SERVE = dict(TINY, max_batch=4, page_size=8, max_pages=8,
                  segment_steps=4)


def test_train_phase_tiny():
    out = chip_smoke.train_phase(batch=2, seq=128, steps=3, **TINY)
    assert out["losses"][-1] < out["losses"][0]
    assert out["layers"] == out["layers_of"] == 2


def test_serve_phase_tiny(_monitor_off_after):
    out = chip_smoke.serve_phase(prompt_lens=(5, 20, 40, 12),
                                 new_tokens=(8, 12, 16, 10), **TINY_SERVE)
    assert out["requests_completed"] == 8
    assert out["second_round_new_compiles"] == 0
    assert out["agree_leading_tokens"] == out["of"] == 8   # fp32: exact
    assert out["int8_requests_completed"] == 2
    assert len(out["prefill_buckets"]) > 1


needs4 = pytest.mark.skipif(jax.device_count() < 4,
                            reason="needs 4 (virtual) devices")


@needs4
def test_tp_serve_phase_tiny():
    out = chip_smoke.tp_serve_phase(prompt_lens=(5, 20, 40),
                                    new_tokens=(8, 8, 8), one_chip="cpu:0",
                                    **TINY_SERVE)
    assert out["agree_leading_tokens_vs_tp1"] == [8, 8, 8]
    assert out["devices"] == [0, 1, 2, 3]


@needs4
def test_hybrid_phase_tiny():
    out = chip_smoke.hybrid_phase(preset="tiny", layers=2, dtype="float32",
                                  n=4, seq=32, rtol=1e-4)
    assert out["mesh"] == {"mp": 2, "sharding": 2, "dp": 1}


def test_kernel_check_fails_on_a_missing_kernel():
    """The routing table bites: a program whose lowered text lacks a
    listed kernel fails the enforced check (here: any CPU program)."""
    lowered = jax.jit(lambda x: x + 1).lower(1.0)
    assert chip_smoke.check_kernels("p", "decode_segment", lowered,
                                    enforce=False) == {}
    with pytest.raises(RuntimeError, match="paged_decode"):
        chip_smoke.check_kernels("p", "decode_segment", lowered,
                                 enforce=True)


# -- no invented device ------------------------------------------------------
class _FakeTpu:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_peaks_v5e_and_unknown_kind(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("PADDLE_TPU_PEAK_BYTES", raising=False)
    try:
        monkeypatch.setattr(jax, "devices",
                            lambda *a: [_FakeTpu("TPU v5 lite")])
        pk = peaks.peaks(refresh=True)
        assert (pk["peak_flops"], pk["peak_bytes_per_s"]) == (197e12, 819e9)
        assert pk["source"] == "table"
        monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu("TPU v9")])
        with pytest.raises(KeyError, match="TPU v9"):
            peaks.peaks(refresh=True)
    finally:
        monkeypatch.undo()
        assert peaks.peaks(refresh=True)["source"] == "calibrated"


def test_tpu_place_raises_without_an_accelerator():
    with pytest.raises(RuntimeError, match="no tpu devices"):
        paddle.TPUPlace(0).jax_device()
    assert paddle.CPUPlace(0).jax_device().platform == "cpu"


# -- one rule for the compile cache ------------------------------------------
def test_compile_cache_placed_from_outside(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.use_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_default_is_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        here = compile_cache.use_compile_cache()
        assert here == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == here
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    code = ("from paddle_tpu.device.compile_cache import use_compile_cache;"
            "print(use_compile_cache())")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    other = subprocess.run([sys.executable, "-c", code], cwd="/", env=dict(
        env, PYTHONPATH=REPO), capture_output=True, text=True, timeout=300)
    assert other.stdout.strip().splitlines()[-1] == here, other.stderr


def test_one_cache_dir_update_in_the_tree():
    hits = subprocess.run(
        ["grep", "-rln", "--include=*.py", "jax_compilation_cache_dir",
         "paddle_tpu", "tools", "experiments", "bench.py", "chip_smoke.py",
         "__graft_entry__.py"], cwd=REPO, capture_output=True, text=True)
    assert hits.stdout.split() == ["paddle_tpu/device/compile_cache.py"]


# -- code for the one installation -------------------------------------------
def test_no_code_for_another_jax():
    hits = subprocess.run(
        ["grep", "-rnE", r"check_rep|hasattr\(_jax, \"shard_map\"\)",
         "paddle_tpu"], cwd=REPO, capture_output=True, text=True)
    assert hits.stdout == ""
