"""``_gmm_tiling`` at the expert shapes of the benchmark's configurations:
tiles that divide the expert's widths (no remainder tile for the megablox
kernel to mask), blocks that fit the kernel's scoped VMEM, and the tiles
the shapes that already divided had before."""
import json
import os

import pytest

from paddle_tpu.ops.pallas import GMM_VMEM_BYTES, _gmm_tiling, _gmm_vmem_bytes

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                       "configs")

# decode (rows x top-k of a step) and prefill (token blocks x top-k) rows
ROWS = [16, 128, 144, 256, 4095, 4096, 12288, 16384, 36864, 49152, 65536]


def expert_shapes(name):
    """(k, n) of the gate/up and of the down products of a configuration's
    routed experts."""
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        cfg = json.load(f)
    h = cfg["hidden_size"]
    w = cfg.get("moe_intermediate_size") or cfg["moe_ffn_hidden_size"]
    return [(h, w), (w, h)]


SHAPES = [(name, k, n)
          for name in ("trinity-mini", "smallthinker-21b-a3b",
                       "deepseek-v3.2")
          for k, n in expert_shapes(name)]


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("name, k, n", SHAPES,
                         ids=[f"{s[0]}-{s[1]}x{s[2]}" for s in SHAPES])
def test_tiles_divide_and_fit(name, k, n, m):
    tm, tk, tn = _gmm_tiling(m, k, n)
    assert tm == (256 if m >= 4096 else 128)
    assert tk % 128 == 0 and k % tk == 0, (tk, k)
    assert tn % 128 == 0 and n % tn == 0, (tn, n)
    assert _gmm_vmem_bytes(tm, tk, tn) <= GMM_VMEM_BYTES


@pytest.mark.parametrize("m", ROWS)
def test_trinity_keeps_its_tiles(m):
    tm = 256 if m >= 4096 else 128
    assert _gmm_tiling(m, 2048, 1024) == (tm, 2048, 1024)
    assert _gmm_tiling(m, 1024, 2048) == (tm, 1024, 1024)


def test_smallthinker_and_latent_tiles():
    """The widths whose old tiles left a remainder: 2,560 (SmallThinker's
    hidden) and 7,168 (the latent model's)."""
    assert _gmm_tiling(144, 2560, 768)[1:] == (2560, 768)
    assert _gmm_tiling(36864, 2560, 768)[1:] == (2560, 768)
    assert _gmm_tiling(144, 768, 2560)[1:] == (768, 2560)
    assert _gmm_tiling(36864, 768, 2560)[1:] == (768, 2560)
    assert _gmm_tiling(128, 7168, 2048)[1:] == (1792, 1024)
    assert _gmm_tiling(16384, 2048, 7168)[1:] == (2048, 1024)


def test_vmem_formula():
    """Double-buffered bf16 rows and weights, a double-buffered float32
    output and the float32 accumulator: (128, 2048, 1024) is 10.5 MiB."""
    assert _gmm_vmem_bytes(128, 2048, 1024) == (
        2 * 2 * (128 * 2048 + 2048 * 1024) + 2 * 4 * 128 * 1024
        + 4 * 128 * 1024)
    assert _gmm_vmem_bytes(128, 2048, 1024) == 10.5 * 2**20
    assert GMM_VMEM_BYTES == 16 * 2**20


def test_a_width_without_a_divisor_keeps_the_old_tile():
    """k not a multiple of 128: no tile divides it, the old one stays."""
    assert _gmm_tiling(128, 2100, 1000) == (128, 2048, 1000)
