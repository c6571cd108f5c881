"""The chip's compiler, asked before the chip: AOT compiles for a described
``TPU v5 lite`` (no device attached) of the Pallas kernels the trainer and
the paged server reach, at real widths. Interpret mode cannot see what the
compiler refuses — a block that breaks the (8, 128) tiling, a kernel that
overruns VMEM — and each of the repairs below was such a refusal.

Kernels pick interpret mode from ``jax.devices()[0]``, which is the CPU
here, so the tests steer the three ``_interpret()`` helpers. The persistent
compile cache is off around the compiles: an entry written for a described
device cannot be read back without one, and the next compile would warn.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import flash_attention_kernel as fk
from paddle_tpu.ops import gated_delta_rule as gdn
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops import pallas_kernels as pk

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to ask
        pytest.skip(f"the v5e topology cannot be described: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _compiled_not_interpreted(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    for mod in (fk, pa, pk, gdn):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    cache = jax.config.jax_enable_compilation_cache
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the conftest's "highest" is for CPU parity tests; the chip runs the
    # default, and Mosaic refuses a bf16 dot at fp32 precision
    jax.config.update("jax_default_matmul_precision", None)
    yield
    jax.config.update("jax_default_matmul_precision", precision)
    jax.config.update("jax_enable_compilation_cache", cache)
    compilation_cache.reset_cache()


def _compile(chip, fn, *shapes):
    """Compile ``fn`` for the chip; returns the kernels in the program."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count("tpu_custom_call")


def test_flash_fwd_bwd(chip):
    def loss(q, k, v):
        return fk.flash_attention_bhsd(q, k, v, causal=True).astype(
            F32).sum()

    qkv = ((4, 16, 2048, 128), BF16)
    assert _compile(chip, jax.grad(loss, argnums=(0, 1, 2)),
                    qkv, qkv, qkv) == 3   # fwd, bwd dq, bwd dk/dv


@pytest.mark.parametrize("hkv", [32, 8])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode(chip, hkv, kv_dtype):
    b, h, d, pages, ps, maxp = 8, 32, 128, 512, 16, 64
    pool = ((pages, ps, hkv, d), I8 if kv_dtype == "int8" else BF16)
    shapes = [((b, h, d), BF16), pool, pool, ((b, maxp), I32), ((b,), I32)]
    if kv_dtype == "int8":   # per-(page, kv_head) scales
        shapes += [((pages, hkv), F32)] * 2
    assert _compile(chip, pa.paged_decode_mha, *shapes) == 1


def test_paged_decode_head_sharded_over_four_chips(topo):
    """``tp=``: the kernel under ``shard_map`` over the head axis of a
    four-chip mesh (Mistral's 32 / 8 heads: 8 / 2 a chip), pools sharded
    on KV heads; no chip gathers a pool."""
    import functools

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices).reshape(4), ("mp",))
    b, h, hkv, d, pages, ps, maxp = 32, 32, 8, 128, 2048, 16, 64

    def arg(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    pool = arg((pages, ps, hkv, d), BF16, None, None, "mp", None)
    text = jax.jit(functools.partial(
        pa.paged_decode_mha, tp=(mesh, "mp"))).lower(
            arg((b, h, d), BF16, None, "mp", None), pool, pool,
            arg((b, maxp), I32), arg((b,), I32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "all-gather" not in text


@pytest.mark.parametrize("heads", [16, 32])
def test_fused_rope(chip, heads):
    assert _compile(chip, pk.fused_rope, ((4, 2048, heads, 128), BF16),
                    ((2048, 64), BF16), ((2048, 64), BF16)) == 1


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_rms_norm(chip, dtype):
    assert _compile(chip, pk.rms_norm, ((4, 2048, 4096), dtype),
                    ((4096,), dtype)) == 1


def test_decode_mha(chip):
    kv = ((8, 2048, 32, 128), BF16)
    assert _compile(chip, pk.decode_mha, ((8, 32, 128), BF16), kv, kv,
                    ((8,), I32)) == 1


def test_fused_linear_param_grad_add(chip):
    # the 1b3 FFN: 5504 = 43 x 128 has no power-of-two tile
    assert _compile(chip, pk.fused_linear_param_grad_add,
                    ((8192, 2048), BF16), ((8192, 5504), BF16),
                    ((2048, 5504), F32)) == 1


def test_unrepairable_width_raises_by_name(chip):
    """A width with no 128-multiple tile is refused by name on the chip —
    no upcast, no padding behind the caller's back."""
    with pytest.raises(ValueError, match="fused_linear_param_grad_add.*128"):
        _compile(chip, pk.fused_linear_param_grad_add, ((256, 100), BF16),
                 ((256, 256), BF16), ((100, 256), F32))


# -- the sparse-expert, window-attention decoder's kernels at its widths ----------
def test_window_flash_fwd(chip):
    """The widest prefill bucket of a sliding layer: 32 query / 4 KV heads
    x 128, 8192 positions, window 2048."""
    def fwd(q, k, v):
        return fk.flash_attention_bhsd(q, k, v, causal=True, window=2048)

    assert _compile(chip, fwd, ((1, 32, 8192, 128), BF16),
                    ((1, 4, 8192, 128), BF16),
                    ((1, 4, 8192, 128), BF16)) == 1


@pytest.mark.parametrize("window, cols", [(None, 544), (2048, 129)],
                         ids=["full", "ring"])
def test_paged_decode_two_geometries(chip, window, cols):
    """32 rows: a full layer's table of 544 pages, a window layer's ring
    of 129."""
    import functools

    b, h, hkv, d, ps = 32, 32, 4, 128, 16
    pool = ((b * cols, ps, hkv, d), BF16)
    fn = functools.partial(pa.paged_decode_mha, window=window)
    assert _compile(chip, fn, ((b, h, d), BF16), pool, pool,
                    ((b, cols), I32), ((b,), I32)) == 1


@pytest.mark.parametrize("rows", [256, 4096, 65536],
                         ids=["decode", "bucket512", "bucket8192"])
def test_grouped_matmul_expert_products(chip, rows, monkeypatch):
    """The three expert products (128 experts, 2048 -> 1024 -> 2048) of a
    decode step (32 rows x 8 choices) and of the narrowest and the widest
    prefill bucket, with the tiles ``_gmm_tiling`` picks."""
    from paddle_tpu.ops import pallas as ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)

    def up(x, w, sizes):
        return ops.grouped_matmul(x, w, sizes, preferred_element_type=BF16)

    def down(x, w, sizes):
        return ops.grouped_matmul(x, w, sizes, preferred_element_type=F32)

    assert _compile(chip, up, ((rows, 2048), BF16),
                    ((128, 2048, 1024), BF16), ((128,), I32)) == 1
    assert _compile(chip, down, ((rows, 1024), BF16),
                    ((128, 1024, 2048), BF16), ((128,), I32)) == 1


# -- the latent-attention, sparse-selection decoder's kernels at its widths -------
def test_dsa_index_scores(chip):
    """16 rows, 64 indexer heads, a table of 1088 pages of 16 keys of
    128."""
    import functools

    from paddle_tpu.ops import sparse_latent_attention as sla

    fn = functools.partial(sla.dsa_index_scores, interpret=False)
    assert _compile(chip, fn, ((16, 64, 128), BF16), ((16, 64), F32),
                    ((17408, 16, 128), BF16), ((16, 1088), I32),
                    ((16,), I32)) == 1


@pytest.mark.parametrize("masked, s", [
    (True, 4096), (True, 8192), (True, 16384), (True, 17408),
    (False, 2048), (False, 16384)],
    ids=lambda v: {True: "selected", False: "causal"}.get(v, str(v)))
def test_selected_attention_prefill(chip, masked, s):
    """A group of 16 heads of each bucket the latent cell runs (the
    selection's [S, S] mask past ``index_topk``, causal alone in the 2,048
    bucket), keys 192 wide, values 128: every tiling compiles."""
    import functools

    from paddle_tpu.ops import sparse_latent_attention as sla

    fn = functools.partial(sla.selected_attention, interpret=False)
    qk, v = ((16, s, 192), BF16), ((16, s, 128), BF16)
    if masked:
        assert _compile(chip, fn, qk, qk, v, ((s, s), I8), ((), I32)) == 1
    else:
        assert _compile(
            chip, lambda q, k, v, last: fn(q, k, v, None, last),
            qk, qk, v, ((), I32)) == 1


@pytest.mark.parametrize("s", [16384, 17408])
def test_latent_prefill_head_groups_under_the_row_loop(chip, s, monkeypatch):
    """A layer's eight head groups of the latent model's prefill at the
    published widths, in the widest bucket of the cell and the engine's
    last width (17 blocks of 1,024): a group's q, k and v are made for the
    live row blocks (``models/_live_rows``: a ``while`` whose outputs go
    into ``[16, S, 192]`` buffers along their second axis), then the
    kernel takes them as they lie."""
    from paddle_tpu.models import deepseek_v32 as dsv
    from paddle_tpu.nn import initializer
    from paddle_tpu.ops import sparse_latent_attention as sla

    # the module holds its own name for paged_attention's helper
    monkeypatch.setattr(sla, "_interpret", lambda: False)

    def shape_only(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))

    initializer.set_global_initializer(shape_only, shape_only)
    try:
        attn = dsv.DeepseekV32Attention(dsv.DeepseekV32Config(
            dtype="bfloat16"))
    finally:
        initializer.set_global_initializer(None, None)
    assert s % dsv.row_block(s, dsv.PREFILL_ROW_BLOCK) == 0 \
        and dsv.row_block(s, dsv.PREFILL_ROW_BLOCK) >= 512

    def groups(cq, row, mask, wqb, wkvb, last):
        return attn._attend_expanded(cq, row, mask, jnp.arange(s), wqb,
                                     wkvb, last)

    args = [jax.ShapeDtypeStruct(sh, d, sharding=chip) for sh, d in (
        ((s, 1536), BF16), ((s, 640), BF16), ((s, s), jnp.bool_),
        ((1536, 128 * 192), BF16), ((512, 128 * 256), BF16), ((), I32))]
    compiled = jax.jit(groups).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and " while(" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


def _assert_no_remainder_tile(m, k, n):
    """The tiles ``grouped_matmul`` takes at (m, k, n) divide k and n: the
    kernel then masks no ragged last tile."""
    from paddle_tpu.ops.pallas import _gmm_tiling

    _, tk, tn = _gmm_tiling(m, k, n)
    assert k % tk == 0 and n % tn == 0, (m, k, n, tk, tn)


def test_grouped_matmul_held_experts(chip, monkeypatch):
    """The held experts' products (16 experts, 7168 -> 2048 -> 7168) of a
    decode step (16 rows x 8 choices) and of a prefill's token block."""
    from paddle_tpu.ops import pallas as ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    for rows in (128, 16384):
        _assert_no_remainder_tile(rows, 7168, 2048)
        _assert_no_remainder_tile(rows, 2048, 7168)
        assert _compile(
            chip, lambda x, w, n: ops.grouped_matmul(
                x, w, n, preferred_element_type=BF16),
            ((rows, 7168), BF16), ((16, 7168, 2048), BF16),
            ((16,), I32)) == 1
        assert _compile(
            chip, lambda x, w, n: ops.grouped_matmul(
                x, w, n, preferred_element_type=F32),
            ((rows, 2048), BF16), ((16, 2048, 7168), BF16),
            ((16,), I32)) == 1


@pytest.mark.parametrize("s", [256, 4096])
def test_gdn_chunk_prefill(chip, s):
    """The chunked scan of one linear layer's admission at the hybrid
    cell's widths (30 heads x 96 x 192), the cell's smallest and widest
    buckets."""
    qk, v = ((1, s, 30, 96), F32), ((1, s, 30, 192), BF16)
    gate = ((1, s, 30), F32)
    assert _compile(chip, gdn.gdn_chunk_prefill, qk, qk, v, gate, gate,
                    ((), I32)) == 1


def test_gdn_decode_step(chip):
    """The one-token update of 48 rows' states in place."""
    state, qk = ((48, 30, 96, 192), F32), ((48, 30, 96), F32)
    assert _compile(chip, gdn.gdn_decode_step, state, qk, qk,
                    ((48, 30, 192), F32), ((48, 30), F32), ((48, 30), F32),
                    ((48,), jnp.bool_)) == 1


def test_attention_kernels_at_thirty_heads(chip):
    """As many KV heads as query heads, a count that fills no tile: the
    flash forward takes 30; ``paged_decode`` copies a page only whole tiles
    of the head axis wide, so the hybrid model stores 32 (its
    ``cache_kv_heads``) and the kernel is asked for that."""
    qkv = ((1, 30, 4096, 128), BF16)
    assert _compile(
        chip, lambda q, k, v: fk.flash_attention_bhsd(q, k, v, causal=True),
        qkv, qkv, qkv) == 1
    pool = ((4096, 16, 32, 128), BF16)
    assert _compile(
        chip, lambda q, k, v, t, n: pa.paged_decode_mha(q, k, v, t, n),
        ((48, 32, 128), BF16), pool, pool, ((48, 320), I32),
        ((48,), I32)) == 1
    with pytest.raises(Exception, match="aligned to tiling"):
        pool = ((4096, 16, 30, 128), BF16)
        _compile(
            chip, lambda q, k, v, t, n: pa.paged_decode_mha(q, k, v, t, n),
            ((48, 30, 128), BF16), pool, pool, ((48, 320), I32),
            ((48,), I32))


# -- the decoder whose router reads the attention's input, at its widths ----------
@pytest.mark.parametrize("window, cols", [(None, 512), (4096, 257)],
                         ids=["full", "ring"])
def test_paged_decode_seven_query_heads_a_kv_head(chip, window, cols):
    """24 rows of 28 query heads over 4 KV heads x 128: a full layer's
    table of 512 pages, a window layer's ring of 257 (4,096 / 16 + 1)."""
    import functools

    b, h, hkv, d, ps = 24, 28, 4, 128, 16
    pool = ((b * cols, ps, hkv, d), BF16)
    fn = functools.partial(pa.paged_decode_mha, window=window)
    assert _compile(chip, fn, ((b, h, d), BF16), pool, pool,
                    ((b, cols), I32), ((b,), I32)) == 1


@pytest.mark.parametrize("hq, hkv, window, cols, kv_dtype", [
    (28, 4, 4096, 257, "int8"), (32, 32, None, 320, "bf16"),
    (32, 32, None, 320, "int8")],
    ids=["seven_a_kv_head_ring_int8", "thirty_two_stored", "thirty_two_int8"])
def test_paged_decode_straight_line_copies(chip, hq, hkv, window, cols,
                                           kv_dtype):
    """A block's copies as straight-line code, one wait a stream, no
    bounds checks: 16 pages a block at 4 KV heads, 2 at 32, int8 pools
    with their scales."""
    import functools

    b, d, ps = 24, 128, 16
    pool = ((b * cols, ps, hkv, d), I8 if kv_dtype == "int8" else BF16)
    shapes = [((b, hq, d), BF16), pool, pool, ((b, cols), I32), ((b,), I32)]
    if kv_dtype == "int8":
        shapes += [((b * cols, hkv), F32)] * 2
    fn = functools.partial(pa.paged_decode_mha, window=window)
    assert _compile(chip, fn, *shapes) == 1


def test_window_flash_fwd_at_seven_query_heads_a_kv_head(chip):
    """The widest prefill bucket of a window layer: 28 query / 4 KV heads
    x 128, 6,144 positions, window 4,096."""
    def fwd(q, k, v):
        return fk.flash_attention_bhsd(q, k, v, causal=True, window=4096)

    assert _compile(chip, fwd, ((1, 28, 6144, 128), BF16),
                    ((1, 4, 6144, 128), BF16),
                    ((1, 4, 6144, 128), BF16)) == 1


@pytest.mark.parametrize("rows", [144, 36864], ids=["decode", "bucket6144"])
def test_grouped_matmul_sixty_four_experts(chip, rows, monkeypatch):
    """The three expert products (64 experts, 2560 -> 768 -> 2560) of a
    decode step (24 rows x 6 choices) and of the widest prefill bucket
    (6,144 tokens x 6)."""
    from paddle_tpu.ops import pallas as ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    _assert_no_remainder_tile(rows, 2560, 768)
    _assert_no_remainder_tile(rows, 768, 2560)
    assert _compile(
        chip, lambda x, w, n: ops.grouped_matmul(
            x, w, n, preferred_element_type=BF16),
        ((rows, 2560), BF16), ((64, 2560, 768), BF16), ((64,), I32)) == 1
    assert _compile(
        chip, lambda x, w, n: ops.grouped_matmul(
            x, w, n, preferred_element_type=F32),
        ((rows, 768), BF16), ((64, 768, 2560), BF16), ((64,), I32)) == 1


# -- latent attention beside grouped linear-attention layers, at its widths ----
def test_paged_latent_decode(chip):
    """The dense latent decode of 32 rows: 64 absorbed heads against one
    640-wide row a position, a table of 2,176 pages of 16."""
    import functools

    from paddle_tpu.ops import sparse_latent_attention as sla

    fn = functools.partial(sla.paged_latent_decode, scale=0.105304,
                           interpret=False)
    assert _compile(chip, fn, ((32, 64, 512), BF16), ((32, 64, 64), BF16),
                    ((69632, 16, 640), BF16), ((32, 2176), I32),
                    ((32,), I32)) == 1


@pytest.mark.parametrize("s", [4096, 32768])
def test_gdn_chunk_prefill_grouped(chip, s):
    """The chunked scan with 64 value heads over 32 key heads of 128."""
    qk, v = ((1, s, 32, 128), F32), ((1, s, 64, 128), BF16)
    gate = ((1, s, 64), F32)
    assert _compile(chip, gdn.gdn_chunk_prefill, qk, qk, v, gate, gate,
                    ((), I32)) == 1


def test_gdn_decode_step_grouped(chip):
    """The one-token update of 32 rows' 64 states, 2 a key head."""
    state, qk = ((32, 64, 128, 128), F32), ((32, 32, 128), F32)
    assert _compile(chip, gdn.gdn_decode_step, state, qk, qk,
                    ((32, 64, 128), F32), ((32, 64), F32), ((32, 64), F32),
                    ((32,), jnp.bool_)) == 1
