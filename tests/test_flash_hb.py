"""Head-batched BSHD flash kernel numerics (PERF.md headroom #2).

Must match the dense reference attention in forward AND gradients —
same contract as tests/test_flash_attention.py for the per-head kernel.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.flash_attention_hb import (flash_attention_bshd_hb,
                                               supports_hb)

# The hb kernel's ORIGINAL batched-3D-dot form was Mosaic-rejected on-chip
# ("Bad lhs type", 2026-07-31); it has been restructured to per-head 2D
# dots but that form is unverified on hardware, so supports_hb refuses
# device routing (and this module skips on device) unless the
# PADDLE_TPU_HB_ON_DEVICE=1 escape hatch opts in.
import os

from paddle_tpu.ops.flash_attention_kernel import _interpret

pytestmark = pytest.mark.skipif(
    not _interpret() and os.environ.get("PADDLE_TPU_HB_ON_DEVICE") != "1",
    reason="hb kernel not hardware-verified (original batched-dot form "
           "was Mosaic-rejected; set PADDLE_TPU_HB_ON_DEVICE=1 to test "
           "the per-head-unrolled restructure on-chip)")


def ref_attention(q, k, v, causal, offset):
    # [B, S, H, D] dense reference
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / np.sqrt(q.shape[-1])
    if causal:
        iq = jnp.arange(q.shape[1])[:, None]
        ik = jnp.arange(k.shape[1])[None, :]
        s = jnp.where((ik <= iq + offset)[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf)


def make(b=2, sq=32, sk=32, h=4, d=8, seed=0):
    rng = np.random.RandomState(seed)
    q = rng.randn(b, sq, h, d).astype(np.float32)
    k = rng.randn(b, sk, h, d).astype(np.float32)
    v = rng.randn(b, sk, h, d).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


class TestForward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = make()
        out = flash_attention_bshd_hb(q, k, v, causal=causal)
        ref = ref_attention(q, k, v, causal, 0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_cross_lengths_bottom_right(self):
        q, k, v = make(sq=16, sk=32)
        out = flash_attention_bshd_hb(q, k, v, causal=True)
        ref = ref_attention(q, k, v, True, 16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_supports_gate(self):
        assert supports_hb((2, 32, 4, 8), (2, 32, 4, 8), 0.0)
        assert not supports_hb((2, 32, 8, 8), (2, 32, 4, 8), 0.0)  # GQA
        assert not supports_hb((2, 32, 4, 8), (2, 32, 4, 8), 0.1)  # dropout


class TestBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_reference(self, causal):
        q, k, v = make(b=1, sq=16, sk=16, h=2, d=8)

        def f_ours(q, k, v):
            return jnp.sum(flash_attention_bshd_hb(q, k, v, causal=causal)
                           ** 2)

        def f_ref(q, k, v):
            return jnp.sum(ref_attention(q, k, v, causal, 0) ** 2)

        g_ours = jax.grad(f_ours, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_ours, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5, err_msg=name)

    def test_grads_cross_length(self):
        q, k, v = make(b=1, sq=8, sk=24, h=2, d=8)

        def f_ours(q, k, v):
            return jnp.sum(flash_attention_bshd_hb(q, k, v, causal=True)
                           * jnp.arange(8.0)[None, :, None, None])

        def f_ref(q, k, v):
            return jnp.sum(ref_attention(q, k, v, True, 16)
                           * jnp.arange(8.0)[None, :, None, None])

        g_ours = jax.grad(f_ours, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_ours, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5, err_msg=name)


class TestOffsetNegative:
    """sq > sk causal (offset < 0): rows with NO valid key must produce
    zero output and zero, finite grads — the lse there is ~-1e30 and
    exp(0)=1 garbage would leak without the valid re-mask (mirrors
    test_flash_attention.py's empty-rows regression for the HB kernel)."""

    def test_empty_rows_zero_output(self):
        q, k, v = make(b=1, sq=32, sk=16, h=2, d=8)
        out = np.asarray(flash_attention_bshd_hb(q, k, v, causal=True))
        # offset = -16: rows i < 16 attend keys <= i-16 -> none
        np.testing.assert_allclose(out[:, :16], 0.0, atol=1e-6)
        # non-empty rows match the reference
        ref = np.asarray(ref_attention(q, k, v, True, -16))
        np.testing.assert_allclose(out[:, 16:], ref[:, 16:], rtol=2e-5,
                                   atol=2e-5)

    def test_empty_rows_grads_zero_and_finite(self):
        q, k, v = make(b=1, sq=32, sk=16, h=2, d=8)

        def f(q, k, v):
            return jnp.sum(flash_attention_bshd_hb(q, k, v, causal=True)
                           ** 2)

        gq, gk, gv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        for g in (gq, gk, gv):
            assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(gq)[:, :16], 0.0, atol=1e-6)

    def test_supports_hb_vmem_gate(self):
        # 32 heads at 512 blocks = 64MB of scores+probs: must be rejected
        assert not supports_hb((1, 1024, 32, 128), (1, 1024, 32, 128), 0.0)
        assert supports_hb((1, 1024, 8, 128), (1, 1024, 8, 128), 0.0)
