"""Tensor-parallel layer parity tests (reference pattern:
test/collective/fleet/hybrid_parallel_mp_layers.py — TP layers must match
single-device math)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed._spmd import layer_pspecs, shard_params
from paddle_tpu.distributed.fleet.layers.mpu import (
    ColumnParallelLinear, ParallelCrossEntropy, RowParallelLinear,
    VocabParallelEmbedding)
from paddle_tpu.distributed.topology import build_mesh, set_mesh


def t2n(t):
    return np.asarray(t.numpy())


@pytest.fixture(autouse=True)
def _mesh():
    mesh = build_mesh(mp=8)
    set_mesh(mesh)
    from paddle_tpu.distributed.communication import core

    core._reset_default_group()
    return mesh


class TestColumnRowParallel:
    def test_column_parallel_eager_matches_linear(self, _mesh):
        layer = ColumnParallelLinear(16, 24, gather_output=True)
        x = np.random.randn(4, 16).astype(np.float32)
        out = layer(paddle.to_tensor(x))
        w = t2n(layer.weight)
        b = t2n(layer.bias)
        np.testing.assert_allclose(t2n(out), x @ w + b, rtol=1e-5, atol=1e-5)

    def test_row_parallel_eager_matches_linear(self, _mesh):
        layer = RowParallelLinear(24, 16, input_is_parallel=True)
        x = np.random.randn(4, 24).astype(np.float32)
        out = layer(paddle.to_tensor(x))
        w = t2n(layer.weight)
        b = t2n(layer.bias)
        np.testing.assert_allclose(t2n(out), x @ w + b, rtol=1e-5, atol=1e-5)

    def test_mlp_sharded_jit_matches_eager(self, _mesh):
        """column(gather=False) -> row(input_is_parallel) MLP under jit over
        the mp=8 mesh == eager single-device math."""
        col = ColumnParallelLinear(16, 32, gather_output=False)
        row = RowParallelLinear(32, 16, input_is_parallel=True)
        shard_params(col, _mesh)
        shard_params(row, _mesh)
        x = np.random.randn(8, 16).astype(np.float32)

        def f(xv):
            h = col(paddle.to_tensor(xv, stop_gradient=True))
            return row(h).value

        jitted = jax.jit(lambda xv: f(xv))
        got = np.asarray(jitted(x))
        w1, b1 = t2n(col.weight), t2n(col.bias)
        w2, b2 = t2n(row.weight), t2n(row.bias)
        expected = (x @ w1 + b1) @ w2 + b2
        np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-4)

    def test_manual_shard_map_matches_serial(self, _mesh):
        """Megatron manual path: run the column->row pair inside shard_map
        with weights sharded by hand; must equal serial matmul."""
        rng = np.random.RandomState(0)
        w1 = rng.randn(16, 32).astype(np.float32)
        w2 = rng.randn(32, 16).astype(np.float32)
        x = rng.randn(8, 16).astype(np.float32)
        from paddle_tpu.distributed.fleet.layers.mpu import mp_ops

        def step(xv, w1v, w2v):
            h = mp_ops._c_identity(paddle.to_tensor(xv))
            h = paddle.matmul(h, paddle.to_tensor(w1v))
            y = paddle.matmul(h, paddle.to_tensor(w2v))
            y = mp_ops._mp_allreduce(y)
            return y.value

        f = shard_map(
            step, mesh=_mesh,
            in_specs=(P(), P(None, "mp"), P("mp", None)),
            out_specs=P(),
        )
        got = np.asarray(jax.jit(f)(x, w1, w2))
        np.testing.assert_allclose(got, x @ w1 @ w2, rtol=1e-4, atol=1e-4)


class TestVocabParallelEmbedding:
    def test_eager_matches_take(self, _mesh):
        emb = VocabParallelEmbedding(64, 12)
        ids = np.random.randint(0, 64, (4, 7))
        out = emb(paddle.to_tensor(ids))
        expected = t2n(emb.weight)[ids]
        np.testing.assert_allclose(t2n(out), expected, rtol=1e-6)

    def test_manual_shard_map_matches_take(self, _mesh):
        rng = np.random.RandomState(1)
        table = rng.randn(64, 12).astype(np.float32)
        ids = rng.randint(0, 64, (4, 7))
        from paddle_tpu.distributed.fleet.layers.mpu import mp_ops

        def step(tbl, idx):
            out = mp_ops._c_lookup_table(paddle.to_tensor(tbl),
                                         paddle.to_tensor(idx))
            return out.value

        f = shard_map(step, mesh=_mesh, in_specs=(P("mp", None), P()),
                      out_specs=P())
        got = np.asarray(jax.jit(f)(table, ids.astype(np.int32)))
        np.testing.assert_allclose(got, table[ids], rtol=1e-5)


class TestParallelCrossEntropy:
    def test_matches_softmax_ce(self, _mesh):
        rng = np.random.RandomState(2)
        logits = rng.randn(6, 40).astype(np.float32)
        labels = rng.randint(0, 40, (6,))
        ce = ParallelCrossEntropy()
        loss = ce(paddle.to_tensor(logits), paddle.to_tensor(labels))
        # numpy reference
        m = logits.max(-1, keepdims=True)
        ex = np.exp(logits - m)
        ref = (np.log(ex.sum(-1, keepdims=True)) + m
               - np.take_along_axis(logits, labels[:, None], -1))
        np.testing.assert_allclose(t2n(loss), ref, rtol=1e-5, atol=1e-5)

    def test_manual_class_parallel_matches(self, _mesh):
        rng = np.random.RandomState(3)
        logits = rng.randn(6, 40).astype(np.float32)
        labels = rng.randint(0, 40, (6,)).astype(np.int32)
        from paddle_tpu.distributed.fleet.layers.mpu import mp_ops

        def step(lg, lb):
            out = mp_ops._c_softmax_with_cross_entropy(
                paddle.to_tensor(lg), paddle.to_tensor(lb))
            return out.value

        f = shard_map(step, mesh=_mesh, in_specs=(P(None, "mp"), P()),
                      out_specs=P())
        got = np.asarray(jax.jit(f)(logits, labels))
        m = logits.max(-1, keepdims=True)
        ex = np.exp(logits - m)
        ref = (np.log(ex.sum(-1, keepdims=True)) + m
               - np.take_along_axis(logits, labels[:, None].astype(np.int64), -1))
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


class TestRNGTracker:
    def test_named_streams_differ_and_restore(self, _mesh):
        from paddle_tpu.distributed.fleet.layers.mpu import (
            get_rng_state_tracker, model_parallel_random_seed)

        model_parallel_random_seed(1234)
        tracker = get_rng_state_tracker()
        x = paddle.to_tensor(np.ones((64, 64), np.float32))
        import paddle_tpu.nn.functional as F

        with tracker.rng_state():
            a = t2n(F.dropout(x, 0.5, training=True))
        b = t2n(F.dropout(x, 0.5, training=True))
        assert not np.allclose(a, b)

    def test_duplicate_seed_rejected(self, _mesh):
        from paddle_tpu.distributed.fleet.layers.mpu import RNGStatesTracker

        tr = RNGStatesTracker()
        tr.add("a", 1)
        with pytest.raises(ValueError):
            tr.add("b", 1)


class TestFleetFacade:
    def test_init_and_hcg(self, _mesh):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4}
        fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.get_hybrid_communicate_group()
        assert hcg.get_model_parallel_world_size() == 4
        assert hcg.get_data_parallel_world_size() == 2

    def test_distributed_model_tp_wrapper(self, _mesh):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"mp_degree": 8}
        fleet.init(is_collective=True, strategy=strategy)
        import paddle_tpu.nn as nn

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = ColumnParallelLinear(8, 16, gather_output=True)

            def forward(self, x):
                return self.fc(x)

        net = fleet.distributed_model(Net())
        x = np.random.randn(2, 8).astype(np.float32)
        out = net(paddle.to_tensor(x))
        assert tuple(out.shape) == (2, 16)
