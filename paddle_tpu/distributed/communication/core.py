"""Collective communication core (TPU-native ProcessGroup replacement).

Reference three-tier backend (SURVEY.md §5.8: ProcessGroupNCCL /
CommContext kernels / legacy c_* ops) collapses into ONE mechanism here:
every collective is an XLA collective over a named mesh axis.

Two calling contexts, same ops:
- **traced** (inside ``shard_map``/``pjit`` over the mesh): the tensor is the
  per-device shard; ops lower to ``lax.psum/all_gather/...`` directly —
  these ride ICI on hardware.
- **eager** ("stacked-ranks" convention): the tensor's LEADING axis indexes
  the group's ranks (rank i's local tensor = t[i]), mirroring how the
  reference's per-process tensors line up side by side. The op runs a jitted
  ``shard_map`` over the group axis, so data placed on the mesh keeps its
  sharding and the collective still executes as an XLA collective.

Why stacked-ranks: single-controller SPMD has no per-process local tensor;
the stacked form is bit-identical to the reference's N local tensors and is
exactly the global-array view of a mesh-sharded batch.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ...core.tensor import Tensor
from ..topology import Group, get_mesh

__all__ = ["ReduceOp", "in_traced_context", "collective", "get_group",
           "new_group", "get_global_group"]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


_REDUCERS = {
    ReduceOp.SUM: lax.psum,
    ReduceOp.MAX: lax.pmax,
    ReduceOp.MIN: lax.pmin,
}


def _preduce(x, op, axis):
    if op in _REDUCERS:
        return _REDUCERS[op](x, axis)
    if op == ReduceOp.AVG:
        return lax.pmean(x, axis)
    if op == ReduceOp.PROD:
        return jnp.exp(lax.psum(jnp.log(jnp.abs(x) + 1e-30), axis)) * _sign_prod(x, axis)
    raise ValueError(f"unsupported reduce op {op}")


def _sign_prod(x, axis):
    neg = lax.psum((x < 0).astype(jnp.int32), axis)
    return jnp.where(neg % 2 == 0, 1.0, -1.0).astype(x.dtype)


def in_traced_context(axis_name: str) -> bool:
    """True when called under shard_map/pmap with axis bound."""
    try:
        lax.axis_index(axis_name)
        return True
    except NameError:
        return False
    except Exception:
        return True  # bound but used outside primitive context


_DEFAULT_GROUP: Optional[Group] = None


def get_global_group() -> Group:
    """The world group: all devices flattened onto a virtual 'world' view.
    Implemented as the dp axis when that is the only >1 axis, else an axis
    tuple over every hybrid axis."""
    global _DEFAULT_GROUP
    if _DEFAULT_GROUP is None:
        mesh = get_mesh()
        sizes = dict(mesh.shape)
        live = [a for a, s in sizes.items() if s > 1]
        axis = live[0] if len(live) == 1 else tuple(mesh.axis_names)
        _DEFAULT_GROUP = Group(axis, mesh,
                               ranks=list(range(int(np.prod(list(sizes.values()))))))
    return _DEFAULT_GROUP


def _reset_default_group():
    global _DEFAULT_GROUP
    _DEFAULT_GROUP = None


def get_group(group) -> Group:
    if group is None:
        return get_global_group()
    return group


def new_group(ranks=None, backend=None, timeout=None) -> Group:
    """collective.py:178 parity. In mesh terms a rank-list subgroup over the
    flattened device order; collectives over it use a gather-compute-scatter
    fallback (sub-axis groups beyond whole axes are rare on TPU — prefer
    whole-axis groups)."""
    mesh = get_mesh()
    return Group(None, mesh, ranks=ranks)


# ---------------------------------------------------------------------------
# The collective engine
# ---------------------------------------------------------------------------


def _flat_world_mesh(mesh: Mesh) -> Mesh:
    devs = mesh.devices.reshape(-1)
    return Mesh(devs, ("world",))


def _axis_for(group: Group):
    if group.axis_name is not None:
        return group.axis_name
    return None


@functools.lru_cache(maxsize=256)
def _build_stacked(mesh, axis, kernel_name, extra):
    """Compile a stacked-ranks collective: input leading dim = group size."""
    kernel = _KERNELS[kernel_name]

    def per_shard(x):
        # x block: [1, ...] — drop the rank dim for the kernel, re-add after
        y = kernel(x[0], axis, extra)
        return y[None] if y is not None else x

    f = shard_map(per_shard, mesh=mesh, in_specs=P(axis),
                  out_specs=P(axis), check_vma=False)
    return jax.jit(f)


# kernels: (local_value, axis, extra) -> local_result
def _k_all_reduce(x, axis, extra):
    return _preduce(x, extra[0], axis)


def _k_all_gather_stack(x, axis, extra):
    return lax.all_gather(x, axis, axis=0)  # [world, ...]


def _k_all_gather_concat(x, axis, extra):
    return lax.all_gather(x, axis, axis=0, tiled=True)  # concat on dim0


def _k_reduce_scatter(x, axis, extra):
    op = extra[0]
    if op == ReduceOp.SUM:
        return lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
    full = _preduce(x, op, axis)
    n = lax.axis_size(axis)
    i = lax.axis_index(axis)
    chunk = x.shape[0] // n
    return lax.dynamic_slice_in_dim(full, i * chunk, chunk, 0)


def _k_all_to_all(x, axis, extra):
    n = lax.axis_size(axis)
    xs = x.reshape((n, x.shape[0] // n) + x.shape[1:])
    return lax.all_to_all(xs, axis, split_axis=0, concat_axis=0,
                          tiled=False).reshape(x.shape)


def _k_broadcast(x, axis, extra):
    src = extra[0]
    full = lax.all_gather(x, axis, axis=0)
    return full[src]


def _k_reduce(x, axis, extra):
    op, dst = extra
    red = _preduce(x, op, axis)
    i = lax.axis_index(axis)
    return jnp.where(i == dst, red, x)


def _k_scatter(x, axis, extra):
    # x: each rank holds the FULL [world*chunk, ...] on src; take own chunk
    src = extra[0]
    full = lax.all_gather(x, axis, axis=0)[src]
    n = lax.axis_size(axis)
    i = lax.axis_index(axis)
    chunk = full.shape[0] // n
    return lax.dynamic_slice_in_dim(full, i * chunk, chunk, 0)


def _k_ppermute(x, axis, extra):
    perm = extra[0]
    return lax.ppermute(x, axis, perm=perm)


_KERNELS = {
    "all_reduce": _k_all_reduce,
    "all_gather_stack": _k_all_gather_stack,
    "all_gather_concat": _k_all_gather_concat,
    "reduce_scatter": _k_reduce_scatter,
    "all_to_all": _k_all_to_all,
    "broadcast": _k_broadcast,
    "reduce": _k_reduce,
    "scatter": _k_scatter,
    "ppermute": _k_ppermute,
}


def collective(kernel_name: str, tensor, group=None, extra=()):
    """Run a collective in either context. Eager input follows the
    stacked-ranks convention (leading dim == group size)."""
    g = get_group(group)
    axis = _axis_for(g)
    value = tensor.value if isinstance(tensor, Tensor) else jnp.asarray(tensor)
    extra = tuple(extra)

    if axis is not None and not isinstance(axis, tuple) and in_traced_context(axis):
        out = _KERNELS[kernel_name](value, axis, extra)
        return Tensor(out)

    mesh = g.mesh
    if axis is None or isinstance(axis, tuple):
        # world / rank-list group: flatten devices to one axis
        mesh = _flat_world_mesh(mesh)
        axis = "world"
        n = g.nranks
    else:
        n = int(mesh.shape[axis])
    if value.shape[0] != n:
        raise ValueError(
            f"stacked-ranks collective expects leading dim == group size "
            f"({n}), got shape {value.shape}. Inside shard_map the per-shard "
            f"form is used automatically.")
    fn = _build_stacked(mesh, axis, kernel_name, extra)
    return Tensor(fn(value))
