"""Tensor-parallel serving mesh — shard one engine over N chips.

The training side already runs pjit meshes and shard_map
(``distributed/_spmd.py``, ``fleet/meta_parallel/``); THIS module is the
serving half: a 1-D ``Mesh`` over the ``"mp"`` axis (the same axis name
the llama layer stack's PartitionSpecs already carry, so the training
sharding plan IS the serving sharding plan) that the continuous-batching
engines shard their device state over:

- **weights** follow their layer pspecs (column-parallel q/k/v/gate/up
  on the out-dim, row-parallel o/down on the in-dim, vocab-parallel
  embedding/lm_head) — GSPMD partitions the projections and inserts
  exactly one psum per block at the row-parallel reductions;
- **KV pools / prefill minis** shard on the
  (kv_)head axis — attention is head-parallel, so the decode read never
  crosses chips; per-(page, kv_head) int8 scales shard the same way;
- **everything per-slot** (sampling vectors, spec_k, adapter_idx, lens,
  the page table) REPLICATES — the PR 2 one-program invariant is
  mesh-invariant: one compiled SPMD program serves any request mix at
  any TP degree.

The page ALLOCATOR, prefix-cache chain hashes, CoW bookkeeping, and
quota/queue logic all operate on page *indices* and host state — they
never see the mesh and need no fork (TP-invariant by construction).

Attention kernels (Pallas on TPU, jnp fallbacks on CPU) are wrapped in
``shard_map`` by their ops modules (``ops/paged_attention.py``,
``ops/_decode.py``, ``ops/pallas.py``) when the engine threads its
``tp=(mesh, axis)`` handle through the model forwards: each shard runs
the UNMODIFIED kernel on its local head slice — zero communication
inside attention, and on TPU the per-shard Mosaic kernel sees local
pools instead of forcing an all-gather of the sharded HBM pools.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["TP_AXIS", "make_tp_mesh", "validate_tp_model",
           "shard_params_tp", "tp_shard_kv", "tp_replicate"]

# the serving mesh axis: "mp" on purpose — llama's ColumnParallel/
# RowParallel/VocabParallel params already carry P(..., "mp") pspecs
# from the training stack, so the engine shards weights by reading the
# annotations it finds instead of keeping a second plan
TP_AXIS = "mp"


def make_tp_mesh(tp_degree: int, devices=None) -> Optional[Mesh]:
    """Build the engine's 1-D tensor-parallel mesh (axis ``"mp"``), or
    None when ``tp_degree == 1`` (single-device engine — every program
    stays exactly the pre-TP trace).

    ``devices`` pins the replica to a device subset (ints index
    ``jax.devices()``; device objects pass through) — the
    ``ReplicaSpec(devices=...)`` seam, so an N-replica × TP-k fleet
    partitions one slice instead of every replica claiming device 0.
    A ``tp_degree == 1`` engine takes no mesh; pinning a lone device
    is the caller's ``jax.default_device`` concern."""
    if (isinstance(tp_degree, bool)
            or not isinstance(tp_degree, (int, np.integer))
            or tp_degree < 1):
        raise ValueError(
            f"tp_degree must be an int >= 1, got {tp_degree!r}")
    if tp_degree == 1:
        return None
    devs = _resolve_devices(devices)
    if devices is not None and len(devs) != tp_degree:
        # a pinned subset is the explicit fleet-partitioning seam: a
        # size mismatch is a slice typo that would silently idle chips
        # (too many) or fail later (too few) — surface it here
        raise ValueError(
            f"tp_devices pins {len(devs)} devices but tp_degree="
            f"{tp_degree} — pass exactly tp_degree devices")
    if len(devs) < tp_degree:
        raise ValueError(
            f"tp_degree={tp_degree} needs at least that many devices, "
            f"got {len(devs)} (jax.devices()) — on CPU CI run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return Mesh(np.asarray(devs[:tp_degree]), (TP_AXIS,))


def _resolve_devices(devices) -> Sequence:
    if devices is None:
        return jax.devices()
    out = []
    all_devs = None
    for d in devices:
        if isinstance(d, (int, np.integer)) and not isinstance(d, bool):
            if all_devs is None:
                all_devs = jax.devices()
            if not 0 <= int(d) < len(all_devs):
                raise ValueError(
                    f"device index {d} out of range "
                    f"(0..{len(all_devs) - 1})")
            out.append(all_devs[int(d)])
        else:
            out.append(d)
    return out


def validate_tp_model(model, tp_degree: int) -> None:
    """Fail at ENGINE CONSTRUCTION — not inside a traced program — when
    the model's geometry cannot shard evenly over ``tp_degree``: query
    heads and kv heads (attention shards per head), the MLP
    intermediate (column/row split), and the vocab (vocab-parallel
    embedding/lm_head). Models without a llama-shaped ``config`` are
    let through — GSPMD will still partition what divides and
    replicate what does not."""
    cfg = getattr(model, "config", None)
    if cfg is None or tp_degree <= 1:
        return
    checks = (
        ("num_attention_heads", getattr(cfg, "num_attention_heads",
                                        None)),
        ("kv_heads", getattr(cfg, "kv_heads", None)),
        ("intermediate_size", getattr(cfg, "intermediate_size", None)),
        ("vocab_size", getattr(cfg, "vocab_size", None)),
    )
    for name, val in checks:
        if val is not None and val % tp_degree:
            raise ValueError(
                f"tp_degree={tp_degree} does not divide model "
                f"{name}={val} — the head/ffn/vocab axes must shard "
                f"evenly")


def shard_params_tp(model, params: dict, mesh: Mesh) -> dict:
    """Place every engine parameter onto the mesh by its layer pspec
    (``distributed/_spmd.set_pspec`` annotations — the training plan),
    replicated when unannotated. Returns a new name->array dict; the
    engine's jitted programs pick the shardings up as committed-input
    shardings, and GSPMD partitions the matmuls accordingly."""
    from ..distributed._spmd import _filter_spec, layer_pspecs

    specs = layer_pspecs(model)   # params + buffers, replicated when
    #                               unannotated — the one plan source
    out = {}
    for name, v in params.items():
        spec = _filter_spec(specs.get(name, P()), mesh)
        out[name] = jax.device_put(v, NamedSharding(mesh, spec))
    return out


def _kv_spec(arr) -> P:
    """PartitionSpec for one cache/pool array: 4-D K/V storage
    ``[..., ..., heads, head_dim]`` shards on the head axis (axis -2);
    2-D per-(page, kv_head) scale arrays shard on the head axis
    (axis -1); anything else replicates."""
    if arr.ndim == 4:
        return P(None, None, TP_AXIS, None)
    if arr.ndim == 2:
        return P(None, TP_AXIS)
    return P()


def tp_shard_kv(caches, mesh: Mesh):
    """Shard a per-layer cache list (page pools or prefill minis; entries are ``(k, v)`` or int8
    ``(k, v, k_scale, v_scale)`` tuples) on the kv-head axis. Pure
    placement — values are untouched, so a sharded pool reads back
    bitwise what an unsharded one holds."""
    return [tuple(jax.device_put(a, NamedSharding(mesh, _kv_spec(a)))
                  for a in entry)
            for entry in caches]


def tp_replicate(x, mesh: Mesh):
    """Commit ``x`` to the mesh fully REPLICATED — the per-slot device
    vectors, the page table, and every host-shipped index vector take
    this path, which is what keeps the one-compiled-program invariant:
    program signatures (shapes + shardings) are identical for any
    request mix at any TP degree."""
    return jax.device_put(x, NamedSharding(mesh, P()))
