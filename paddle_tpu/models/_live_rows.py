"""Row-wise work under a dynamic extent: a prefill's bucket is a static
``S`` rows wide, the prompt in it ``live`` rows long (traced), and whatever
is a function of one row alone need run over the prompt's rows only.

:func:`live_rows` cuts the rows into blocks of a static size and runs the
blocks at or before the last live row under a ``fori_loop`` whose trip
count is traced (it lowers to a ``while``), so a prompt of 8,706 in a bucket
of 16,384 pays for 9 blocks of 1,024 and not for 16. Rows of a block that
never ran are zero in every output; a caller reads none of them. Nothing
here knows of a model.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["live_rows", "row_block", "rows_run"]


def row_block(rows: int, want: int) -> int:
    """The block for a bucket of ``rows``: ``want`` where it divides the
    bucket (or the bucket, where that is narrower), else their largest
    common divisor (17,408 = 17 x 1,024 under a ``want`` of 2,048)."""
    return math.gcd(rows, want)


def rows_run(rows: int, live: int, want: int) -> int:
    """Rows :func:`live_rows` runs of a bucket of ``rows`` that holds
    ``live``, with :func:`row_block` of ``want``: the host's count."""
    block = row_block(rows, want)
    return -(-live // block) * block


def _each(axes, n: int) -> tuple:
    return (axes,) * n if isinstance(axes, int) else tuple(axes)


def live_rows(fn, arrays, live, block: int, in_axes=0, out_axes=0):
    """``fn(*arrays)`` for a ``fn`` that maps rows to rows, computed for the
    blocks that hold the first ``live`` rows alone.

    ``arrays``: each with the bucket's ``S`` rows along its axis of
    ``in_axes`` (an int for all, or one each). ``fn`` takes them (or slabs
    of ``block`` rows of them) and returns an array or a pytree of arrays
    with the same rows along ``out_axes`` (an int, or one a leaf); row
    ``t`` of its outputs must depend on row ``t`` of its inputs alone.
    ``live``: None, or an int32 scalar (traced), 0 <= live <= S. ``block``
    (static) divides ``S``.

    With ``live=None``, or a block as wide as the bucket, this is
    ``fn(*arrays)``. Else block ``i`` runs for ``i < ceil(live / block)``,
    each input cut with ``dynamic_slice_in_dim`` and each output written
    into a bucket-wide buffer of zeros with ``dynamic_update_slice_in_dim``:
    rows past the last block that ran are 0."""
    arrays = tuple(arrays)
    in_axes = _each(in_axes, len(arrays))
    rows = arrays[0].shape[in_axes[0]]
    if rows % block:
        raise ValueError(f"a block of {block} rows does not divide the "
                         f"bucket's {rows}")
    if live is None or block == rows:
        return fn(*arrays)

    def slab(i):
        return [jax.lax.dynamic_slice_in_dim(a, i * block, block, ax)
                for a, ax in zip(arrays, in_axes)]

    shapes, tree = jax.tree_util.tree_flatten(
        jax.eval_shape(lambda: fn(*slab(0))))
    out_axes = _each(out_axes, len(shapes))

    def body(i, bufs):
        outs = jax.tree_util.tree_leaves(fn(*slab(i)))
        return [jax.lax.dynamic_update_slice_in_dim(b, o, i * block, ax)
                for b, o, ax in zip(bufs, outs, out_axes)]

    bufs = jax.lax.fori_loop(
        0, (jnp.asarray(live, jnp.int32) + block - 1) // block, body,
        [jnp.zeros(s.shape[:ax] + (rows,) + s.shape[ax + 1:], s.dtype)
         for s, ax in zip(shapes, out_axes)])
    return jax.tree_util.tree_unflatten(tree, bufs)
