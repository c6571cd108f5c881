"""The gated delta rule (linear attention with a fixed-size state), its
causal depthwise convolution, and their two serving forms: a chunked scan
over a whole prompt and a one-token update of ``[rows]`` states.

Per head (``dk`` key dims, ``dv`` value dims), with ``q`` and ``k``
L2-normed, ``q`` scaled by ``dk^-0.5``, ``g <= 0`` the log decay and
``beta`` the write strength, all float32:

    S   = exp(g_t) * S_{t-1}                  # S: [dk, dv], S_0 = 0
    d_t = beta_t * (v_t - k_t . S)            # [dv]
    S_t = S + k_t (x) d_t
    o_t = q_t . S_t

Grouped heads: with ``Hv`` value heads over ``Hk`` key heads (``Hv`` a
multiple of ``Hk``), value head ``j`` reads key head ``j // (Hv / Hk)``:
q and k come with ``Hk`` heads, v, g, beta and the state with ``Hv``. Both
kernels take the key heads as they are and read each once for its group
(nothing is repeated in HBM); equal counts are the ungrouped form.

``gdn_chunk_prefill`` (a Pallas kernel) runs it over a prompt in chunks of
``CHUNK`` positions: inside a chunk the WY representation turns the
recurrence into products on the matrix unit, the float32 state is carried
from chunk to chunk in VMEM. With ``G`` the chunk's running sum of ``g``,
``D[i, j] = exp(G_i - G_j)`` for ``i >= j`` and ``M`` the strictly lower
part of ``beta_i (k_i . k_j) D[i, j]``:

    T     = (I + M)^-1
    w, u  = T (beta exp(G) k),  T (beta v)
    v'    = u - w S
    o     = (exp(G) q) S + tril((q k^T) D) v'
    S     = exp(G_last) S + (exp(G_last - G) k)^T v'

``M`` is nilpotent, so its inverse is a FINITE product and nothing is
truncated: inside diagonal blocks of 16 positions ``(I - N)^-1 = (I + N)
(I + N^2)(I + N^4)(I + N^8)``; the four blocks are then joined by the same
identity one level up (``X^4 = 0``). Every product is float32 at the
highest precision. Positions past ``last_idx`` carry ``g = 0`` and ``beta
= 0`` and pass the state through unchanged; chunks wholly past it are
neither fetched (the index maps look at ``last_idx``) nor computed, and
their outputs are zeros.

``gdn_decode_step`` (a Pallas kernel) updates the states of live rows in
place, one read and one write of each: a grid over the live rows (a
compacted list, scalar-prefetched) and groups of heads, a dead row
neither fetched nor written. ``decode_step_xla`` is the same update as an
XLA composition, and ``recurrence`` the equations above as a ``lax.scan``
(what the chunked form is tested against).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _dot, _interpret

__all__ = ["CHUNK", "causal_conv", "conv_rows", "conv_step", "l2norm",
           "recurrence", "gdn_chunk_prefill", "gdn_decode_step",
           "decode_step_xla"]

CHUNK = 64          # positions a chunk; four blocks of _BLOCK for the inverse
_BLOCK = 16
F32 = jnp.float32


# -- the convolution, in both forms --------------------------------------------
def causal_conv(u, w):
    """silu of the depthwise causal convolution over positions: ``u``
    [B, S, C], ``w`` [C, K]; position t sees u[t-K+1 .. t], zeros before
    the start. float32 accumulation, result in u's dtype."""
    s, width = u.shape[1], w.shape[1]
    pad = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    acc = sum(pad[:, j:j + s].astype(F32) * w[:, j].astype(F32)
              for j in range(width))
    return jax.nn.silu(acc).astype(u.dtype)


def conv_rows(u, last_idx, width):
    """The ``width - 1`` pre-convolution inputs a decode step at position
    ``last_idx + 1`` needs: u[last_idx-width+2 .. last_idx], zeros before
    position 0. ``u`` [B, S, C] -> [B, width-1, C]."""
    pad = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    return jax.lax.dynamic_slice_in_dim(pad, last_idx + 1, width - 1, axis=1)


def conv_step(rows, u_new, w):
    """One position of the convolution: ``rows`` [R, K-1, C] (the last
    inputs, oldest first), ``u_new`` [R, C] -> (silu(conv) [R, C], the rows
    shifted by one with ``u_new`` last)."""
    window = jnp.concatenate([rows, u_new[:, None].astype(rows.dtype)], 1)
    acc = jnp.einsum("rkc,ck->rc", window.astype(F32), w.astype(F32))
    return jax.nn.silu(acc).astype(u_new.dtype), window[:, 1:]


def l2norm(x, eps=1e-6):
    """x / sqrt(sum x^2 + eps) over the last axis (float32 in and out)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


# -- the recurrence as written ---------------------------------------------------
def _grouped(q, k, heads):
    """q, k [..., Hk, dk] -> each key head repeated for the value heads
    of its group, [..., heads, dk] (the plain forms' reading)."""
    group = heads // q.shape[-2]
    if group == 1:
        return q, k
    return (jnp.repeat(q, group, axis=-2), jnp.repeat(k, group, axis=-2))


def recurrence(q, k, v, g, beta, state=None):
    """The equations of the module's docstring, one position at a time.
    q/k [B, S, Hk, dk], v [B, S, H, dv], g/beta [B, S, H], state
    [B, H, dk, dv] or None (zeros); float32. Returns (o [B, S, H, dv],
    final state)."""
    q, k = _grouped(q, k, v.shape[2])
    if state is None:
        b, _, h, dk = q.shape
        state = jnp.zeros((b, h, dk, v.shape[-1]), F32)
    hi = jax.lax.Precision.HIGHEST

    def step(st, x):
        qt, kt, vt, gt, bt = x
        st = st * jnp.exp(gt)[..., None, None]
        d = bt[..., None] * (vt - jnp.einsum("bhk,bhkv->bhv", kt, st,
                                             precision=hi))
        st = st + kt[..., :, None] * d[..., None, :]
        return st, jnp.einsum("bhk,bhkv->bhv", qt, st, precision=hi)

    xs = tuple(jnp.moveaxis(a.astype(F32), 1, 0) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(step, state.astype(F32), xs)
    return jnp.moveaxis(o, 0, 1), state


# -- prefill: the chunked scan ---------------------------------------------------
def _chunk_kernel(last_ref, q_ref, k_ref, v_ref, gb_ref, o_ref, s_ref,
                  group=1):
    """One (key head, chunk) grid step: the ``group`` value heads that read
    the key head, one after another; ``k k^T`` and ``q k^T`` are made once
    for all of them. With ``group`` 1 the value blocks carry no head
    axis."""
    c = pl.program_id(1)
    n = q_ref.shape[0]

    def get(ref, t):
        return ref[...] if group == 1 else ref[t]

    def put(ref, t, value):
        if group == 1:
            ref[...] = value
        else:
            ref[t] = value

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(c * n <= last_ref[0])
    def _():
        q, k = q_ref[...], k_ref[...]
        shared = {}         # the group's products of q and k, made once
        for t in range(group):
            _chunk_head(q, k, get(v_ref, t).astype(F32),
                        gb_ref[2 * t:2 * t + 1, :],
                        gb_ref[2 * t + 1:2 * t + 2, :],
                        lambda t=t: get(s_ref, t), shared, lambda o, t=t: put(o_ref, t, o),
                        lambda st, t=t: put(s_ref, t, st), o_ref.dtype)


def _chunk_head(q, k, v, g_row, beta_row, get_s, shared, put_o, put_s,
                o_dtype):
    """One value head's chunk: the module docstring's WY form."""
    n = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eye = row == col

    def column(x):          # [1, n] along lanes -> [n, 1] along sublanes
        return jnp.sum(jnp.where(eye, x, 0.0), axis=1, keepdims=True)

    g_col, beta_col = column(g_row), column(beta_row)
    decay = jnp.exp(jnp.where(row >= col, g_col - g_row, -jnp.inf))
    nt = ((1,), (1,))
    below = row > col
    if "kk" not in shared:
        shared["kk"] = _dot(k, k, nt)
    m = jnp.where(below, beta_col * shared["kk"] * decay, 0.0)
    # T = (I + M)^-1, M nilpotent: diagonal blocks first, then the
    # blocks' own (block-)nilpotent remainder
    mm = ((1,), (0,))
    ident = eye.astype(F32)
    own = (row // _BLOCK) == (col // _BLOCK)
    nd = jnp.where(own, -m, 0.0)
    t_d, power = ident + nd, nd
    for _ in range(3):                    # N^2, N^4, N^8: N^16 = 0
        power = _dot(power, power, mm)
        t_d = t_d + _dot(t_d, power, mm)
    x = _dot(t_d, jnp.where(own, 0.0, -m), mm)
    join = ident + x
    join = join + _dot(join, _dot(x, x, mm), mm)          # X^4 = 0
    t = _dot(join, t_d, mm)

    e_col = jnp.exp(g_col)
    w = _dot(t, k * (beta_col * e_col), mm)
    u = _dot(t, v * beta_col, mm)
    s = get_s()
    v_new = u - _dot(w, s, mm)
    causal = row >= col
    if "qk" not in shared:
        shared["qk"] = _dot(q, k, nt)
    qk = jnp.where(causal, shared["qk"] * decay, 0.0)
    put_o((_dot(q * e_col, s, mm) + _dot(qk, v_new, mm)).astype(o_dtype))
    # G at the chunk's end, as a column (G never rises, so its least):
    # Mosaic broadcasts along one axis at a time, a [1, 1] not at all
    def last(rows):
        return jnp.min(jnp.broadcast_to(g_row, (rows, n)), axis=1,
                       keepdims=True)

    put_s(s * jnp.exp(last(s.shape[0]))
          + _dot(k * jnp.exp(last(n) - g_col), v_new, ((0,), (0,))))


def gdn_chunk_prefill(q, k, v, g, beta, last_idx, interpret=None):
    """The gated delta rule over whole sequences, state zero at the start.

    q/k [B, S, Hk, dk] float32 (L2-normed, ``q`` scaled), v [B, S, H, dv],
    g/beta [B, S, H] float32 (``H`` a multiple of ``Hk``: grouped heads,
    see the module's docstring); ``last_idx``: int32 scalar, the last
    position that counts (every row's). Returns (o [B, S, H, dv] in v's
    dtype, zeros past ``last_idx``'s chunk; the state after ``last_idx``
    [B, H, dk, dv] float32). ``S`` is padded to whole chunks inside. A grid
    step is a key head's chunk, for the value heads of its group."""
    b, s, hk, dk = q.shape
    h, dv = v.shape[2], v.shape[-1]
    group = h // hk
    n = CHUNK
    pad = -s % n
    last = jnp.asarray(last_idx, jnp.int32)
    live = (jnp.arange(s + pad) <= last)[None, :, None]

    def heads_major(a, fill=0.0):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2),
                    constant_values=fill)
        return jnp.moveaxis(a, 2, 1).reshape(
            (b * a.shape[2], s + pad) + a.shape[3:])

    nc = (s + pad) // n
    g = jnp.where(live, jnp.pad(g.astype(F32), ((0, 0), (0, pad), (0, 0))),
                  0.0)
    beta = jnp.where(live, jnp.pad(beta.astype(F32),
                                   ((0, 0), (0, pad), (0, 0))), 0.0)
    g_sum = jnp.cumsum(g.reshape(b, nc, n, h), axis=2)
    gb = jnp.stack([jnp.moveaxis(g_sum, 3, 1),
                    jnp.moveaxis(beta.reshape(b, nc, n, h), 3, 1)],
                   axis=3)
    if group == 1:
        gb = gb.reshape(b * h, nc, 2, n)
    else:                  # a key head's rows: (g, beta) of each value head
        gb = jnp.moveaxis(gb.reshape(b, hk, group, nc, 2, n), 2, 3).reshape(
            b * hk, nc, 2 * group, n)

    def chunk(c, last):      # past the last live chunk: that chunk, again
        return jnp.minimum(c, last[0] // n)

    def at(width):
        return pl.BlockSpec((None, n, width),
                            lambda i, c, last: (i, chunk(c, last), 0))

    vals, states = at(dv), pl.BlockSpec((None, dk, dv),
                                        lambda i, c, last: (i, 0, 0))
    lead, kernel = (b * h,), _chunk_kernel
    if group > 1:          # a key head's value heads, side by side
        vals = pl.BlockSpec((None, group, n, dv),
                            lambda i, c, last: (i, 0, chunk(c, last), 0))
        states = pl.BlockSpec((None, group, dk, dv),
                              lambda i, c, last: (i, 0, 0, 0))
        lead = (b * hk, group)
        kernel = functools.partial(_chunk_kernel, group=group)
    o, state = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(lead + (s + pad, dv), v.dtype),
                   jax.ShapeDtypeStruct(lead + (dk, dv), F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * hk, nc),
            in_specs=[at(dk), at(dk), vals,
                      pl.BlockSpec((None, None, 2 * group, n),
                                   lambda i, c, last: (i, chunk(c, last),
                                                       0, 0))],
            out_specs=(vals, states)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret() if interpret is None else interpret,
        name="gdn_chunk_prefill",
    )(last.reshape(1), heads_major(q.astype(F32)), heads_major(k.astype(F32)),
      heads_major(v).reshape(lead + (s + pad, dv)), gb)
    # chunks past ``last_idx`` were never written: zeros, not what lay there
    o = jnp.moveaxis(o.reshape(b, h, s + pad, dv), 1, 2)
    chunk_live = (jnp.arange(s + pad) // n <= last // n)[None, :, None, None]
    o = jnp.where(chunk_live, o, jnp.zeros((), o.dtype))[:, :s]
    return o, state.reshape(b, h, dk, dv)


# -- decode: one token a row -------------------------------------------------------
def decode_step_xla(state, q, k, v, g, beta, live):
    """One position of the recurrence for every live row, as an XLA
    composition. state [R, H, dk, dv] float32; q/k [R, Hk, dk], v
    [R, H, dv], g/beta [R, H] float32; live [R] bool. Returns (o [R, H, dv]
    float32, the states, a dead row's unchanged)."""
    q, k = _grouped(q, k, v.shape[1])
    hi = jax.lax.Precision.HIGHEST
    decay = jnp.exp(g)[..., None]
    d = beta[..., None] * (v - decay * jnp.einsum(
        "rhk,rhkv->rhv", k, state, precision=hi))
    new = state * decay[..., None] + k[..., :, None] * d[..., None, :]
    o = jnp.einsum("rhk,rhkv->rhv", q, new, precision=hi)
    return o, jnp.where(live[:, None, None, None], new, state)


def _decode_kernel(rows_ref, n_ref, db_ref, s_ref, qt_ref, kt_ref, v_ref,
                   o_ref, s_out):
    i, j = pl.program_id(0), pl.program_id(1)
    group = s_ref.shape[0]
    per_key = group // kt_ref.shape[1]     # value heads a key head

    # no live row at all: the one block that is fetched goes back as it came
    @pl.when((n_ref[0] == 0) & (i == 0))
    def _():
        s_out[...] = s_ref[...]

    @pl.when(i < n_ref[0])
    def _():
        heads = pl.num_programs(1) * group
        for t in range(group):
            s = s_ref[t]                                   # [dk, dv]
            c = t // per_key
            k_col, q_col = kt_ref[:, c:c + 1], qt_ref[:, c:c + 1]
            at = 2 * (rows_ref[i] * heads + j * group + t)
            decay, beta = db_ref[at], db_ref[at + 1]       # scalars
            ks = jnp.sum(k_col * s, axis=0, keepdims=True)       # [1, dv]
            d = beta * (v_ref[t:t + 1, :] - decay * ks)
            s = decay * s + k_col * d
            s_out[t] = s
            o_ref[t:t + 1, :] = jnp.sum(q_col * s, axis=0, keepdims=True)


def _head_group(h, per_key=1):
    """Heads a grid step of the decode kernel: the largest divisor of
    ``h`` up to 10 that holds whole groups of ``per_key`` value heads (a
    key head's), whose states (in and out, double-buffered) take 3 MB at
    96 x 192."""
    return max(d for d in range(per_key, max(min(h, 10), per_key) + 1,
                                per_key) if h % d == 0)


def gdn_decode_step(state, q, k, v, g, beta, live, interpret=None):
    """:func:`decode_step_xla` with the states updated IN PLACE (the
    ``state`` argument is aliased to the result: donate it), one read and
    one write of each live row's state and none of a dead row's. Grouped
    heads (q/k [R, Hk, dk]): a grid step's block of q and k holds the key
    heads of its value heads, each once."""
    r, h, dk, dv = state.shape
    group = _head_group(h, h // q.shape[1])
    hg = h // group
    # live rows first, then the last live row repeated: a repeated block is
    # neither fetched nor written again
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_live = jnp.sum(live).astype(jnp.int32)
    rows = jnp.where(jnp.arange(r) < n_live, order,
                     order[jnp.maximum(n_live - 1, 0)])

    def grouped(a):                  # [R, H, x] -> [R, hg, H / hg, x]
        return a.astype(F32).reshape(r, hg, a.shape[1] // hg, a.shape[-1])

    def columns(a):                  # [R, Hk, dk] -> [R, hg, dk, Hk / hg]
        return jnp.swapaxes(grouped(a), 2, 3)

    # exp(g) and beta of (row, head) as scalars, prefetched beside the rows
    db = jnp.stack([jnp.exp(g), beta], axis=-1).astype(F32).reshape(-1)

    def spec(*tail):
        # past the live rows: the block of the last live step, again (with
        # no live row, row 0's blocks, copied through)
        return pl.BlockSpec(
            (None,) * (4 - len(tail)) + tail,
            lambda i, j, rows, n, db: (
                rows[i], jnp.where(i < jnp.maximum(n[0], 1), j, hg - 1),
                0, 0))

    o, state = pl.pallas_call(
        _decode_kernel,
        out_shape=(jax.ShapeDtypeStruct((r, hg, group, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(r, hg),
            in_specs=[spec(group, dk, dv), spec(dk, q.shape[1] // hg),
                      spec(dk, q.shape[1] // hg), spec(group, dv)],
            out_specs=(spec(group, dv), spec(group, dk, dv))),
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret() if interpret is None else interpret,
        name="gdn_decode_step",
    )(rows, n_live.reshape(1), db, state, columns(q), columns(k), grouped(v))
    # a dead row's output block was never written
    return jnp.where(live[:, None, None], o.reshape(r, h, dv), 0.0), state
