"""Routed experts: a token-choice sparse FFN with no capacity and no
dropped token, every shape static.

Each token scores every expert from a float32 product: either the
sigmoid of the product, the ``top_k`` largest of ``score + bias`` chosen
(the bias steers the choice only), their scores renormalised and scaled;
or (``score="softmax"``) the ``top_k`` largest products chosen and a
softmax over them. The token's output is the weighted sum of the chosen
experts' gated linear units (SwiGLU, or ReLU-GLU with ``act="relu"``).
The router may read another tensor than the experts compute on. The
products run as three grouped matrix products over the (token, choice)
rows sorted by expert (:func:`paddle_tpu.ops.pallas.grouped_matmul`), so
an expert's weights are read once for all its rows and an expert no row
chose is not read at all. Reference analog: the reference's capacity-dispatch MoE
(incubate/distributed/models/moe) pads every expert to a capacity and
drops what overflows; this layer is the serving form, exact for any skew.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ...core.autograd import apply_op
from ..initializer import Constant, Initializer, XavierUniform
from .layers import Layer

__all__ = ["RoutedExperts", "glu", "route_top_k", "routed_experts_ffn"]

# How far an expert's initial weights lie from the other experts', as a
# share of their norm (see :class:`_Upcycled`).
EXPERT_SPREAD = 0.01
# The most the float32 result rows [tokens x choices, hidden] of one pass
# through the experts may take (see :class:`RoutedExperts`).
ROWS_BYTES = 1 << 29


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "limit"))
def _upcycled_uniform(key, shape, dtype, limit):
    base_key, own_key = jax.random.split(key)
    base = jax.random.uniform(base_key, shape[1:], jnp.float32, -limit, limit)
    own = jax.random.uniform(own_key, shape, jnp.float32, -limit, limit)
    return (math.sqrt(1.0 - EXPERT_SPREAD ** 2) * base
            + EXPERT_SPREAD * own).astype(dtype)


class _Upcycled(Initializer):
    """Stacked expert weights [E, fan_in, fan_out] as sparse upcycling
    leaves them (Komatsuzaki et al. 2022: every expert starts as a copy of
    one dense FFN) plus a part of each expert's own:
    ``sqrt(1 - EXPERT_SPREAD^2)`` x one Xavier-uniform draw shared by all
    experts + ``EXPERT_SPREAD`` x a draw for each, so an element keeps the
    Xavier variance.

    Why not a draw for each expert alone: between the k-th and the
    (k+1)-th of many router scores lies less than a bf16 activation
    resolves for a few tokens in a hundred, whatever the router's scale,
    so bf16 inference and a float32 reference now and then choose another
    last expert. With independent random experts that one choice replaces
    an eighth of the routed sum by something unrelated, a far larger error
    than lower precision or a wrong mask makes, and a comparison of logits
    can then tell neither from a sound run. Experts ``EXPERT_SPREAD``
    apart turn the near-tie into an error of that size, and leave a
    dropped term, a wrong weight or fp8 arithmetic as visible as they
    are."""

    def _generate(self, key, shape, dtype):
        limit = math.sqrt(6.0 / (shape[1] + shape[2]))
        return _upcycled_uniform(key, shape, jnp.dtype(dtype), limit)


# the gated linear unit's activation, by name
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def route_top_k(x, router, bias, top_k: int, route_scale: float = 1.0,
                route_norm: bool = True, n_group: int = 1,
                topk_group: int = 1, score: str = "sigmoid"):
    """x [T, h] -> (experts [T, k] int32, weights [T, k] float32).

    The product and the scores are float32 at the highest matmul
    precision whatever ``x``'s dtype: near-ties between the k-th and the
    (k+1)-th score decide which expert runs, and bf16 cannot tell them
    apart.

    ``score="sigmoid"``: the scores are the product's sigmoid, chosen by
    ``score + bias``, weighted by the chosen scores (renormalised where
    ``route_norm``). ``score="softmax"``: the ``top_k`` largest products
    are chosen and weighted by a softmax over them alone (a softmax over
    every expert renormalised over the chosen gives the same weights);
    ``bias`` is None and ``route_norm`` has nothing left to do.

    ``n_group`` > 1 limits the choice to groups (``noaux_tc``): the
    experts lie in ``n_group`` groups of equal size, a group's score is
    the sum of its 2 largest ``score + bias``, and the ``top_k`` are
    chosen inside the ``topk_group`` best groups. 1 group = no limit."""
    logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        top, sel = jax.lax.top_k(logits, top_k)
        return sel.astype(jnp.int32), jax.nn.softmax(top, -1) * route_scale
    scores = jax.nn.sigmoid(logits)
    biased = scores + bias.astype(jnp.float32)
    if n_group > 1:
        t, e = biased.shape
        grouped = biased.reshape(t, n_group, e // n_group)
        best2, _ = jax.lax.top_k(grouped, 2)
        _, keep = jax.lax.top_k(best2.sum(axis=-1), topk_group)
        kept = jnp.zeros((t, n_group), bool).at[
            jnp.arange(t)[:, None], keep].set(True)
        biased = jnp.where(kept[:, :, None], grouped,
                           -jnp.inf).reshape(t, e)
    _, sel = jax.lax.top_k(biased, top_k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), w * route_scale


def glu(g, u, act: str = "silu", limit=None):
    """The gated linear unit's middle in float32 from the two products:
    ``act(g) * u``, or with ``limit`` ``act(min(g, limit)) * clip(u,
    -limit, limit)`` (a SwiGLU limit: the gate clamped from above, the
    linear half on both sides)."""
    if limit is None:
        return ACTIVATIONS[act](g.astype(jnp.float32)) * u.astype(jnp.float32)
    g = jnp.minimum(g.astype(jnp.float32), limit)
    return ACTIVATIONS[act](g) * jnp.clip(u.astype(jnp.float32), -limit,
                                          limit)


def routed_experts_ffn(x, sel, w, gate, up, down, valid=None,
                       first_expert=None, act: str = "silu", limit=None):
    """sum_k w[t, k] * expert_{sel[t, k]}(x[t]) for x [T, h], an expert
    being ``(act(x Wg) * x Wu) Wd``, or with ``limit`` (static)
    ``(act(min(x Wg, limit)) * clip(x Wu, -limit, limit)) Wd``.

    gate/up [E, h, m], down [E, m, h]; ``act`` names an entry of
    ``ACTIVATIONS``. ``valid`` [T] bool: rows that are
    padding or dead take no expert (their output is 0, and they make no
    expert's weights be read). ``first_expert`` (an int): the layer is one
    chip's SHARE of an expert-parallel layer, ``sel`` names experts of
    the whole layer and the ``E`` held here are ``[first_expert,
    first_expert + E)``; a choice of an expert held elsewhere is dropped
    before the products (its term is that chip's to add). Returns (out
    [T, h] in x's dtype, stats) with ``stats`` = {"experts_hit": held
    experts with at least one row, "expert_rows_max": rows of the busiest
    held expert}, int32 scalars, and with a share also
    "expert_rows_here": the (row, choice) pairs that landed here."""
    from ...ops.pallas import grouped_matmul

    t, k = sel.shape
    n_exp = gate.shape[0]
    ids = sel.reshape(-1)
    if first_expert is not None:
        ids = ids - first_expert
        ids = jnp.where((ids >= 0) & (ids < n_exp), ids, n_exp)
    if valid is not None:
        # past every group: sorted last, visited by no product
        ids = jnp.where(jnp.repeat(valid, k), ids, n_exp)
    order = jnp.argsort(ids)                      # stable: by expert
    sizes = jnp.zeros((n_exp + 1,), jnp.int32).at[ids].add(1)[:n_exp]
    xs = jnp.take(x, order // k, axis=0)          # [T*k, h]
    g = grouped_matmul(xs, gate, sizes, preferred_element_type=x.dtype)
    u = grouped_matmul(xs, up, sizes, preferred_element_type=x.dtype)
    mid = glu(g, u, act, limit).astype(x.dtype)
    ys = grouped_matmul(mid, down, sizes,
                        preferred_element_type=jnp.float32)
    in_group = jnp.arange(t * k) < jnp.sum(sizes)
    ys = jnp.where(in_group[:, None], ys, 0.0) \
        * jnp.take(w.reshape(-1), order)[:, None]
    back = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))       # inverse permutation
    out = jnp.take(ys, back, axis=0).reshape(t, k, -1).sum(axis=1)
    stats = {"experts_hit": jnp.sum((sizes > 0).astype(jnp.int32)),
             "expert_rows_max": jnp.max(sizes)}
    if first_expert is not None:
        stats["expert_rows_here"] = jnp.sum(sizes)
    return out.astype(x.dtype), stats


class RoutedExperts(Layer):
    """Router + stacked gated experts. ``forward(x, valid=None,
    router_input=None)`` takes x [..., h] and returns (out, stats) — see
    :func:`routed_experts_ffn`; ``router_input`` [..., h], where given, is
    what the router reads in place of ``x``. ``score`` and ``act``: the
    router's scores and the experts' activation (:func:`route_top_k`,
    ``ACTIVATIONS``). With sigmoid scores ``expert_bias`` is a float32
    parameter, zero at initialisation, that enters the choice of experts
    only; a softmax router has none.

    ``n_group`` / ``topk_group``: group-limited routing
    (:func:`route_top_k`). ``limit``: the experts' SwiGLU limit
    (:func:`glu`; None = none). ``held=(first, count)``: this layer is one
    chip's share of ``num_experts``: the router scores all of them, the
    weights of ``count`` experts from ``first`` on are held, and the
    output is their part of the sum. Where the sorted (token, choice) rows
    of a call would pass ``ROWS_BYTES`` as float32 (a 16,384-token prefill
    at hidden 7168: 3.8 GB), the tokens are computed in blocks, one after
    another; the counters of the blocks are added, ``expert_rows_max`` is
    the largest."""

    def __init__(self, hidden_size: int, expert_width: int,
                 num_experts: int, top_k: int, route_scale: float = 1.0,
                 route_norm: bool = True, n_group: int = 1,
                 topk_group: int = 1, held=None, score: str = "sigmoid",
                 act: str = "silu", limit=None):
        super().__init__()
        if score not in ("sigmoid", "softmax") or act not in ACTIVATIONS:
            raise ValueError(f"score={score!r}, act={act!r}: the router "
                             f"scores by 'sigmoid' or 'softmax', the "
                             f"experts take one of {sorted(ACTIVATIONS)}")
        self.top_k = top_k
        self.route_scale = route_scale
        self.route_norm = route_norm
        self.n_group, self.topk_group = n_group, topk_group
        self.score, self.act, self.limit = score, act, limit
        self.first_expert = None if held is None else int(held[0])
        # tokens a block: the largest power of two whose rows fit
        self.token_block = 1 << int(math.log2(
            ROWS_BYTES // (4 * top_k * hidden_size)))
        h, m, e = hidden_size, expert_width, num_experts
        n_held = e if held is None else int(held[1])
        self.router = self.create_parameter(
            [h, e], default_initializer=XavierUniform(h, e))
        self.expert_bias = None if score == "softmax" else \
            self.create_parameter([e], dtype="float32",
                                  default_initializer=Constant(0.0))
        self.gate_proj = self.create_parameter(
            [n_held, h, m], default_initializer=_Upcycled())
        self.up_proj = self.create_parameter(
            [n_held, h, m], default_initializer=_Upcycled())
        self.down_proj = self.create_parameter(
            [n_held, m, h], default_initializer=_Upcycled())

    def forward(self, x, valid=None, router_input=None):
        def f(xv, rv, router, bias, gate, up, down):
            def block(flat, ok, rflat=None):
                sel, w = route_top_k(flat if rflat is None else rflat,
                                     router, bias, self.top_k,
                                     self.route_scale, self.route_norm,
                                     self.n_group, self.topk_group,
                                     score=self.score)
                return routed_experts_ffn(
                    flat, sel, w, gate, up, down,
                    valid=None if ok is None else ok.reshape(-1),
                    first_expert=self.first_expert, act=self.act,
                    limit=self.limit)

            flat = xv.reshape(-1, xv.shape[-1])
            rflat = None if rv is None else rv.reshape(-1, rv.shape[-1])
            tb, t = self.token_block, flat.shape[0]
            if t <= tb:
                out, stats = block(flat, valid, rflat)
                return out.reshape(xv.shape), stats
            ok = (jnp.ones((t,), bool) if valid is None
                  else valid.reshape(-1))
            nb = -(-t // tb)           # the last block's tail: no token

            def blocks(a):
                return jnp.pad(a, ((0, nb * tb - t),) + ((0, 0),) * (
                    a.ndim - 1)).reshape(nb, tb, *a.shape[1:])

            out, stats = jax.lax.map(lambda a: block(*a), (
                blocks(flat), blocks(ok)) + (
                () if rflat is None else (blocks(rflat),)))
            stats = {k: (v.max() if k == "expert_rows_max" else v.sum())
                     for k, v in stats.items()}
            return out.reshape(nb * tb, -1)[:t].reshape(xv.shape), stats

        return apply_op(f, x, router_input, self.router, self.expert_bias,
                        self.gate_proj, self.up_proj, self.down_proj,
                        op_name="routed_experts")
