"""Window and full attention layers side by side, for the paged serving
engine: what the decoders with such layers (``models/afmoe.py``,
``models/smallthinker.py``) share.

A layer is a full layer (every earlier position) or a window layer (the
last ``window`` positions, rope on its queries and keys). Its KV lives in
one of TWO geometries: a full layer holds every position of a row in
pages, a window layer the last ``window`` in a RING of pages
(``inference/paged_cache.WindowedPageAllocator``). The model contract the
engine reads (``init_cache`` / ``forward_with_cache``: one-shot prefill
from position 0 into a bucket-wide cache; ``init_paged_cache`` /
``forward_decode_paged``; ``paged_layout``) is written here once. What a
model class says for itself: its projections and norms, what a head goes
through before attention and what the attention's output goes through
(:class:`WindowedAttention`'s three hooks), its embedding, and its
decoder layer's ``forward_with_cache`` / ``forward_decode_paged``, each
returning ``(x, cache, routing stats or None)``.

A configuration gives ``window`` (the window layers' width),
``is_sliding(layer)``, ``head_dim``, ``num_key_value_heads``,
``num_hidden_layers``, ``rope_theta`` and ``dtype``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.autograd import apply_op
from ..core.tensor import Tensor
from ..nn.layer.layers import Layer
from .llama import _rope_cos_sin, apply_rotary_emb

__all__ = ["ring_pages", "WindowedAttention", "WindowedModel",
           "WindowedForCausalLM"]


def _val(t):
    return t.value if isinstance(t, Tensor) else t


def ring_pages(window: int, page_size: int) -> int:
    """Pages a window layer holds for one row at the most: the window's
    own, and one more so that the page being written never shares a ring
    slot with a page the window still reaches."""
    return -(-window // page_size) + 1


class WindowedAttention(Layer):
    """Grouped-query attention of a full layer (``window`` None: no
    position encoding) or a window layer (rope, the last ``window`` keys).
    A subclass makes ``q_proj``, ``k_proj``, ``v_proj``, ``o_proj`` and
    sets ``window``, ``num_heads``, ``kv_heads`` and ``config``; the hooks
    say what else its heads go through."""

    def _head_weights(self) -> tuple:
        """Parameters ``_heads`` takes besides the projections."""
        return ()

    def _heads(self, qv, kv, vv, weights, cos, sin):
        """Projections [B, S, H*D] -> heads [B, S, H, D] in the cache
        dtype, a window layer's queries and keys rotated in float32.
        ``cos``/``sin``: float32, broadcastable to [B, S, 1, D/2]."""
        b, s, hd = qv.shape[0], qv.shape[1], self.config.head_dim
        qh = qv.reshape(b, s, self.num_heads, hd)
        kh = kv.reshape(b, s, self.kv_heads, hd)
        if self.window is not None:
            qh = apply_rotary_emb(qh.astype(jnp.float32), cos, sin
                                  ).astype(qv.dtype)
            kh = apply_rotary_emb(kh.astype(jnp.float32), cos, sin
                                  ).astype(kv.dtype)
        return qh, kh, vv.reshape(b, s, self.kv_heads, hd)

    def _out(self, ctx, x):
        """The attention's output [B, S, H*D] -> [B, S, hidden]; ``x`` is
        the layer's input to attention."""
        return self.o_proj(ctx)

    def forward_with_cache(self, x, cos, sin, cache):
        """Prefill from position 0: x [B, S, h]; ``cache`` (k, v)
        [B, S_max, Hkv, D] takes the prompt's keys and values at [0, S).
        Returns (out, new_cache)."""
        from ..ops.pallas import flash_attention

        b, s = x.shape[0], x.shape[1]
        weights = self._head_weights()

        def attend(qv, kv, vv, *rest):
            hw, (kc, vc) = rest[:len(weights)], rest[len(weights):]
            qh, kh, vh = self._heads(qv, kv, vv, hw,
                                     cos[None, :s, None, :],
                                     sin[None, :s, None, :])
            ctx = flash_attention(qh, kh, vh, causal=True,
                                  window=self.window)
            kc = jax.lax.dynamic_update_slice_in_dim(
                kc, kh.astype(kc.dtype), 0, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(
                vc, vh.astype(vc.dtype), 0, axis=1)
            return ctx.reshape(b, s, -1), kc, vc

        ctx, kc, vc = apply_op(
            attend, self.q_proj(x), self.k_proj(x), self.v_proj(x),
            *weights, *cache, op_name="cached_attention")
        return self._out(ctx, x), (_val(kc), _val(vc))

    def forward_decode_paged(self, x, cos, sin, cache, page_table, lens,
                             live):
        """One token per row at per-row position ``lens``. A full layer's
        ``page_table`` row lists the row's pages in order; a window
        layer's is a RING of ``ring_pages`` slots in which position p
        lives at slot (p // page_size) % ring: the kernel is handed the
        ring turned so that the window's first page comes first, and the
        lengths counted from that page."""
        from ..ops.paged_attention import paged_decode_mha

        b = x.shape[0]
        weights = self._head_weights()

        def attend(qv, kv, vv, *rest):
            hw, (kp, vp) = rest[:len(weights)], rest[len(weights):]
            ps, cols = kp.shape[1], page_table.shape[1]
            c = cos[lens][:, None, None, :]
            sn = sin[lens][:, None, None, :]
            qh, kh, vh = self._heads(qv, kv, vv, hw, c, sn)
            # a dead row attends nothing: length 0 costs the kernel no page
            new_len = jnp.where(live, lens + 1, 0)
            col = lens // ps
            table = page_table
            if self.window is not None:
                col = col % cols
                first = jnp.maximum(new_len - self.window, 0) // ps
                turn = (first[:, None] + jnp.arange(cols)[None, :]) % cols
                table = jnp.take_along_axis(page_table, turn, axis=1)
                new_len = new_len - first * ps
            page = page_table[jnp.arange(b), jnp.minimum(col, cols - 1)]
            # dead rows / unmapped pages -> sentinel, dropped by scatter
            page = jnp.where(live & (page >= 0), page, kp.shape[0])
            kp = kp.at[page, lens % ps].set(kh[:, 0].astype(kp.dtype),
                                            mode="drop")
            vp = vp.at[page, lens % ps].set(vh[:, 0].astype(vp.dtype),
                                            mode="drop")
            ctx = paged_decode_mha(qh[:, 0], kp, vp, table, new_len,
                                   window=self.window)
            return ctx.reshape(b, 1, -1), kp, vp

        ctx, kp, vp = apply_op(
            attend, self.q_proj(x), self.k_proj(x), self.v_proj(x),
            *weights, *cache, op_name="paged_attention")
        return self._out(ctx, x), (_val(kp), _val(vp))


class WindowedModel(Layer):
    """The decoder stack: a subclass makes ``config``, ``embed_tokens``,
    ``layers`` and ``norm``."""

    def _embed(self, input_ids):
        return self.embed_tokens(input_ids)

    def _rope(self, positions: int):
        cfg = self.config
        return _rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                             jnp.float32)

    def forward_with_cache(self, input_ids, caches, pos=0, last_idx=None):
        if not (isinstance(pos, int) and pos == 0):
            raise NotImplementedError(
                "prefill at an offset (chunked prefill, a warm prefix hit) "
                "is not implemented for window layers")
        x = self._embed(input_ids)
        s = x.shape[1]
        cos, sin = self._rope(s)
        # bucket padding past the prompt's last token takes no expert
        valid = (None if last_idx is None
                 else (jnp.arange(s) <= last_idx)[None, :])
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            x, cache, _ = layer.forward_with_cache(x, cos, sin, cache,
                                                   valid=valid)
            new_caches.append(cache)
        if last_idx is not None:
            # the head is wide: only the position that is sampled
            x = apply_op(lambda v: jax.lax.dynamic_slice_in_dim(
                v, last_idx, 1, axis=1), x, op_name="last_position")
        return self.norm(x), new_caches

    def forward_decode_paged(self, input_ids, caches, page_table, lens,
                             live):
        cfg = self.config
        full_table, ring_table = page_table
        x = self._embed(input_ids)
        ps = caches[0][0].shape[1]
        cos, sin = self._rope(full_table.shape[1] * ps)
        lens = jnp.minimum(lens, full_table.shape[1] * ps - 1)
        new_caches = []
        hit = rows_max = jnp.int32(0)
        for i, (layer, cache) in enumerate(zip(self.layers, caches)):
            x, cache, stats = layer.forward_decode_paged(
                x, cos, sin, cache,
                ring_table if cfg.is_sliding(i) else full_table, lens, live)
            new_caches.append(cache)
            if stats is not None:
                hit = hit + _val(stats["experts_hit"])
                rows_max = rows_max + _val(stats["expert_rows_max"])
        return (self.norm(x), new_caches,
                {"experts_hit": hit, "expert_rows_max": rows_max})


class WindowedForCausalLM(Layer):
    """The paged engine's model contract over a :class:`WindowedModel`:
    a subclass makes ``config``, ``model`` and an untied ``lm_head``."""

    def _logits(self, hidden):
        """The head's product with a float32 result, whatever the weights'
        dtype: the top of a wide vocabulary's bf16 logits would be rounded
        to steps as large as the differences between them."""
        return apply_op(
            lambda h, w: jnp.matmul(h, w,
                                    preferred_element_type=jnp.float32),
            hidden, self.lm_head.weight, op_name="lm_head")

    def forward(self, input_ids):
        """Logits [B, S, V] of a whole sequence, no cache kept."""
        ids = _val(input_ids)
        logits, _ = self.forward_with_cache(
            input_ids, self.init_cache(ids.shape[0], ids.shape[1]), 0)
        return logits

    def init_cache(self, batch_size: int, max_len: int):
        cfg = self.config
        shape = (batch_size, max_len, cfg.num_key_value_heads, cfg.head_dim)
        dt = jnp.dtype(cfg.dtype)
        return [(jnp.zeros(shape, dt), jnp.zeros(shape, dt))
                for _ in range(cfg.num_hidden_layers)]

    def forward_with_cache(self, input_ids, caches, pos=0, last_idx=None):
        """(logits, new_caches) of a one-shot prefill from position 0.
        ``last_idx`` (a traced position): logits [B, 1, V] of that
        position only, and the padding after it is routed nowhere."""
        hidden, caches = self.model.forward_with_cache(
            input_ids, caches, pos, last_idx=last_idx)
        return self._logits(hidden), caches

    def paged_layout(self, page_size: int) -> dict:
        """What the paged engine has to know of this model's cache: which
        layers keep a ring of the last ``window`` positions, and the
        ring's pages; that prefill takes ``last_idx``; that a decode step
        hands out counters."""
        cfg = self.config
        return {"ring": {"window": cfg.window,
                         "ring_pages": ring_pages(cfg.window, page_size),
                         "window_layers": tuple(
                             cfg.is_sliding(i)
                             for i in range(cfg.num_hidden_layers))},
                "last_idx": True, "counters": True,
                "rows": "per-head K and V in two geometries (its window "
                        "layers keep a ring of pages)"}

    def init_paged_cache(self, num_pages: int, page_size: int,
                         window_pages: int = 0):
        """Per-layer page pools: ``num_pages`` for a full layer,
        ``window_pages`` for a window layer."""
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        out = []
        for i in range(cfg.num_hidden_layers):
            shape = (window_pages if cfg.is_sliding(i) else num_pages,
                     page_size, cfg.num_key_value_heads, cfg.head_dim)
            out.append((jnp.zeros(shape, dt), jnp.zeros(shape, dt)))
        return out

    def forward_decode_paged(self, input_ids, caches, page_table, lens,
                             live):
        """(logits [B, 1, V], new_caches, routing counts) — one decode
        step over the two page tables ``(full, ring)``."""
        hidden, caches, stats = self.model.forward_decode_paged(
            input_ids, caches, page_table, lens, live)
        return self._logits(hidden), caches, stats
