"""Prompt-lookup n-gram draft proposer (speculative decoding).

The draft source for LOSSLESS n-gram speculative decoding (prompt
lookup): continue the longest recent-suffix match found earlier in the
context. Extracted from ``generate_speculative`` so the OFFLINE path
(:meth:`CausalLMEngine.generate_speculative`) and the BATCHED serving
path (per-slot proposers inside the continuous-batching engine's
speculative decode segments) share one tested unit instead of two
copies of the suffix-match logic.

Two layers:

- :class:`NgramIndex` — the incremental n-gram -> continuation index
  over a token list the caller owns;
- :class:`NgramProposer` — per-SEQUENCE state (the context list + its
  index): seed it with the prompt, ``extend()`` it with each accepted
  token as decoding streams, ``propose()`` drafts. This is the object
  the serving engines keep per request id; a preempted/replayed request
  simply rebuilds it from ``prompt + generated`` (the index is a pure
  function of the context).

Plus the DEVICE twin: :func:`propose_device` runs the same suffix-match
lookup as a fixed-shape jax computation over per-slot history windows
held on device — the draft source of the continuous-batching engines'
``spec_mode="device"`` fused segment, where a host proposer would cost
a device→host sync per verify step.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["NgramIndex", "NgramProposer", "propose_device"]


class NgramIndex:
    """Incremental prompt-lookup index: maps each n-gram (n <=
    ngram_max) to the continuation start of its most recent occurrence.
    Registration lags one position behind the context tail so the
    current suffix never matches itself; amortized O(ngram_max) per
    appended token (a fresh linear scan per proposal would be O(L) of
    host work per verify step — the latency this path exists to cut)."""

    def __init__(self, ngram_max: int):
        if not isinstance(ngram_max, (int, np.integer)) or ngram_max < 1:
            raise ValueError(
                f"ngram_max must be a positive int, got {ngram_max!r}")
        self.n_max = int(ngram_max)
        self.maps = {n: {} for n in range(1, self.n_max + 1)}
        self._reg = 0          # grams ending before this index are in

    def _register_upto(self, ctx, end):
        for j in range(self._reg, end):
            for n in range(1, min(self.n_max, j + 1) + 1):
                self.maps[n][tuple(ctx[j - n + 1:j + 1])] = j + 1
        self._reg = max(self._reg, end)

    def propose(self, ctx, k: int):
        """Up to ``k`` draft tokens continuing the longest recent
        suffix of ``ctx`` seen earlier in ``ctx`` (padded with the last
        draft — or the tail token on a total miss — to exactly k)."""
        L = len(ctx)
        self._register_upto(ctx, L - 1)   # exclude the current tail
        for n in range(min(self.n_max, L - 1), 0, -1):
            start = self.maps[n].get(tuple(ctx[L - n:]))
            if start is not None:
                cont = ctx[start:start + k]
                if cont:
                    return (cont + [cont[-1]] * (k - len(cont)))[:k]
        return [ctx[-1]] * k


class NgramProposer:
    """One sequence's draft proposer: context (prompt + every accepted
    token so far) plus its :class:`NgramIndex`, updated INCREMENTALLY
    as tokens stream — the serving engines call ``extend()`` with each
    segment step's accepted tokens and ``propose()`` once per verify
    forward, so per-step host work stays O(ngram_max * k), independent
    of the context length."""

    def __init__(self, tokens, draft_k: int, ngram_max: int = 3):
        if not isinstance(draft_k, (int, np.integer)) or draft_k < 1:
            raise ValueError(
                f"draft_k must be a positive int, got {draft_k!r}")
        self.k = int(draft_k)
        self.ctx: List[int] = [int(t) for t in np.asarray(tokens)
                               .reshape(-1)]
        self._index = NgramIndex(ngram_max)
        # host-side accounting the engines aggregate per segment
        self.proposed = 0
        self.accepted = 0

    def extend(self, tokens) -> None:
        """Append accepted tokens to the context (the index registers
        them lazily at the next ``propose``)."""
        self.ctx.extend(int(t) for t in tokens)

    def propose(self, k=None) -> List[int]:
        """Draft ``k`` (default: this proposer's ``draft_k``) tokens
        from the current context."""
        k = self.k if k is None else int(k)
        self.proposed += k
        return self._index.propose(self.ctx, k)


def propose_device(hist, hl, k: int, ngram_max: int):
    """Fixed-shape device twin of :meth:`NgramIndex.propose` over
    per-row history windows: ``hist`` is ``[B, H]`` int32 (each row the
    LAST ``hl[b] <= H`` context tokens, left-aligned), returns ``[B, k]``
    int32 drafts. For any row whose full context fits its window this
    produces EXACTLY the host proposer's drafts — longest suffix match
    first, most recent occurrence within a length, continuation padded
    with its own last token, total miss degrading to the tail token —
    so the host/device draft sources only diverge once a context
    outgrows the ring, and even then only in ACCEPTANCE (emitted tokens
    are always the model's own greedy picks; see the engines'
    speculative docs). Pure jnp (traceable inside ``lax.scan``); cost
    is O(H * ngram_max) per row per call, independent of context
    length."""
    H = hist.shape[1]
    n_max = int(ngram_max)
    k = int(k)

    def one(row, ln):
        j = jnp.arange(H)
        i = jnp.arange(n_max)
        # token at window position j-i (the gram ending at j, read
        # back-to-front) vs the current tail suffix token at ln-1-i;
        # distinct sentinels for the two out-of-range sides so a
        # padding position can never fake a match
        pos = j[:, None] - i[None, :]
        tokj = jnp.where(pos >= 0, row[jnp.clip(pos, 0, H - 1)], -1)
        tpos = ln - 1 - i
        tail = jnp.where(tpos >= 0, row[jnp.clip(tpos, 0, H - 1)], -2)
        run = jnp.cumprod((tokj == tail[None, :]).astype(jnp.int32),
                          axis=1)      # run[j, n-1]: n-gram match at j
        n_arr = i + 1
        # a valid length-n match needs the gram fully inside the window
        # (j >= n-1) and must exclude the current suffix itself
        # (j <= ln-2 — the host index registers one behind the tail)
        ok = ((run > 0) & (j[:, None] >= n_arr[None, :] - 1)
              & (j[:, None] <= ln - 2))
        # longest n wins, most recent j breaks ties — exactly the host
        # loop order (n descending, map holds the latest occurrence)
        score = jnp.where(ok, n_arr[None, :] * H + j[:, None], -1)
        j_sel = jnp.argmax(score) // n_max
        start = jnp.where(jnp.max(score) >= 0, j_sel + 1, ln - 1)
        # clamping to the window tail replicates the host's pad-with-
        # last (and the total-miss [tail]*k fallback, via start=ln-1)
        idx = jnp.clip(start + jnp.arange(k), 0, jnp.maximum(ln - 1, 0))
        return row[idx].astype(jnp.int32)

    return jax.vmap(one)(hist, hl)
