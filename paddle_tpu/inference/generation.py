"""Autoregressive generation engine over the KV-cache decode path.

The serving counterpart of the reference's fused_multi_transformer decode
loop (``fused_multi_transformer_op.cu.h:745`` masked MHA over CacheKV; the
reference drives it token-by-token from AnalysisPredictor). TPU-native
form: ONE jitted prefill program + ONE jitted multi-token decode program
(``lax.scan`` over steps, cache carried functionally, cache buffers
donated) — token steps never leave the device, so the host round-trip
is paid once per generate() call, not once per token.
"""
from __future__ import annotations

import functools
import heapq
import time
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .. import monitor
from .. import tracing as trace
from ..core.tensor import Tensor
from ..nn.functional_call import substituted_state
from .ngram import NgramIndex, NgramProposer, propose_device

__all__ = ["GenerationConfig", "CausalLMEngine",
           "PagedContinuousBatchingEngine", "prefill_buckets_for",
           "RequestFault", "EngineFault", "classify_fault",
           "REQUEST_SITES", "PagePoolExhausted", "ADMISSION_MODES",
           "NgramProposer"]


# -- fault taxonomy (serving-path blast-radius classification) ---------------
#
# At serving scale faults are routine inputs, not exceptional shutdowns.
# The scheduler needs to know, for every exception an engine call
# raises, how much state it poisons — that is the whole containment
# contract:
#
# - REQUEST-scoped: one request's admission went wrong (malformed
#   prompt the model chokes on, a prefill error). The engine's abort
#   guards already reclaimed the slot/pages, device state for everyone
#   else is coherent — fail THAT request with its cause, keep serving.
# - ENGINE-scoped: device state is suspect (an XLA/device error inside
#   a decode segment that mutates every slot's cache). The engine must
#   be rebuilt (`reset_state`) and in-flight requests replayed.
# - FATAL: process-level signals (KeyboardInterrupt/SystemExit) that
#   must never be swallowed by a recovery loop.

class RequestFault(RuntimeError):
    """A fault scoped to ONE request: fail that request with its cause
    and keep serving everyone else (the engine's device state is
    coherent — admission abort guards reclaimed any claimed capacity).
    Raise this from model/engine code running single-request work (the
    admission/prefill/chunk seams, where the scheduler knows which
    request is in flight). At a BATCH-wide seam (a decode segment over
    every slot) there is no single request to attribute it to, so a
    supervisor must still treat it as engine-scoped there."""


class EngineFault(RuntimeError):
    """A fault that poisons the ENGINE's device state (e.g. a device
    error mid decode segment): the supervisor must rebuild state
    (:meth:`PagedContinuousBatchingEngine.reset_state`) and replay in-flight
    requests from their stored prompt + tokens emitted so far."""


# seams where an unclassified exception defaults to request scope: the
# engine was doing single-request work behind an abort guard, so shared
# device state was never touched
REQUEST_SITES = frozenset({"admit", "prefill", "chunk"})

# paged-engine admission policies (see PagedContinuousBatchingEngine)
ADMISSION_MODES = ("reserved", "optimistic")

# speculative-decoding execution modes (see PagedContinuousBatchingEngine):
# "host" proposes on host with a device→host readback per verify step;
# "device" fuses propose→verify→accept into one compiled segment loop
# (the history ring IS the draft source — one readback per segment)
SPEC_MODES = ("host", "device")

# device-mode draft sources: "ngram" = suffix-match lookup over the
# slot's device history ring (ngram.propose_device, the host proposer's
# windowed twin); "self" = reuse the verify forward's trailing greedy
# tokens as the NEXT step's drafts (EAGLE-lite, no trained heads — the
# ring still bootstraps each segment's first step)
SPEC_DRAFTS = ("ngram", "self")


class PagePoolExhausted(RuntimeError):
    """Optimistic-mode page growth could not be satisfied in the
    inter-segment gap even for the requests the caller chose to keep.

    ``rids`` names the requests whose next-segment growth the pool
    cannot cover. A serving scheduler never lets this surface — it
    preempts victims in the gap until growth fits (or fails a request
    that cannot fit even alone, with this as the typed cause); a bare
    engine driver that ignores memory pressure sees it loudly from
    ``decode_segment`` instead of silently corrupting KV."""

    def __init__(self, rids, message: str):
        super().__init__(message)
        self.rids = list(rids)


def classify_fault(exc: BaseException, site: str = "decode") -> str:
    """Blast radius of ``exc`` raised at serving seam ``site``:
    ``"request"`` / ``"engine"`` / ``"fatal"``.

    Explicit :class:`RequestFault` / :class:`EngineFault` win over the
    site default; anything unclassified is request-scoped at the
    single-request seams (:data:`REQUEST_SITES` — admission work runs
    behind abort guards that reclaim capacity) and engine-scoped at the
    batch-wide ones (``decode``, ``collect``). Caveat for supervisors:
    a ``"request"`` verdict is only ACTIONABLE where a single request
    is in flight — at a batch-wide seam there is nobody to pin it on,
    so the serving scheduler escalates any non-fatal fault there to
    engine recovery regardless of this verdict."""
    if isinstance(exc, (KeyboardInterrupt, SystemExit)):
        return "fatal"
    if isinstance(exc, EngineFault):
        return "engine"
    if isinstance(exc, RequestFault):
        return "request"
    return "request" if site in REQUEST_SITES else "engine"


def prefill_buckets_for(spec, max_len: int, floor: int = 16):
    """Normalize a ``prefill_buckets`` engine knob to a sorted tuple of
    pad targets, or None (bucketing disabled — exact-length prefill, one
    compiled program per distinct prompt length).

    ``"auto"`` (the engines' default) gives powers of two from ``floor``
    up to ``max_len`` — O(log max_len) prefill programs instead of
    O(#distinct prompt lengths); an explicit sequence is deduped/sorted
    and always extended to cover ``max_len`` (every admissible prompt
    must land in SOME bucket)."""
    if spec is None:
        return None
    if spec == "auto":
        out = []
        b = int(floor)
        if b < 1:
            raise ValueError(f"bucket floor must be >= 1, got {floor}")
        while b < max_len:
            out.append(b)
            b *= 2
        out.append(max_len)
        return tuple(out)
    out = sorted({int(b) for b in spec})
    if not out or out[0] < 1:
        raise ValueError(f"prefill_buckets must be positive ints, got "
                         f"{spec!r}")
    if out[-1] > max_len:
        raise ValueError(
            f"prefill bucket {out[-1]} exceeds max_len={max_len}")
    if out[-1] < max_len:
        out.append(max_len)
    return tuple(out)


def _normalize_prefill_chunk(prefill_chunk, max_len: int):
    """Validate the ``prefill_chunk`` engine knob (shared by all
    engines). ``max_len`` must be a multiple of the chunk: chunks start
    at multiples of C, so divisibility is exactly what guarantees every
    (padded) chunk window [pos, pos+C) stays inside the cache — an
    overhanging final chunk would be CLAMPED by dynamic_update_slice
    and silently overwrite earlier prompt KV."""
    if prefill_chunk is None:
        return None
    if isinstance(prefill_chunk, bool) or not isinstance(
            prefill_chunk, (int, np.integer)) or prefill_chunk < 1:
        raise ValueError(
            f"prefill_chunk must be a positive int or None, got "
            f"{prefill_chunk!r}")
    if max_len % int(prefill_chunk) != 0:
        raise ValueError(
            f"max_len({max_len}) must be a multiple of "
            f"prefill_chunk({int(prefill_chunk)}) — a final chunk "
            "overhanging the cache would clamp and corrupt earlier KV")
    return int(prefill_chunk)


def _bucket_for(buckets, plen: int) -> int:
    """Smallest bucket >= plen (buckets sorted, last == max_len)."""
    for b in buckets:
        if b >= plen:
            return b
    return buckets[-1]


def _pad_ids(ids: np.ndarray, width: int) -> np.ndarray:
    """Right-pad [B, plen] token ids to [B, width] (pad id 0). Padded
    prefill is numerically identical to exact prefill: causal masking
    means no REAL query position ever attends a pad key, the engines
    read logits at the true last position (not -1), and the garbage KV
    the pad tail writes past plen is masked by every decode read (all
    decode attention is length-masked) and overwritten as the sequence
    grows."""
    plen = ids.shape[1]
    if plen >= width:
        return ids
    return np.pad(ids, ((0, 0), (0, width - plen)))


class GenerationConfig:
    """Per-request decoding parameters.

    Validated at CONSTRUCTION: in online serving a config arrives from
    the network per request, and a malformed one must be rejected at
    admission (an HTTP 400), never crash a shared decode segment that
    other requests are riding in.
    """

    def __init__(self, max_new_tokens: int = 64, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0, do_sample: bool = False,
                 eos_token_id: Optional[int] = None, seed: int = 0,
                 speculative: bool = False,
                 draft_k: Optional[int] = None,
                 adapter: Optional[str] = None):
        INT32_MAX = 2 ** 31 - 1   # engine state is int32 on device; a
        #                           larger value must fail HERE, not
        #                           leak a slot mid-admission
        if (isinstance(max_new_tokens, bool)
                or not isinstance(max_new_tokens, (int, np.integer))
                or not 1 <= max_new_tokens <= INT32_MAX):
            raise ValueError(
                f"max_new_tokens must be an int in [1, 2**31), got "
                f"{max_new_tokens!r}")
        if not (isinstance(temperature, (int, float, np.floating))
                and temperature > 0):
            # `not (x > 0)` also rejects NaN
            raise ValueError(
                f"temperature must be > 0, got {temperature!r}")
        if (isinstance(top_k, bool)
                or not isinstance(top_k, (int, np.integer))
                or not 0 <= top_k <= INT32_MAX):
            raise ValueError(
                f"top_k must be an int in [0, 2**31) (0 disables), got "
                f"{top_k!r}")
        if not (isinstance(top_p, (int, float, np.floating))
                and 0 < top_p <= 1):
            raise ValueError(
                f"top_p must satisfy 0 < top_p <= 1, got {top_p!r}")
        if eos_token_id is not None and (
                isinstance(eos_token_id, bool)
                or not isinstance(eos_token_id, (int, np.integer))
                or not 0 <= eos_token_id <= INT32_MAX):
            raise ValueError(
                f"eos_token_id must be an int in [0, 2**31) or None, "
                f"got {eos_token_id!r}")
        if isinstance(seed, bool) or not isinstance(seed,
                                                   (int, np.integer)):
            raise ValueError(f"seed must be an int, got {seed!r}")
        if draft_k is not None and (
                isinstance(draft_k, bool)
                or not isinstance(draft_k, (int, np.integer))
                or not 1 <= draft_k <= 256):
            # 256 is far above any useful draft window; an absurd value
            # must fail at admission, not compile an absurd program
            raise ValueError(
                f"draft_k must be an int in [1, 256] or None "
                f"(engine default), got {draft_k!r}")
        if adapter is not None and (not isinstance(adapter, str)
                                    or not adapter
                                    or len(adapter) > 256):
            # a malformed adapter name must fail at config construction
            # (the HTTP 400 path), never inside a shared decode segment
            raise ValueError(
                f"adapter must be a non-empty str (<= 256 chars) or "
                f"None (base model), got {adapter!r}")
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.do_sample = bool(do_sample)
        self.eos_token_id = (None if eos_token_id is None
                             else int(eos_token_id))
        self.seed = int(seed)
        # speculative decoding opt-in (continuous-batching engine
        # built with draft_k > 0): greedy requests propose/verify
        # n-gram drafts per segment step; sampled requests fall back to
        # plain decode (lossless acceptance needs the argmax target).
        # draft_k caps THIS request's draft window (None = the
        # engine's).
        self.speculative = bool(speculative)
        self.draft_k = None if draft_k is None else int(draft_k)
        # multi-tenant LoRA: the fine-tune this request decodes under
        # (None = base model). Resolved to a bank index at admission —
        # an unknown/unloading name fails THAT request at the admit
        # seam (request-scoped), everyone else keeps serving.
        self.adapter = adapter


def _sample(logits, key, cfg: GenerationConfig):
    """One next-token choice from [B, V] logits."""
    if not cfg.do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / max(cfg.temperature, 1e-6)
    if cfg.top_k and cfg.top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -cfg.top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if cfg.top_p < 1.0:
        sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set with cumulative prob >= top_p; cutoff = last kept logit
        keep = cum - probs < cfg.top_p
        cutoff = jnp.min(jnp.where(keep, sorted_l, jnp.inf), axis=-1,
                         keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def _filter_rows(logits, temp, top_k, top_p):
    """`_sample`'s temperature, top-k and top-p with TRACED per-row
    parameters ([B] vectors over [B, V] logits): the float32 logits a
    categorical draw takes. A row with top_k == 0 / top_p == 1.0 skips
    that filter (`_sample`'s `if` branches, expressed as masks)."""
    vocab = logits.shape[-1]
    scaled = logits.astype(jnp.float32) / jnp.maximum(temp, 1e-6)[:, None]
    # values alone are sorted, so an unstable sort gives the stable one's
    # result; the chip's compiler takes 8 s for it and 24 s for the
    # stable one, in every program that holds a sampling branch
    desc = jnp.sort(scaled, axis=-1, stable=False)[:, ::-1]
    k_eff = jnp.clip(top_k, 1, vocab)
    kth = jnp.take_along_axis(desc, (k_eff - 1)[:, None], axis=-1)
    scaled = jnp.where((top_k > 0)[:, None] & (scaled < kth),
                       -jnp.inf, scaled)
    # top-p runs over the top-k-FILTERED logits (_sample's order)
    desc2 = jnp.sort(scaled, axis=-1, stable=False)[:, ::-1]
    probs = jax.nn.softmax(desc2, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = cum - probs < top_p[:, None]
    cutoff = jnp.min(jnp.where(keep, desc2, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where((top_p < 1.0)[:, None] & (scaled < cutoff),
                     -jnp.inf, scaled)


def _sample_rows(logits, key, samp):
    """Per-ROW next-token choice from [B, V] logits: every sampling
    parameter (greedy-vs-sample, temperature, top-k, top-p, eos) is a
    per-slot device VECTOR installed at admission, not a trace constant
    — so ONE compiled segment program serves any mix of per-request
    GenerationConfigs (the continuous-batching engine's online form;
    the old cfg-keyed specialization recompiled per distinct config).

    Greedy rows reduce to the exact argmax `_sample` computes, so mixed
    batches keep bitwise greedy parity with ``CausalLMEngine``. Rows with
    top_k == 0 / top_p == 1.0 skip those filters (same gating as
    `_sample`'s `if` branches, expressed as masks).

    Each row draws from its OWN noise stream: the request's seed (a
    per-slot vector) is folded into the shared per-step key, so a
    request's sampled trajectory depends on ITS GenerationConfig.seed,
    not on which other requests share the batch."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def drawn(_):
        scaled = _filter_rows(logits, samp["temp"], samp["top_k"],
                              samp["top_p"])
        keys = jax.vmap(lambda s: jax.random.fold_in(key, s))(
            samp["seed"])
        return jax.vmap(jax.random.categorical)(keys, scaled) \
            .astype(jnp.int32)

    # all-greedy batches (the do_sample=False default) skip the whole
    # sort/softmax/cumsum pipeline at RUNTIME — lax.cond on a traced
    # scalar executes one branch, so the single-program property holds
    # while a greedy segment pays only the argmax
    sampled = jax.lax.cond(jnp.any(samp["sample"]), drawn,
                           lambda _: greedy, None)
    return jnp.where(samp["sample"], sampled, greedy)


def _sample_one(logits, seed, temp, top_k, top_p, do_sample):
    """`_sample` of ONE request's [1, V] logits with every parameter a
    traced scalar, so that one compiled program samples any request's
    first token: greedy is `_sample`'s argmax; a sampled request draws
    from ``PRNGKey(seed)`` over the logits `_filter_rows` gives, in
    `_sample`'s order of operations. ``lax.cond`` runs one branch: a
    greedy admission pays the argmax alone."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def drawn(_):
        scaled = _filter_rows(logits, temp[None], top_k[None],
                              top_p[None])
        return jax.random.categorical(
            jax.random.PRNGKey(seed), scaled, axis=-1).astype(jnp.int32)

    return jax.lax.cond(do_sample, drawn, lambda _: greedy, None)[0]


def _segment_key(seed, counter):
    """A segment's sampling key, computed INSIDE the segment programs
    from two uint32 scalars that ride as arguments (`_u32`): bit for
    bit ``jax.random.fold_in(jax.random.PRNGKey(seed), counter)`` of the
    host integers. Made eagerly, the seed and the fold were two device
    programs of their own before every segment."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), counter)


def _u32(n: int):
    """The low 32 bits of a host integer as a numpy scalar: what
    ``PRNGKey`` and ``fold_in`` keep of a Python int without x64."""
    return np.uint32(int(n) & 0xFFFFFFFF)


def _live_samp(samp, active):
    """The per-slot sampling vectors as a segment program reads them:
    a slot's sampled flag counts only while the slot holds a request
    (``active``, the host's live mask), so retirement writes nothing on
    the device and an all-greedy batch still skips `_sample_rows`'
    sort, softmax and cumsum."""
    return dict(samp, sample=samp["sample"] & active)


def _prompt_ids(prompt):
    """Normalize a prompt (Tensor / ndarray / list) to int32 [1, plen].
    serve()'s capacity probe and add_request MUST agree on this — a
    Tensor probed with a bare np.asarray becomes a size-1 object array
    and defeats the paged defer logic."""
    # lint: allow-host-sync(a prompt arrives from the host; a Tensor's
    # ids are read once, at admission, before any device work of it)
    return np.asarray(prompt.value if isinstance(prompt, Tensor)
                      else prompt).astype(np.int32).reshape(1, -1)


def _prompt_len(prompt) -> int:
    return _prompt_ids(prompt).shape[1]


# back-compat alias: the n-gram machinery lives in inference/ngram.py
# now (shared by the offline generate_speculative path and the batched
# serving engines' per-slot proposers)
_NgramIndex = NgramIndex


class CausalLMEngine:
    """Compiled prefill + decode for a causal LM exposing
    ``init_cache`` / ``forward_with_cache`` (LlamaForCausalLM, GPT...).

    Usage::

        eng = CausalLMEngine(model, max_batch=8, max_len=2048)
        out_ids = eng.generate(prompt_ids, GenerationConfig(max_new_tokens=64))
    """

    def __init__(self, model, max_batch: int, max_len: int,
                 prefill_buckets="auto",
                 prefill_chunk: Optional[int] = None):
        self.model = model
        self.max_batch = max_batch
        self.max_len = max_len
        self.prefill_buckets = prefill_buckets_for(prefill_buckets,
                                                   max_len)
        self.prefill_chunk = _normalize_prefill_chunk(prefill_chunk,
                                                      max_len)
        self.params = {k: p.value for k, p in model.named_parameters()}

        def prefill(params, ids, caches, last_idx):
            logits, caches = self._fwd(params, ids, caches, 0)
            return logits[:, last_idx], caches

        # jax.jit's own cache specializes per ids shape — with bucketing
        # the prompt is padded to one of O(log max_len) widths, so the
        # compiled prefill program count is bounded by len(buckets)
        # instead of #distinct prompt lengths. last_idx (the true last
        # prompt position) is a traced value, not a shape. decode stays
        # keyed by GenerationConfig because the config is *trace-static*
        # (branching on do_sample/eos), not shape-derived.
        self._prefill = monitor.monitored_jit(prefill, name="lm_prefill",
                                              donate_argnums=(2,))

        def prefill_chunk_fn(params, ids, caches, pos, last_idx):
            # pos is TRACED: one compiled program serves every chunk of
            # every prompt (llama routes traced-offset prefill through
            # ops.pallas.prefix_chunk_attention)
            logits, caches = self._fwd(params, ids, caches, pos)
            return logits[:, last_idx], caches

        self._prefill_chunk = monitor.monitored_jit(
            prefill_chunk_fn, name="lm_prefill_chunk", donate_argnums=(2,))
        self._decode_cache = {}

    # -- pure functions -------------------------------------------------------
    def _fwd(self, params, ids, caches, pos):
        from ..core.autograd import no_grad

        with substituted_state(self.model, params), no_grad():
            logits, caches = self.model.forward_with_cache(
                Tensor(ids), caches, pos)
        return (logits.value if isinstance(logits, Tensor) else logits,
                caches)

    def _run_prefill(self, ids: np.ndarray, caches):
        """Bounded-compile prefill dispatch: chunked for prompts longer
        than ``prefill_chunk`` (fixed-shape chunks at traced offsets —
        ONE compiled program reused for every chunk), else padded up to
        the covering bucket. Returns (last-position logits [B, V],
        caches)."""
        plen = ids.shape[1]
        C = self.prefill_chunk
        if C is not None and plen > C:
            pos = 0
            while pos < plen:
                chunk = ids[:, pos:pos + C]
                r = chunk.shape[1]
                if r < C:       # only the FINAL chunk may be partial
                    chunk = _pad_ids(chunk, C)
                last_logits, caches = self._prefill_chunk(
                    self.params, chunk, caches, jnp.int32(pos),
                    jnp.int32(r - 1))
                pos += C
            return last_logits, caches
        width = (plen if self.prefill_buckets is None
                 else _bucket_for(self.prefill_buckets, plen))
        return self._prefill(self.params, _pad_ids(ids, width), caches,
                             jnp.int32(plen - 1))

    def _decode_fn(self, n_steps: int, cfg: GenerationConfig):
        key_cfg = (n_steps, cfg.do_sample, cfg.temperature, cfg.top_k,
                   cfg.top_p, cfg.eos_token_id)
        if key_cfg not in self._decode_cache:
            def decode_n(params, first_tok, caches, pos0, key):
                # a row whose FIRST sampled token is already EOS must stay
                # frozen through the scan
                if cfg.eos_token_id is not None:
                    done_init = first_tok == cfg.eos_token_id
                else:
                    done_init = jnp.zeros(first_tok.shape, bool)

                def step(carry, _):
                    tok, caches, pos, key, done = carry
                    logits, caches = self._fwd(params, tok[:, None],
                                               caches, pos)
                    key, sub = jax.random.split(key)
                    nxt = _sample(logits[:, 0], sub, cfg)
                    if cfg.eos_token_id is not None:
                        nxt = jnp.where(done, cfg.eos_token_id, nxt)
                        done = done | (nxt == cfg.eos_token_id)
                    return (nxt, caches, pos + 1, key, done), nxt

                (_, caches, _, _, _), toks = jax.lax.scan(
                    step, (first_tok, caches, pos0, key, done_init), None,
                    length=n_steps)
                return jnp.swapaxes(toks, 0, 1), caches   # [B, n_steps]

            self._decode_cache[key_cfg] = monitor.monitored_jit(
                decode_n, name="lm_decode", donate_argnums=(2,))
        return self._decode_cache[key_cfg]

    # -- public ---------------------------------------------------------------
    def generate(self, input_ids, config: Optional[GenerationConfig] = None):
        """input_ids: [B, prompt_len] (np/jnp/Tensor). Returns np.ndarray
        [B, prompt_len + max_new_tokens] (prompt + generated)."""
        cfg = config or GenerationConfig()
        ids = np.asarray(input_ids.value if isinstance(input_ids, Tensor)
                         else input_ids).astype(np.int32)
        b, plen = ids.shape
        if b > self.max_batch:
            raise ValueError(
                f"batch {b} exceeds max_batch={self.max_batch} the engine "
                f"was built for")
        if plen + cfg.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt({plen}) + max_new_tokens({cfg.max_new_tokens}) "
                f"exceeds engine max_len({self.max_len})")
        caches = self.model.init_cache(b, self.max_len)
        last_logits, caches = self._run_prefill(ids, caches)
        key = jax.random.PRNGKey(cfg.seed)
        key, sub = jax.random.split(key)
        first = _sample(last_logits, sub, cfg)
        n_rest = cfg.max_new_tokens - 1
        if n_rest > 0:
            rest, caches = self._decode_fn(n_rest, cfg)(
                self.params, first, caches, jnp.int32(plen), key)
            gen = np.concatenate([np.asarray(first)[:, None],
                                  np.asarray(rest)], axis=1)
        else:
            gen = np.asarray(first)[:, None]
        return np.concatenate([ids, gen], axis=1)

    # -- speculative decoding -------------------------------------------------
    def _spec_verify_fn(self, width: int):
        """One jitted verification forward of ``width`` tokens at a
        traced offset (compiled once per width)."""
        key = ("spec", width)
        if key not in self._decode_cache:
            def verify(params, inp, caches, pos):
                return self._fwd(params, inp, caches, pos)

            self._decode_cache[key] = monitor.monitored_jit(
                verify, name="lm_spec_verify", donate_argnums=(2,))
        return self._decode_cache[key]

    def generate_speculative(self, input_ids,
                             config: Optional[GenerationConfig] = None,
                             draft_k: int = 8, ngram_max: int = 3):
        """LOSSLESS n-gram (prompt-lookup) speculative decoding: propose
        ``draft_k`` tokens by continuing the longest recent-suffix match
        found earlier in the context, verify ALL of them in ONE model
        forward, and accept the matched prefix plus the model's own next
        token — so each forward yields between 1 and draft_k+1 tokens.

        Losslessness: every emitted token is the model's own argmax —
        acceptance targets and the bonus token come FROM the
        verification forward, so the output is the model's greedy
        continuation by construction. Bitwise it equals ``generate()``
        wherever the chunked-verify and one-token decode attention paths
        reduce identically (exactly true in f32 / the test suite; on a
        bf16 TPU cache the two kernels' reduction orders can low-bit
        flip a near-tied argmax — same class of divergence as any
        speculative-vs-sequential system).

        Greedy-only and B=1 (the latency-serving case). The reference
        has no speculative path; on TPU, decode is HBM-bandwidth-bound,
        so verifying k+1 positions costs barely more than one — the win
        is model forwards per token (reported in
        ``self.last_spec_stats``). Rejected drafts leave stale cache
        entries past the accepted length; the next verification
        overwrites them, and the cached-attention mask (absolute
        ``kv_pos <= sq_pos``) never reads beyond the query's position.
        """
        cfg = config or GenerationConfig()
        if cfg.do_sample:
            raise ValueError(
                "speculative decoding here is greedy-only (lossless "
                "acceptance needs the argmax target); use generate() "
                "for sampling")
        ids = np.asarray(input_ids.value if isinstance(input_ids, Tensor)
                         else input_ids).astype(np.int32)
        if ids.ndim == 1:
            ids = ids[None]
        b, plen = ids.shape
        if b != 1:
            # NOT _prompt_ids: its reshape(1, -1) would silently flatten
            # a batch into one long prompt
            raise ValueError("speculative decoding serves B=1 requests")
        if plen + cfg.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt({plen}) + max_new_tokens({cfg.max_new_tokens}) "
                f"exceeds engine max_len({self.max_len})")
        caches = self.model.init_cache(1, self.max_len)
        last_logits, caches = self._run_prefill(ids, caches)
        out = [int(np.argmax(np.asarray(last_logits[0])))]
        # per-sequence proposer state (inference/ngram.py): context =
        # prompt + every emitted token, extended incrementally — the
        # SAME unit the batched serving engines keep per slot
        prop = NgramProposer([int(t) for t in ids[0]] + [out[0]],
                             draft_k, ngram_max)
        pos = plen                      # tokens the CACHE holds
        forwards = 1                    # the prefill
        extra = 0                       # emitted tokens beyond 1/forward
        eos = cfg.eos_token_id
        verify = self._spec_verify_fn(draft_k + 1)
        while (len(out) < cfg.max_new_tokens
               and (eos is None or out[-1] != eos)
               and pos + 1 + draft_k <= self.max_len):
            draft = prop.propose()
            inp = np.asarray([[out[-1]] + draft], np.int32)
            logits, caches = verify(self.params, inp, caches,
                                    jnp.int32(pos))
            forwards += 1
            greedy = np.asarray(jnp.argmax(logits[0], axis=-1))
            m = 0
            while m < draft_k and int(greedy[m]) == draft[m]:
                m += 1
            accepted = draft[:m] + [int(greedy[m])]
            before = len(out)
            for t in accepted:
                out.append(t)
                prop.extend([t])
                if (len(out) >= cfg.max_new_tokens
                        or (eos is not None and t == eos)):
                    break
            extra += len(out) - before - 1
            # cache gained [out_prev_last, accepted drafts]; the final
            # accepted token is the model's own pick, not yet cached
            pos += 1 + m
        # tail: plain 1-wide steps when max_len headroom < draft_k+1
        one = self._spec_verify_fn(1)
        while (len(out) < cfg.max_new_tokens
               and (eos is None or out[-1] != eos)
               and pos + 1 <= self.max_len - 1):
            logits, caches = one(self.params,
                                 np.asarray([[out[-1]]], np.int32),
                                 caches, jnp.int32(pos))
            forwards += 1
            out.append(int(np.argmax(np.asarray(logits[0, 0]))))
            prop.extend([out[-1]])
            pos += 1
        # generate() always emits the prefill token, even at budget 0
        budget = max(cfg.max_new_tokens, 1)
        if eos is not None and eos in out:
            # generate() freezes finished rows on eos — match exactly
            i = out.index(eos)
            out = out[:i + 1] + [eos] * (budget - i - 1)
        out = out[:budget]
        self.last_spec_stats = {"forwards": forwards,
                                "tokens": len(out),
                                # emitted draft/bonus tokens beyond the
                                # one-per-forward floor: with eos=None
                                # tokens == forwards + accepted exactly,
                                # so speedup bars can be DERIVED from
                                # the measured acceptance instead of
                                # hard-coding an environment-dependent
                                # tokens/forward threshold
                                "accepted_draft_tokens": extra,
                                "tokens_per_forward":
                                    len(out) / max(forwards, 1)}
        return np.concatenate([ids, np.asarray([out], np.int32)], axis=1)


def _lora_rows(bank, aidx, ids):
    """The model forwards' ``lora`` input for a prefill of ``ids`` under
    ONE adapter: (bank, per-row index), or None for an empty bank (the
    exact pre-LoRA trace)."""
    if not bank:
        return None
    return bank, jnp.full((ids.shape[0],), aidx, jnp.int32)


class _ChunkedAdmission:
    """Host-side state of one in-flight CHUNKED admission. The slot (and
    the request's claim of pages) is already taken; ``mini``
    accumulates the prompt's KV chunk by chunk until the final chunk
    installs it and the request goes live under ``rid``. Drive with
    ``engine.admit_chunk``; reclaim with ``engine.abort_admit``."""

    __slots__ = ("rid", "slot", "ids", "plen", "cfg", "mini", "off",
                 "t0", "closed", "chunks_done", "last_logits")

    def __init__(self, rid, slot, ids, plen, cfg, mini, off=0):
        self.rid = rid
        self.slot = slot
        self.ids = ids
        self.plen = plen
        self.cfg = cfg
        self.mini = mini
        # chunk cursor; a prefix-cache hit starts it past the cached
        # coverage (aligned down to a chunk boundary) so cached chunks
        # never recompute
        self.off = off
        self.t0 = time.perf_counter()
        self.closed = False
        self.chunks_done = 0
        self.last_logits = None


class PagedContinuousBatchingEngine:
    """Continuous batching over a PAGED KV pool: the engine that serves.

    :class:`CausalLMEngine` runs one common-length batch per
    ``generate()`` over dense slabs and is the independent reference
    served tokens are held to. This engine serves MIXED-length traffic
    (the reference's fused_multi_transformer_op.cu.h:1641 remove_padding
    and :1680 length-indexed masked MHA; the page layout is vLLM's, Kwon
    et al. SOSP'23):

    - ``max_batch`` SLOTS, each a page-table row into shared per-layer
      pools: HBM holds ``num_pages * page_size`` tokens (the tokens in
      flight), any free page serves any slot, and a row's decode
      attention walks the pages ITS length spans. A slot holds at most
      ``max_len = max_pages * page_size`` positions;
    - requests are ADMITTED into free slots and finished rows retire
      between jitted decode segments, so new work never waits for the
      longest running request. A cold admission is ONE program per
      prompt bucket (``jit_prefill_one``: a bucket-wide mini cache, the
      prefill, the scatter of every layer's rows into the claimed
      pages); ``prefill_buckets`` bounds the compiles and
      :meth:`warmup` runs them all ahead of traffic; with
      ``prefill_chunk`` a long prompt admits chunk by chunk across gaps
      (:meth:`begin_admit` / :meth:`admit_chunk`);
    - ONE segment program (``jit_segment``) serves every occupancy
      pattern and every mix of GenerationConfigs and LoRA adapters:
      slot ids, lengths, sampling parameters and adapter indices are
      traced per-slot vectors, never shapes or trace constants.

    The model contract: ``forward`` (training, plain inference),
    ``forward_with_cache`` (prefill here; ``CausalLMEngine``'s decode
    too), ``init_paged_cache`` + ``forward_decode_paged`` (a decode step
    through the page table) and, where it speculates,
    ``forward_decode_spec_paged`` (the W-position verify step). A model
    whose pages hold anything but per-head K and V in one table also
    gives ``paged_layout``, a dict the constructor reads in ONE place:
    ``ring`` (window layers keep a ring of pages: the engine then keeps
    two page tables, ``paged_cache.WindowedPageAllocator``), ``last_idx``
    (prefill is told the prompt's last position), ``counters`` (a decode
    step returns counters, which ``jit_segment`` sums and hands back) and
    ``rows`` (what its pages hold: latent rows, a ring), by which the
    engine refuses, by name, the features whose programs do not read
    such pages, and ``state_layers`` (the layers that keep a fixed-size
    state a ROW and no pages, a recurrent state: their entries of the
    pools are ``[max_batch, ...]`` arrays indexed by slot, which an
    admission overwrites and a dead row never reads, so retirement,
    preemption and replay copy nothing).

    ``admission_mode``: ``"reserved"`` (default) claims a request's
    worst case (prompt + max_new_tokens) at admission, so a running
    request can never exhaust the pool; ``"optimistic"`` claims the
    prompt plus one page and grows each live slot per gap
    (:meth:`grow_for_segment`). When growth cannot be met the CALLER
    relieves pressure with :meth:`preempt_request` (greedy
    preempt-resume is bitwise an unpreempted run) or
    ``decode_segment`` raises :class:`PagePoolExhausted`, never a
    silent dropped write; ``kv_watermark`` pauses new admissions while
    the pool is under pressure.

    ``prefix_cache=True``: admission hashes the prompt in page_size
    blocks, maps resident blocks READ-ONLY into the slot's table (only
    the uncached tail is computed, at a traced offset), and the first
    write into a shared page is copied on write in the gap; released
    cached pages park in an LRU the pool reclaims on demand.
    ``kv_dtype="int8"``: int8 pages with per-(page, kv_head)
    running-absmax scales (``quantization.kv``). ``draft_k > 0``:
    n-gram speculative decoding per slot (``spec_mode`` ``"host"`` or a
    fused ``"device"`` segment). ``lora_capacity > 0``: a hot-loadable
    adapter bank. ``tp_degree > 1``: weights and pools sharded on the
    head axis of a 1-D mesh (``inference/tp.py``). ``debug_pages=True``
    runs the allocator's invariant checks at every gap.

    Usage::

        eng = PagedContinuousBatchingEngine(
            model, max_batch=4, num_pages=256, page_size=16, max_pages=32)
        outs = eng.serve([ids1, ids2, ...], GenerationConfig(...))
    """

    def __init__(self, model, max_batch: int, num_pages: int,
                 page_size: int, max_pages: int,
                 prefill_buckets="auto",
                 prefill_chunk: Optional[int] = None,
                 admission_mode: str = "reserved",
                 kv_watermark: float = 0.9,
                 debug_pages: bool = False,
                 prefix_cache: bool = False,
                 kv_dtype: str = "bf16",
                 draft_k: int = 0, ngram_max: int = 3,
                 spec_mode: str = "host", spec_draft: str = "ngram",
                 spec_history: int = 128,
                 lora_capacity: int = 0, lora_rank: int = 8,
                 lora_targets=("q", "k", "v", "o"),
                 tp_degree: int = 1, tp_devices=None):
        from ..quantization.kv import KV_DTYPES
        from .paged_cache import PageAllocator
        from .tp import (TP_AXIS, make_tp_mesh, shard_params_tp,
                         validate_tp_model)

        if admission_mode not in ADMISSION_MODES:
            raise ValueError(
                f"admission_mode must be one of {ADMISSION_MODES}, got "
                f"{admission_mode!r}")
        if not (isinstance(kv_watermark, (int, float))
                and 0 < kv_watermark <= 1):
            raise ValueError(
                f"kv_watermark must satisfy 0 < w <= 1 (fraction of "
                f"the page pool), got {kv_watermark!r}")
        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, got "
                f"{kv_dtype!r}")
        self.admission_mode = admission_mode
        self.kv_watermark = float(kv_watermark)
        self.prefix_cache = bool(prefix_cache)
        # overload-control actuator (serving.control brownout rung 4):
        # while True, NEW admissions skip prefix-cache lookup/insert
        # and take the plain cold path (already warmed — pausing
        # compiles nothing, and no CoW/shared pages are minted under
        # pressure). Resident cached blocks stay mapped; in-flight
        # warm admissions finish normally. Host bool, flipped by the
        # serving scheduler thread between segments.
        self.prefix_pause = False
        # KV page storage: "bf16" = the model's cache dtype, bitwise
        # the pre-quantization path; "int8" stores pages int8 with
        # per-(page, kv_head) running-absmax scales riding the page
        # table — half the bytes per decode read, ~2x the pages at
        # fixed HBM, correctness bar bounded-not-bitwise (see
        # quantization.kv). Must be set before _init_decode_state
        # builds the pools.
        self.kv_dtype = kv_dtype
        # slot -> warm-admission info ({"ids","c_map","hashes","saved"})
        # staged from the admission's lookup until its rows are in the
        # pages; popped by _index_prompt / _abort_admit
        self._prefix_stash = {}
        # segment count a clean grow_for_segment covered; decode_segment
        # consumes it to skip its (device-syncing) exhaustion re-check
        self._growth_stamp: Optional[int] = None
        # (lens, done) host copies shared by every grow_for_segment call
        # in ONE gap — relief that preempts k victims re-runs the grow
        # loop k+1 times, but lens/done only change when a segment runs
        # (decode) or a slot admits (_register), both of which clear it
        self._gap_sync = None
        self.num_pages = num_pages
        self.page_size = page_size
        max_len = max_pages * page_size
        # THE place the engine reads what a model says of its cache
        # (``paged_layout``; a model without it keeps per-head K and V in
        # one table and gives no answer): ``ring`` — its geometries: None
        # = one table serves every layer, else the window layers' ring
        # (``window``, ``ring_pages``, ``window_layers``); ``last_idx`` —
        # its prefill takes the prompt's last position (that position's
        # logits alone, no padding routed); ``prefill_row_block`` — that
        # prefill runs its row-wise work in blocks of so many rows, up to
        # the prompt's last (``models/_live_rows``; the span's
        # ``rows_run``); ``counters`` — a decode step
        # returns counters beside its pools; ``rows`` — what its pages
        # hold where that is not per-head K and V in one table, which the
        # features below neither read nor write; ``state_layers`` — one
        # bool a layer, True where the layer keeps a state a row (its
        # entry of the pools is [max_batch, ...], indexed by slot) and no
        # pages: the page budget counts the other layers alone
        layout = getattr(model, "paged_layout", None)
        layout = layout(page_size) if layout is not None else {}
        self._ring = layout.get("ring")
        self._prefill_last_idx = bool(layout.get("last_idx"))
        self._prefill_row_block = layout.get("prefill_row_block")
        self._step_counters = bool(layout.get("counters"))
        self._state_layers = layout.get("state_layers")
        if layout.get("rows"):
            refused = {"tp_degree": tp_degree != 1,
                       "kv_dtype='int8'": kv_dtype != "bf16",
                       "draft_k (speculative decoding)": draft_k != 0,
                       "prefix_cache": prefix_cache,
                       "prefill_chunk": prefill_chunk is not None,
                       "lora_capacity (LoRA)": lora_capacity != 0}
            for feature, asked in refused.items():
                if asked:
                    raise ValueError(
                        f"{feature} is not implemented for "
                        f"{type(model).__name__}: its pages hold "
                        f"{layout['rows']}, which this feature's "
                        f"programs do not read or write")
        if self._ring is not None:
            from .paged_cache import WindowedPageAllocator

            self.alloc = WindowedPageAllocator(
                num_pages, page_size, max_batch, max_pages,
                self._ring["ring_pages"], debug=debug_pages)
        else:
            self.alloc = PageAllocator(num_pages, page_size, max_batch,
                                       max_pages, debug=debug_pages,
                                       prefix_cache=prefix_cache,
                                       kv_dtype=kv_dtype)
        # what the cache's kind adds to the two calls that build the pools
        # (``model.init_paged_cache``) and fill them at admission
        # (``paged_cache.write_prompt``): nothing, the window layers'
        # ring, or the layers that keep a state a row
        self._pool_kwargs, self._write_kwargs = {}, {}
        if self._ring is not None:
            self._pool_kwargs = {"window_pages": self.alloc.window.num_pages}
            self._write_kwargs = {
                "window_layers": self._ring["window_layers"]}
        elif self._state_layers is not None:
            self._pool_kwargs = {"state_rows": max_batch}
            self._write_kwargs = {"state_layers": self._state_layers}
        if (isinstance(draft_k, bool)
                or not isinstance(draft_k, (int, np.integer))
                or not 0 <= draft_k <= 256):
            raise ValueError(
                f"draft_k must be an int in [0, 256] (0 disables "
                f"speculative decoding), got {draft_k!r}")
        if spec_mode not in SPEC_MODES:
            raise ValueError(
                f"spec_mode must be one of {SPEC_MODES}, got "
                f"{spec_mode!r}")
        if spec_draft not in SPEC_DRAFTS:
            raise ValueError(
                f"spec_draft must be one of {SPEC_DRAFTS}, got "
                f"{spec_draft!r}")
        if (isinstance(spec_history, bool)
                or not isinstance(spec_history, (int, np.integer))
                or not 8 <= spec_history <= 65536):
            raise ValueError(
                f"spec_history must be an int in [8, 65536] (the "
                f"device history-ring width), got {spec_history!r}")
        if (isinstance(lora_capacity, bool)
                or not isinstance(lora_capacity, (int, np.integer))
                or lora_capacity < 0):
            raise ValueError(
                f"lora_capacity must be an int >= 0 (0 disables "
                f"multi-tenant LoRA), got {lora_capacity!r}")
        # tensor parallelism (inference/tp.py): tp_degree > 1 builds a
        # 1-D "mp" mesh and shards weights (per their layer pspecs) and
        # every KV store on the (kv_)head axis; per-slot vectors, page
        # tables, and all host bookkeeping REPLICATE, so the engine's
        # programs keep their one-program-per-shape invariant at any
        # degree. tp_devices pins the mesh to a device subset (the
        # ReplicaSpec fleet-partitioning seam). Must be resolved before
        # _init_decode_state builds the device pools.
        # mesh first (validates the degree and device availability),
        # then the model-geometry divisibility check
        self.tp_mesh = make_tp_mesh(tp_degree, tp_devices)
        validate_tp_model(model, tp_degree)
        self.tp_degree = int(tp_degree)
        # the (mesh, axis) handle the model forwards thread into the
        # attention ops' shard_map wrap (None = pre-TP trace, bitwise
        # the single-device engine)
        self._tp = (None if self.tp_mesh is None
                    else (self.tp_mesh, TP_AXIS))
        self.model = model
        self.max_batch = max_batch
        self.max_len = max_len
        self.prefill_buckets = prefill_buckets_for(prefill_buckets,
                                                   max_len)
        self.prefill_chunk = _normalize_prefill_chunk(prefill_chunk,
                                                      max_len)
        # speculative decoding (per-slot capability): draft_k > 0
        # widens the decode path with ONE extra compiled program (the
        # (draft_k+1)-token verify step) that spec-opted slots ride;
        # plain/sampled slots share it at 1 token/step. 0 = the spec
        # path never compiles and decode_segment is exactly the plain
        # scan.
        self.draft_k = int(draft_k)
        self.ngram_max = int(ngram_max)
        # speculative execution mode + device-draft source (idle-only
        # attributes, like draft_k — the serving Server mirrors them):
        # "device" replaces the host per-verify-step loop with ONE
        # fused compiled segment whose draft source is the per-slot
        # history ring below
        self.spec_mode = spec_mode
        self.spec_draft = spec_draft
        self.spec_history = int(spec_history)
        self._spec = {}                # rid -> NgramProposer (spec rows)
        # engine-lifetime host accounting (serve_bench / spec_stats):
        # proposed/accepted draft tokens, verify forwards, per-slot
        # participations (slot_steps), tokens emitted (spec segments
        # only), blocking per-verify-step host readbacks (host mode's
        # documented price; structurally 0 in device mode)
        self._spec_totals = {"proposed": 0, "accepted": 0,
                             "forwards": 0, "slot_steps": 0,
                             "emitted": 0, "host_syncs": 0}
        # engine label: concurrent engines (multi-model serving) publish
        # throughput side by side; retired via close()/__del__
        self._monitor_engine = monitor.instance_label("engine")
        self.params = {k: p.value for k, p in model.named_parameters()}
        if self.tp_mesh is not None:
            # column-parallel q/k/v/gate/up, row-parallel o/down,
            # vocab-parallel embed/lm_head — straight from the layer
            # pspec annotations the training stack already carries
            self.params = shard_params_tp(model, self.params,
                                          self.tp_mesh)
        # multi-tenant LoRA (lora_capacity > 0): an AdapterRegistry owns
        # the stacked per-target factor bank ([L, K+1, r, d] per
        # projection, index 0 = base model) plus hot load/unload; every
        # serving program takes the bank as a jit ARGUMENT and gathers
        # each slot's delta by its per-slot adapter_idx device vector —
        # one compiled program serves any adapter mix, loads rewrite
        # bank rows only (zero per-adapter compiles). 0 disables: the
        # programs take an empty-dict bank and trace the exact
        # pre-LoRA computation.
        self.lora_capacity = int(lora_capacity)
        self.adapters = None
        if self.lora_capacity:
            shapes_fn = getattr(model, "lora_shapes", None)
            if shapes_fn is None:
                raise ValueError(
                    f"lora_capacity needs a model exposing "
                    f"lora_shapes(targets) (llama does); "
                    f"{type(model).__name__} does not")
            num_layers, shapes = shapes_fn(tuple(lora_targets))
            # lazy import: paddle_tpu.serving imports this module
            from ..serving.adapters import AdapterRegistry

            dtype = next(iter(self.params.values())).dtype
            self.adapters = AdapterRegistry(
                self.lora_capacity, lora_rank, tuple(lora_targets),
                num_layers, shapes, dtype, self._monitor_engine)
        # adapter-index bookkeeping around admissions: slot -> index
        # while an admission is in flight (popped by _register /
        # _abort_admit), rid -> index while the request lives (released
        # by _retire). guarded-by: scheduler-thread
        self._aidx_stash = {}
        self._rid_aidx = {}
        self._init_decode_state()
        self._slot_req = {}            # slot -> request id
        self._tokens = {}              # request id -> [generated ids]
        self._budget = {}              # request id -> remaining tokens
        self._plen = {}                # request id -> prompt length
        self._cfg = {}                 # request id -> GenerationConfig
        self._finished = {}            # request id -> np.ndarray
        self._next_req = 0
        self._segments_run = 0         # PRNG stream position for sampling

        def prefill_one(params, ids, pools, page_table, slot, plen, bank,
                        aidx):
            # a cold one-shot admission is this ONE program per bucket:
            # the bucket-wide mini cache is made in here (zeros XLA need
            # not materialise), the prefill runs on it, and every
            # layer's rows go into the DONATED pools by write_tokens'
            # own arithmetic (unmapped pages drop; int8 drops past
            # plen). slot / plen / aidx are traced: programs are keyed
            # per bucket width, not per prompt length, and one program
            # serves every adapter (an empty bank is trace-static and
            # falls back to the exact pre-LoRA prefill)
            from .paged_cache import write_prompt

            mini = self._tp_kv(self.model.init_cache(1, ids.shape[1]))
            if self._prefill_last_idx:
                # the model is told the last position: it computes that
                # position's logits alone and routes no padding
                logits, mini = self._fwd_prefill(params, ids, mini,
                                                 last_idx=plen - 1)
                last = logits[:, 0]
            else:
                logits, mini = self._fwd_prefill(
                    params, ids, mini, lora=_lora_rows(bank, aidx, ids))
                last = logits[:, plen - 1]
            # a window layer's rows go into its ring, a state layer's
            # final state into the slot's row
            return last, write_prompt(pools, page_table, slot, plen, mini,
                                      **self._write_kwargs)

        # monitor "cb_prefill", XLA module jit_prefill_one: the miss
        # counters and the benchmark's readers find a prompt's prefill
        # by them
        self._prefill_paged = monitor.monitored_jit(
            prefill_one, name="cb_prefill",
            owner=self._monitor_engine, donate_argnums=(2,))

        def prefill_chunk_fn(params, ids, mini, pos, last_idx, bank,
                             aidx):
            # traced offset -> ops.pallas.prefix_chunk_attention: ONE
            # compiled program serves every chunk of every admission
            logits, mini = self._fwd_prefill(
                params, ids, mini, pos, lora=_lora_rows(bank, aidx, ids))
            return logits[:, last_idx], mini

        self._prefill_chunk = monitor.monitored_jit(
            prefill_chunk_fn, name="cb_prefill_chunk",
            owner=self._monitor_engine, donate_argnums=(2,))

        def mini_cache(width):
            # a B=1 dense mini cache that OUTLIVES a program (a warm
            # prefix hit's, a chunked admission's), where chunks write
            # the prompt's KV before it installs into the pages:
            # every layer's zeros in ONE program per width (eager, a
            # layer's two jnp.zeros were 2L dispatches an admission),
            # sharded on the head axis like the pool they feed, so the
            # gather/scatter install programs move head-local rows.
            # Plain jit: a program with no FLOPs is not the ledger's
            return self._tp_kv(self.model.init_cache(1, width))

        self._mini_cache = jax.jit(mini_cache, static_argnums=(0,))

        H = self.spec_history

        def admit_state(lens, last, done, samp, hist, hl, slot, plen,
                        logits, key_seed, temp, top_k, top_p, do_samp,
                        eos, seed, spec_k, adapter, hrow, hlen):
            # the tail of every admission in ONE program: the first
            # token sampled from the prompt's last-position logits
            # (greedy: the argmax; sampled: from PRNGKey(key_seed)),
            # its eos verdict, the per-slot scalars AND the request's
            # sampling parameters. Admission sits in the
            # latency-critical gap between decode segments: sampled
            # eagerly the token was three to eight dispatches, and each
            # .at[].set apart a round-trip, where this costs one
            first = _sample_one(logits, key_seed, temp, top_k, top_p,
                                do_samp)
            tok_done = (eos >= 0) & (first == eos)
            samp = {
                "temp": samp["temp"].at[slot].set(temp),
                "top_k": samp["top_k"].at[slot].set(top_k),
                "top_p": samp["top_p"].at[slot].set(top_p),
                "sample": samp["sample"].at[slot].set(do_samp),
                "eos": samp["eos"].at[slot].set(eos),
                "seed": samp["seed"].at[slot].set(seed),
                "spec_k": samp["spec_k"].at[slot].set(spec_k),
                "adapter": samp["adapter"].at[slot].set(adapter),
            }
            # history-ring seed: hrow is the prompt's last H-1 tokens
            # (host-padded to the fixed [H] shape — never a recompile);
            # the first token lands in its slot here
            hrow = jnp.where(
                hlen > 0,
                hrow.at[jnp.clip(hlen - 1, 0, H - 1)].set(first),
                hrow)
            # (first, tok_done) packed: the host reads both in ONE pull
            return (jnp.stack([first, tok_done.astype(jnp.int32)]),
                    lens.at[slot].set(plen),
                    last.at[slot].set(first),
                    done.at[slot].set(tok_done), samp,
                    hist.at[slot].set(hrow), hl.at[slot].set(hlen))

        self._admit_state = monitor.monitored_jit(
            admit_state, name="cb_admit_state",
            owner=self._monitor_engine,
            donate_argnums=(0, 1, 2, 3, 4, 5))
        self._segment_cache = {}
        self._measure_quant_savings()

        def reset_scales(pools, mask):
            # ONE fixed-shape program per pool shape: freshly claimed
            # pages' scale rows (a previous owner's absmax leftovers)
            # drop to the floor before any write — per-page dispatches
            # or a count-shaped index vector would recompile per gap
            from ..quantization.kv import KV_SCALE_FLOOR

            out = []
            for kp, vp, ks, vs in pools:
                ks = jnp.where(mask[:, None], KV_SCALE_FLOOR, ks)
                vs = jnp.where(mask[:, None], KV_SCALE_FLOOR, vs)
                out.append((kp, vp, ks, vs))
            return out

        self._reset_scales = monitor.monitored_jit(
            reset_scales, name="cb_reset_scales",
            owner=self._monitor_engine, donate_argnums=(0,))

    def _init_decode_state(self) -> None:
        """Fresh device-side decode state: caches, per-slot scalars,
        the per-slot SAMPLING vectors (see ``_sample_rows`` — each
        request's GenerationConfig is installed into its slot at
        admission, so one segment program serves mixed configs; eos -1
        means none), and the free-slot heap. ONE definition shared by
        ``__init__`` and ``reset_state`` — a supervised restart must
        rebuild exactly what construction builds, so a new per-slot
        vector added here can never be forgotten on the recovery
        path."""
        mb = self.max_batch
        self.caches = self._make_caches()
        self.lens = jnp.zeros((mb,), jnp.int32)
        self.last = jnp.zeros((mb,), jnp.int32)
        self.done_dev = jnp.zeros((mb,), bool)
        self.samp = {
            "temp": jnp.ones((mb,), jnp.float32),
            "top_k": jnp.zeros((mb,), jnp.int32),
            "top_p": jnp.ones((mb,), jnp.float32),
            "sample": jnp.zeros((mb,), bool),
            "eos": jnp.full((mb,), -1, jnp.int32),
            "seed": jnp.zeros((mb,), jnp.int32),
            # per-slot draft window (0 = plain decode): the widened
            # verify step caps each row's acceptance at ITS spec_k, so
            # one compiled program serves any spec/plain/sampled mix
            "spec_k": jnp.zeros((mb,), jnp.int32),
            # per-slot LoRA adapter index (0 = base model — bank row 0
            # is zeros, so the gathered delta is exactly 0.0): the
            # weights half of the per-slot-vector invariant. Rides the
            # samp dict so every program that takes the sampling
            # vectors sees it without a signature fork; consumed only
            # when a non-empty bank is passed alongside.
            "adapter": jnp.zeros((mb,), jnp.int32),
        }
        # per-slot token-history ring (device-mode speculative draft
        # source): each row holds the LAST spec_history tokens of
        # prompt + everything emitted, left-aligned, hist_len valid.
        # Installed at admission (_admit_state seeds prompt tail +
        # first token — a replayed request re-admits prompt+generated,
        # so the ring rebuilds exactly like the host proposer's
        # context), appended inside the fused segment. Allocated
        # unconditionally (mb x H int32 is trivial) so flipping
        # draft_k/spec_mode on an idle engine never needs a state
        # rebuild.
        self.hist = jnp.zeros((mb, self.spec_history), jnp.int32)
        self.hist_len = jnp.zeros((mb,), jnp.int32)
        if self.tp_mesh is not None:
            # the per-slot vectors REPLICATE on the mesh (the PR 2
            # invariant is TP-invariant): committing them here keeps
            # every program's input shardings identical from warmup
            # through serving — no sharding-keyed recompiles
            self.lens = self._tp_rep(self.lens)
            self.last = self._tp_rep(self.last)
            self.done_dev = self._tp_rep(self.done_dev)
            self.samp = {k: self._tp_rep(v)
                         for k, v in self.samp.items()}
            self.hist = self._tp_rep(self.hist)
            self.hist_len = self._tp_rep(self.hist_len)
        self._free = list(range(mb))

    def _active_mask(self) -> np.ndarray:
        """The live mask the segment programs take, [max_batch] bool:
        the slots that hold a request, from host bookkeeping alone. It
        rides into each program as a numpy argument, so admission and
        retirement keep no copy of it on the device."""
        active = np.zeros((self.max_batch,), bool)
        active[list(self._slot_req)] = True
        return active

    # -- tensor-parallel placement helpers -----------------------------------
    def _tp_rep(self, x):
        """Commit a device value fully replicated on the TP mesh
        (identity when tp_degree == 1)."""
        if self.tp_mesh is None:
            return x
        from .tp import tp_replicate

        return tp_replicate(x, self.tp_mesh)

    def _tp_kv(self, caches):
        """Shard a per-layer KV list (slabs / pools / minis) on the
        kv-head axis (identity when tp_degree == 1)."""
        if self.tp_mesh is None:
            return caches
        from .tp import tp_shard_kv

        return tp_shard_kv(caches, self.tp_mesh)

    def _make_caches(self):
        """``(pools, page tables)``: the per-layer page pools and the
        host page table(s) as device arrays. TP: pools (and int8 scales)
        shard on the kv-head axis; the page TABLE replicates — page
        indices are mesh-invariant, so the allocator/prefix-cache host
        logic needs no fork."""
        if self.kv_dtype == "int8":
            try:
                pools = self.model.init_paged_cache(
                    self.num_pages, self.page_size, kv_dtype="int8")
            except TypeError as e:
                raise ValueError(
                    f"kv_dtype='int8' needs a model whose "
                    f"init_paged_cache accepts kv_dtype (llama does); "
                    f"{type(self.model).__name__} does not") from e
            return self._tp_kv(pools), self._device_tables()
        return (self._tp_kv(self.model.init_paged_cache(
                    self.num_pages, self.page_size, **self._pool_kwargs)),
                self._device_tables())

    def _device_tables(self):
        """The host page table(s) as the device programs take them: one
        array, or ``(full, ring)`` for a model with window layers. Every
        upload passes here, a segment's and an admission's alike, so
        here a traced run keeps what was sent (``_tables_sent``, for the
        ``changed`` of ``engine.tables``) and any other run drops it."""
        self._tables_sent = self._tables_bytes() if trace.enabled() else None
        if self._ring is not None:
            return tuple(jnp.asarray(t) for t in self.alloc.tables())
        return self._tp_rep(jnp.asarray(self.alloc.page_table))

    def _tables_bytes(self) -> list:
        """The host page table(s) as bytes, to compare with what the
        last upload sent: a copy and a ``!=`` under the interpreter
        lock, where numpy's comparison lets go of it and the threads the
        last collection woke would run inside the comparison."""
        tabs = (self.alloc.tables() if self._ring is not None
                else (self.alloc.page_table,))
        return [t.tobytes() for t in tabs]

    def _measure_quant_savings(self) -> None:
        """Price the int8 layout from the REAL pool arrays: HBM bytes
        per page a bf16 pool would need minus what the int8 pools +
        scales actually take — the allocator counts it per claimed
        page (``paddle_tpu_kv_quant_bytes_saved_total``)."""
        if self.kv_dtype != "int8":
            self.alloc.bytes_saved_per_page = 0
            return
        pools, _ = self.caches
        base = quant = 0
        for kp, vp, ks, vs in pools:
            base += (kp.size + vp.size) * 2          # bf16 baseline
            quant += (kp.nbytes + vp.nbytes + ks.nbytes + vs.nbytes)
        self.alloc.bytes_saved_per_page = max(
            (base - quant) // self.num_pages, 0)

    def kv_page_cost(self) -> dict:
        """HBM cost of one page under the current storage dtype:
        ``{"bytes_per_page"}`` is the actual cost (scales included);
        ``{"bf16_equiv_bytes_per_page"}`` prices the SAME page at
        2 bytes/element — the production-baseline denominator for
        serve_bench's effective-capacity record, independent of the
        CPU test model's f32 cache dtype."""
        pools, _ = self.caches
        total = elems = 0
        for entry, state in zip(pools, self._state_layers
                                or (False,) * len(pools)):
            if state:       # a state a row holds no page
                continue
            total += sum(a.nbytes for a in entry)
            elems += sum(a.size for a in entry)
        return {"bytes_per_page": total // self.num_pages,
                "bf16_equiv_bytes_per_page":
                    2 * elems // self.num_pages}

    def set_kv_dtype(self, kv_dtype: str) -> None:
        """Swap the pool storage dtype on an IDLE engine (the
        ``Server(kv_dtype=...)`` mirror hook): rebuilds the pools —
        any cached prefix KV dies with them, so the content index
        clears too — and keeps every compiled program (the other
        dtype's variants stay cached; warmup covers the new ones)."""
        from ..quantization.kv import KV_DTYPES

        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, got "
                f"{kv_dtype!r}")
        if kv_dtype == self.kv_dtype:
            return
        if self._slot_req:
            raise RuntimeError(
                "kv_dtype can only be changed on an idle engine")
        # old pools dropped before the new ones allocate (reset_state's
        # peak-HBM argument applies here too)
        self.caches = None
        self.alloc.clear_prefix_index()
        self.alloc.set_kv_dtype(kv_dtype)
        self.kv_dtype = kv_dtype
        self._prefix_stash.clear()
        self._growth_stamp = None
        self._gap_sync = None
        self.caches = self._make_caches()
        self._measure_quant_savings()

    def _flush_fresh_scales(self) -> None:
        """Reset freshly claimed pages' scale rows to the floor (int8;
        one masked fixed-shape program) — runs at the write choke
        points (cache install, pre-segment) so no quantized store ever
        runs absmax against a previous owner's scales."""
        if self.kv_dtype != "int8":
            return
        fresh = self.alloc.take_fresh_scales()
        if not fresh:
            return
        mask = np.zeros((self.num_pages,), bool)
        mask[fresh] = True
        pools, pt = self.caches
        self.caches = (self._reset_scales(pools, jnp.asarray(mask)),
                       pt)

    def _bank(self) -> dict:
        """The LoRA factor bank to pass into the jitted serving
        programs: the registry's live arrays (a load/unload swaps them
        — same shapes, new data, no recompile), or ``{}`` when LoRA is
        disabled (trace-static: the programs fall back to the exact
        pre-LoRA computation)."""
        return self.adapters.bank if self.adapters is not None else {}

    def _fwd_kwargs(self, lora) -> dict:
        """Optional kwargs for the model's serving forwards: ``lora``
        only when batched adapters ride along, ``tp`` only when the
        engine runs on a mesh — so a model without either kwarg keeps
        working and the pre-TP/pre-LoRA traces stay byte-identical."""
        kw = {}
        if lora is not None:
            kw["lora"] = lora
        if self._tp is not None:
            kw["tp"] = self._tp
        return kw

    def _fwd_prefill(self, params, ids, caches, pos=0, lora=None, **kw):
        from ..core.autograd import no_grad

        with substituted_state(self.model, params), no_grad():
            logits, caches = self.model.forward_with_cache(
                Tensor(ids), caches, pos, **self._fwd_kwargs(lora), **kw)
        return (logits.value if isinstance(logits, Tensor) else logits,
                caches)

    def _fwd_ragged(self, params, tok, caches, lens, live, lora=None):
        """One decode step through the page table: ``(logits, caches,
        aux)``. ``aux`` is a dict of int32 counters the model's step
        hands out (a model that routes experts returns its routing
        counts) or None; the segment program sums them over its steps
        and returns them beside its tokens."""
        from ..core.autograd import no_grad

        pools, pt = caches
        with substituted_state(self.model, params), no_grad():
            out = self.model.forward_decode_paged(
                Tensor(tok), pools, pt, lens, live,
                **self._fwd_kwargs(lora))
        logits, pools, aux = out if self._step_counters else (*out, None)
        return (logits.value if isinstance(logits, Tensor) else logits,
                (pools, pt), aux)

    def _reserved(self, plen: int, cfg) -> int:
        return min(plen + cfg.max_new_tokens, self.max_len)

    def _optimistic_claim(self, plen: int, cfg) -> int:
        """Tokens an OPTIMISTIC admission claims up front: the prompt
        plus one page of headroom (the first decode step writes at
        position ``plen``, so bare-prompt coverage would force growth
        before the very first segment), never more than the worst case
        the reserved policy would take."""
        return min(plen + self.page_size, self._reserved(plen, cfg))

    def _can_admit(self, prompt_len: int, cfg) -> bool:
        """Whether the head-of-queue request's pages fit RIGHT NOW (a
        free slot is assumed). serve() consults this so a transiently
        full pool defers admission to the next inter-segment gap
        instead of raising mid-loop."""
        # any free slot owns zero pages, so capacity is slot-agnostic.
        # Prefix caching never tightens this probe: a warm admission
        # claims at most what a cold one would (shared pages count as
        # coverage), and when the pool cannot also spare the one
        # copy-on-write page a partial-block hit needs, admission
        # DEGRADES the hit to full blocks instead of demanding more
        # (so a request whose worst case exactly fills the pool still
        # admits). can_admit saying yes must mean add_request cannot
        # raise for capacity.
        probe = self._free[0] if self._free else 0
        if self.admission_mode == "reserved":
            return self.alloc.can_fit(probe,
                                      self._reserved(prompt_len, cfg))
        claim = self._optimistic_claim(prompt_len, cfg)
        if not self.alloc.can_fit(probe, claim):
            return False
        if self._slot_req:
            # high watermark: while running requests already crowd the
            # pool, pause NEW admissions before growth pressure forces
            # a preemption — running work frees pages by finishing. An
            # IDLE pool skips the watermark (a lone request must always
            # be able to admit, or a big claim could wedge forever).
            used_after = (self.alloc.used_pages
                          + self.alloc.pages_for(claim))
            if used_after > self.kv_watermark * self.num_pages:
                return False
        return True

    def free_slots(self) -> int:
        """Number of free cache slots right now. Public capacity probe
        (with :meth:`can_admit`) for serving schedulers — callers must
        not reach into the private ``_free`` list."""
        return len(self._free)

    def load(self) -> dict:  # lint: hot-path
        """Host-side load snapshot: ``{"free_slots", "active_slots",
        "max_batch", "max_len", "tp_degree", "free_pages",
        "total_pages", "occupancy", "kv_dtype"}``. Everything is host bookkeeping already
        maintained between segments — NO device sync, no HTTP, no lock
        beyond what the ints themselves need — so a health endpoint or
        a replica router can read it at any time, including while the
        scheduler thread is deep inside a decode segment. Consumed by
        ``Server.load()``/``/healthz`` and the router's least-loaded
        replica selection."""
        out = {"free_slots": len(self._free),
               "active_slots": len(self._slot_req),
               "max_batch": self.max_batch,
               "max_len": self.max_len,
               "tp_degree": self.tp_degree}
        if self.tp_mesh is not None:
            # mesh-shape surface for /healthz + routers: host-side
            # metadata only (the Mesh object is static), no device sync
            out["tp"] = {
                "degree": self.tp_degree,
                "axis": self.tp_mesh.axis_names[0],
                "devices": [str(d)
                            for d in self.tp_mesh.devices.flat]}
        out["free_pages"] = self.alloc.free_pages
        out["total_pages"] = self.alloc.num_pages
        out["occupancy"] = round(self.alloc.occupancy, 4)
        if self.adapters is not None:
            # registry snapshot (resident/draining names, capacity) —
            # host dict reads only; the router's adapter-affinity
            # scoring and /healthz both consume it
            out["lora"] = self.adapters.resident()
        out["kv_dtype"] = self.kv_dtype
        return out

    def can_admit(self, prompt_len: int, cfg: GenerationConfig) -> bool:
        """Non-raising admission probe: True iff ``add_request`` with a
        ``prompt_len``-token prompt and ``cfg`` would succeed RIGHT NOW
        (a free slot exists, the request fits ``max_len``, and the page
        pool can take its claim).

        Contract: schedulers consult THIS and treat False as "defer to
        the next inter-segment gap" (or reject with backpressure);
        ``add_request`` raising is the programmer-error path for callers
        that skipped the probe, not a control-flow signal."""
        return (bool(self._free)
                and prompt_len + cfg.max_new_tokens <= self.max_len
                and self._can_admit(prompt_len, cfg))

    # lint: hot-path
    def add_request(self, prompt_ids, cfg: GenerationConfig, *,
                    on_dispatch: Optional[Callable[[], None]] = None
                    ) -> int:
        """Prefill one request into a free slot; returns the request id.
        Raises if no slot is free (call decode_segment / collect first)
        — probe :meth:`can_admit` to defer instead of catching.
        ``on_dispatch`` runs once the admission's programs are on the
        device and before the host waits for its first token, as in
        :meth:`decode_segment`."""
        if not self._free:
            raise RuntimeError("no free slot; drain with decode_segment()")
        t0 = time.perf_counter()
        ids = _prompt_ids(prompt_ids)
        plen = ids.shape[1]
        if plen + cfg.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt({plen}) + max_new_tokens({cfg.max_new_tokens}) "
                f"exceeds engine max_len({self.max_len})")
        if not self._can_admit(plen, cfg):
            raise RuntimeError(
                "page pool exhausted; drain with decode_segment()")
        aidx = self._acquire_adapter(cfg)
        slot = heapq.heappop(self._free)
        self._aidx_stash[slot] = aidx
        try:
            rid = self._next_req
            self._next_req += 1
            last_logits = self._admit_cache(slot, ids, plen, cfg)
        except BaseException:
            # a failed admission must not leak capacity: the popped
            # slot (and any page reservation _admit_cache made; LoRA,
            # the adapter reference) goes back to the pool before the
            # error propagates
            self._abort_admit(slot)
            raise
        return self._first_token(slot, rid, ids, plen, last_logits, cfg,
                                 aidx, t0, on_dispatch)

    def _first_token(self, slot: int, rid: int, ids, plen: int,
                     last_logits, cfg, aidx: int, t0: float,
                     on_dispatch=None) -> int:
        """The tail every admission shares (one-shot, warm, chunked):
        ONE program (``cb_admit_state``) samples the first token from
        the prompt's last logits and installs the slot's state, ONE
        pull brings the token and its eos verdict to the host, and the
        request is registered. The device call and the pull sit inside
        the abort guard; the bookkeeping after them does not (no device
        call left to fail). Traced as ``engine.first_token``: the pull
        is where the host waits for the prefill it dispatched
        earlier."""
        with trace.span("engine.first_token"):
            try:
                first, tok_done = self._install_state(
                    slot, plen, last_logits, cfg, rid=rid, aidx=aidx,
                    ids=ids, on_dispatch=on_dispatch)
            except BaseException:
                self._abort_admit(slot)
                raise
            self._init_spec(rid, ids, first, cfg)
            self._plen[rid] = plen
            return self._register(slot, rid, first, tok_done, cfg, t0)

    def _acquire_adapter(self, cfg) -> int:
        """Resolve the request's adapter name to its bank index and
        take a live reference (0 = base model, no reference). Raises
        ValueError — a REQUEST-scoped verdict at the admission seam —
        for an unknown/unloading name or an adapter request against an
        engine built without ``lora_capacity``."""
        name = getattr(cfg, "adapter", None)
        if name is None:
            return 0
        if self.adapters is None:
            raise ValueError(
                f"request names adapter {name!r} but the engine was "
                f"built without lora_capacity")
        return self.adapters.acquire(name)

    def _adapter_salt(self, slot: int) -> bytes:
        """Prefix-cache chain salt for the admission in flight on
        ``slot`` (b"" = base namespace): cached KV is a function of the
        weights that produced it, so every adapter hashes its blocks in
        its own namespace and a cross-adapter warm hit is structurally
        impossible."""
        if self.adapters is None:
            return b""
        return self.adapters.salt(self._aidx_stash.get(slot, 0))

    def _init_spec(self, rid: int, ids, first, cfg) -> None:
        """Create the request's host-side n-gram proposer (speculative
        rows only), seeded with prompt + the admission's first token.
        A replayed/preempted request re-admits ``prompt + generated``
        as its prompt, so the proposer rebuilds with full context —
        the index is a pure function of it. Runs BEFORE ``_register``
        so an immediately-retired request's proposer is popped by
        ``_retire``, never leaked."""
        k = self._spec_k_for(cfg)
        if k > 0:
            self._spec[rid] = NgramProposer(
                [int(t) for t in ids[0]] + [first], k, self.ngram_max)

    def _spec_k_for(self, cfg) -> int:
        """Draft window for a request under ``cfg`` (0 = plain decode):
        needs an engine built with ``draft_k > 0``, a ``speculative``
        opt-in, and a GREEDY request — sampled rows fall back to plain
        decode (lossless acceptance needs the argmax target). The
        request's own ``draft_k`` caps the engine's (never widens it —
        the verify program's width is the engine's compile key)."""
        if (not self.draft_k or not getattr(cfg, "speculative", False)
                or cfg.do_sample):
            return 0
        k = getattr(cfg, "draft_k", None)
        return self.draft_k if k is None else min(int(k), self.draft_k)

    def _spec_k_of(self, rid: int) -> int:
        """Host-side draft window of an ACTIVE request (0 = plain)."""
        prop = self._spec.get(rid)
        return 0 if prop is None else prop.k

    def _install_state(self, slot: int, plen: int, last_logits, cfg,
                       rid: int = 0, aidx: int = 0, ids=None,
                       on_dispatch=None):
        """Sample the request's first token from ``last_logits`` (the
        prompt's last position, [1, V]: greedy the argmax, sampled from
        ``PRNGKey(cfg.seed + rid)``) and install its per-slot scalars
        AND sampling parameters (the LoRA adapter index included), all
        in ONE jitted program; returns ``(first, tok_done)`` as host
        values from ONE pull. ``ids`` (the host-side prompt, when the
        caller has one) seeds the slot's history ring with the prompt's
        trailing window — the device-mode draft source; a replayed
        request re-admits prompt+generated, so the ring rebuilds exactly
        like the host proposer's context."""
        eos = -1 if cfg.eos_token_id is None else cfg.eos_token_id
        H = self.spec_history
        hrow = np.zeros((H,), np.int32)
        hlen = 0
        if ids is not None:
            # lint: allow-host-sync(the prompt is a host array)
            tail = np.asarray(ids, np.int32).reshape(-1)[-(H - 1):]
            hrow[:len(tail)] = tail
            hlen = len(tail) + 1     # + the first token (set in-program)
        # the logits come out of whichever prefill program ran; under TP
        # they are committed replicated, so the program has one
        # signature. The scalars are numpy: they ride as arguments,
        # where a jnp.int32() each is a device program of its own
        # where it dispatches and where it waits are spans of their own
        # (children of ``engine.first_token`` in an admission): a host
        # stall with the chip idle then reads as one or the other
        with trace.span("engine.dispatch"):
            (out, self.lens, self.last, self.done_dev, self.samp,
             self.hist, self.hist_len) = self._admit_state(
                self.lens, self.last, self.done_dev, self.samp, self.hist,
                self.hist_len, np.int32(slot), np.int32(plen),
                self._tp_rep(last_logits), _u32(cfg.seed + rid),
                np.float32(cfg.temperature), np.int32(cfg.top_k),
                np.float32(cfg.top_p), np.bool_(cfg.do_sample),
                np.int32(eos), np.int32(cfg.seed % (2 ** 31)),
                np.int32(self._spec_k_for(cfg)), np.int32(aidx), hrow,
                np.int32(hlen))
        if on_dispatch is not None:
            on_dispatch()
        with trace.span("engine.wait"):
            # lint: allow-host-sync(the admission's ONE pull: the first
            # token and its eos verdict, packed; the host waits here for
            # the prefill it dispatched)
            first, tok_done = np.asarray(out).tolist()
        return first, bool(tok_done)

    def _register(self, slot: int, rid: int, first: int, tok_done: bool,
                  cfg, t0: float) -> int:
        """Host-side bookkeeping tail of a completed admission (one-shot
        or chunked): record the request (``first`` and ``tok_done`` are
        host values already), retire degenerate ones, count metrics.
        Runs OUTSIDE the abort guard — no device call left."""
        # a new live slot may be under-covered for the next segment
        # (optimistic claims stop at prompt + one page) — any growth
        # stamp predating it is stale, as is the gap's (lens, done)
        # snapshot (admission just wrote this slot's rows). Retire/free
        # paths only RELEASE capacity and never un-cover or advance a
        # surviving slot, so they keep both.
        self._growth_stamp = None
        self._gap_sync = None
        # the admission's adapter reference transfers from the slot
        # stash to the live request; _retire releases it
        self._rid_aidx[rid] = self._aidx_stash.pop(slot, 0)
        self._slot_req[slot] = rid
        self._tokens[rid] = [first]
        self._budget[rid] = cfg.max_new_tokens - 1
        self._cfg[rid] = cfg
        if tok_done or self._budget[rid] <= 0:
            self._retire(slot)
        if monitor.enabled():
            monitor.histogram(
                "paddle_tpu_kv_admission_seconds",
                "add_request latency: prefill + cache install + slot "
                "state update").observe(time.perf_counter() - t0)
            monitor.counter(
                "paddle_tpu_requests_total",
                "serving requests by lifecycle event",
                ("event",)).labels(event="admitted").inc()
            # the prompt's first generated token is sampled HERE, not in
            # a decode segment — count it so tokens_total means tokens
            monitor.counter(
                "paddle_tpu_generated_tokens_total",
                "tokens generated by the continuous-batching engines "
                "(admission first-token + decode segments)").inc()
        return rid

    # -- bounded-compile prefill helpers -------------------------------------
    def _prefill_width(self, plen: int) -> int:
        """Pad target for a plen-token prompt (plen itself when
        bucketing is disabled)."""
        if self.prefill_buckets is None:
            return plen
        return _bucket_for(self.prefill_buckets, plen)

    def _count_prefill(self, bucket) -> None:
        if monitor.enabled():
            monitor.counter(
                "paddle_tpu_prefill_requests_total",
                "admission prefills by engine and padded bucket width "
                "('chunked' = chunked admission)",
                ("engine", "bucket")).labels(
                engine=self._monitor_engine, bucket=str(bucket)).inc()

    def _prefill_span(self, plen: int, bucket: int, cached: int = 0,
                      fused: int = 0):
        """The ``engine.prefill`` span: the DISPATCH of one prefill
        program, not its device time. ``bucket`` (the compiled
        program's width) is the observable that explains its latency
        class, ``plen - cached`` is what it computes of the prompt,
        ``fused`` = 1 when the mini cache and the install ride inside
        the program, ``rows_run`` the rows its row-wise work covers: the
        bucket's, or the prompt's in whole blocks with a model that
        names a ``prefill_row_block``."""
        if not trace.enabled():
            return trace.NULL_SPAN
        rows = bucket
        if self._prefill_row_block is not None:
            from ..models._live_rows import rows_run

            rows = rows_run(bucket, plen, self._prefill_row_block)
        return trace.span("engine.prefill", engine=self._monitor_engine,
                          plen=plen, bucket=bucket, cached=cached,
                          fused=fused, rows_run=rows)

    def _cold_width(self, plen: int) -> int:
        """Program width of a cold one-shot prefill, counted."""
        width = self._prefill_width(plen)
        self._count_prefill(width if self.prefill_buckets is not None
                            else "exact")
        return width

    def _lookup_degraded(self, slot: int, ids, plen: int, cfg):
        """Shared warm-admission preamble (one-shot AND chunked):
        longest resident cached prefix — in the admission's ADAPTER
        namespace (the chain hash is salted with the adapter id, so a
        base-model block can never warm-hit an adapter's admission or
        vice versa) — degraded to full blocks when the pool cannot
        spare the partial page's CoW."""
        salt = self._adapter_salt(slot)
        pids, c_map, hashes = self.alloc.lookup_prefix(ids[0],
                                                       salt=salt)
        pids, c_map = self._degrade_partial_hit(slot, plen, cfg,
                                                pids, c_map)
        return pids, c_map, hashes, salt

    def _admit_cache(self, slot: int, ids, plen: int, cfg):
        """Prefill the prompt and install its KV into ``slot``'s pages;
        returns the prompt's last-position logits. A prefix-cache hit
        takes the warm path; everything else is the fused cold
        program."""
        if self.prefix_cache and not self.prefix_pause:
            pids, c_map, hashes, salt = self._lookup_degraded(
                slot, ids, plen, cfg)
            self._prefix_stash[slot] = {
                "ids": ids, "c_map": c_map, "hashes": hashes,
                "saved": min(c_map, plen - 1), "salt": salt}
            if c_map > 0:
                return self._admit_cache_warm(slot, ids, plen, cfg,
                                              pids, c_map)
        # COLD path: claim the pages (the program needs the slot's
        # page-table row; a claim that fails, fails before any device
        # work), then ONE program prefills into a mini cache sized to
        # the prompt's BUCKET (no max_len slab — the pool is the whole
        # point; the bucket keys the compiled program count to
        # O(len(buckets))) and scatters its rows into those pages
        with trace.span("engine.reserve"):
            self._reserve_admit(slot, plen, cfg)
        return self._run_prefill_paged(
            slot, ids, plen, aidx=self._aidx_stash.get(slot, 0))

    def _run_prefill_paged(self, slot: int, ids, plen: int,
                           aidx: int = 0):
        """Pad the prompt to its bucket and run the fused cold-admission
        program (``engine.prefill{fused=1}``: mini cache, prefill under
        the request's adapter, install) into ``slot``'s claimed pages;
        returns the last-position logits [1, V]."""
        width = self._cold_width(plen)
        # int8: the claimed pages' scale rows reset BEFORE the program
        # runs its running absmax against them
        self._flush_fresh_scales()
        with self._prefill_span(plen, width, fused=1) as sp:
            if self._ring is not None and trace.enabled():
                # rows of the prompt that go into the window layers' rings
                ps, ring = self.page_size, self._ring["ring_pages"]
                first_page = max((plen - 1) // ps - ring + 1, 0)
                sp.set(window_rows=plen - first_page * ps)
            last_logits = self._prefill_install(
                slot, _pad_ids(ids, width), plen, aidx)
        self._index_prompt(slot, plen)
        return last_logits

    def _prefill_install(self, slot: int, ids, plen: int, aidx: int):
        pt = self._device_tables()
        pools, _ = self.caches
        # numpy scalars ride as arguments: a jnp.int32() is a device
        # program of its own
        last_logits, pools = self._prefill_paged(
            self.params, ids, pools, pt, np.int32(slot), np.int32(plen),
            self._bank(), np.int32(aidx))
        self.caches = (pools, pt)
        return last_logits

    def _degrade_partial_hit(self, slot: int, plen: int, cfg, pids,
                             c_map: int):
        """A partial-block hit (coverage ending mid-page) maps a page
        the request must copy-on-write before its first write — one
        page BEYOND its normal claim. When the pool cannot spare it,
        DEGRADE the hit to full blocks (drop the partial page) rather
        than demand extra capacity: a request whose worst case exactly
        fills the pool must still admit, cache or no cache."""
        ps = self.page_size
        if not pids or c_map % ps == 0:
            return pids, c_map
        claim = (self._reserved(plen, cfg)
                 if self.admission_mode == "reserved"
                 else self._optimistic_claim(plen, cfg))
        if self.alloc.can_fit(slot, claim + ps):
            return pids, c_map
        return pids[:-1], (c_map // ps) * ps

    def _admit_cache_warm(self, slot: int, ids, plen: int, cfg, pids,
                          c_map: int):
        """Prefix-cache hit admission: gather the cached prefix KV from
        the resident pages (a pure copy — bitwise what the original
        prefill wrote), prefill ONLY the uncached tail at a traced
        offset through the shared chunk program, then map the cached
        pages read-only and install the tail. At least the LAST prompt
        token always recomputes — its logits seed the first sampled
        token — even when the whole prompt is resident (its KV write
        is simply masked out then)."""
        # compute start: everything below is served from cache; cap at
        # plen-1 so the last position's logits exist
        c_cmp = min(c_map, plen - 1)
        wt = (plen - c_cmp if self.prefill_buckets is None
              else _bucket_for(self.prefill_buckets, plen - c_cmp))
        # the tail chunk writes mini rows [c_cmp, c_cmp+wt) — pull the
        # compute start DOWN when the bucket would overhang max_len
        # (the fwd's dynamic_update_slice clamps, which would corrupt
        # cached rows); recomputing a few extra cached positions is
        # value-neutral (their installs are masked out) and keeps the
        # program keyed on wt alone
        c_cmp = min(c_cmp, self.max_len - wt)
        # tokens-saved is the compute actually skipped ([0, c_cmp)),
        # not the raw coverage — the clamp above shrinks it
        self._prefix_stash[slot]["saved"] = c_cmp
        tail = plen - c_cmp
        with trace.span("engine.mini_cache"):
            mini = self._mini_cache(self.max_len)
            mini = self._gather_mini(mini, pids)
        self._count_prefill("warm")
        tail_ids = _pad_ids(ids[:, c_cmp:], wt)
        # bucket: the tail program's width
        with self._prefill_span(plen, wt, cached=c_cmp):
            last_logits, mini = self._prefill_chunk(
                self.params, tail_ids, mini, np.int32(c_cmp),
                np.int32(tail - 1), self._bank(),
                np.int32(self._aidx_stash.get(slot, 0)))
        with trace.span("engine.reserve"):
            self.alloc.map_shared(slot, pids)
            self._reserve_admit(slot, plen, cfg)
        with trace.span("engine.install"):
            self._install_mini(slot, mini, plen)
        return last_logits

    def _gather_mini(self, mini, pids):
        """Copy the resident pages into the head of a max_len-width
        dense mini cache (per layer) — the cached-prefix KV the tail
        prefill attends over. The page vector is padded to the FULL
        page-table row width so every warm admission shares one
        compiled gather program (junk rows for the ``-1`` tail sit
        past the cached coverage, overwritten or masked)."""
        from .paged_cache import gather_pages, gather_pages_q

        row = np.full((self.alloc.page_table.shape[1],), -1, np.int32)
        row[:len(pids)] = pids
        pages = jnp.asarray(row)
        pools, _ = self.caches
        out = []
        if self.kv_dtype == "int8":
            # dequantize whole resident pages into the float mini: the
            # tail prefill attends over exactly the values the fused
            # decode reads see, so warm and cold agree to quantization
            # error, never to a format skew
            for (kp, vp, ks, vs), (mk, mv) in zip(pools, mini):
                mk, mv = gather_pages_q(kp, vp, ks, vs, pages, mk, mv)
                out.append((mk, mv))
            return out
        for (kp, vp), (mk, mv) in zip(pools, mini):
            mk, mv = gather_pages(kp, vp, pages, mk, mv)
            out.append((mk, mv))
        return out

    def _cow_page(self, slot: int, page_idx: int) -> None:
        """Host-side copy-on-write of one shared page in the
        inter-segment gap: claim a fresh page (allocator bookkeeping),
        copy the pool rows on device, swap the table entry (shipped at
        the next segment)."""
        from .paged_cache import copy_page, copy_page_q

        old, new = self.alloc.cow(slot, page_idx)
        pools, pt = self.caches
        new_pools = []
        if self.kv_dtype == "int8":
            # the copy carries the page's SCALES with its rows (int8
            # rows are meaningless under another page's scale); the
            # note tells the allocator's scale accounting the copy
            # happened — forgetting either fails check() loudly
            for kp, vp, ks, vs in pools:
                kp, vp, ks, vs = copy_page_q(kp, vp, ks, vs,
                                             np.int32(old),
                                             np.int32(new))
                new_pools.append((kp, vp, ks, vs))
            self.caches = (new_pools, pt)
            self.alloc.note_scale_copied(new)
            return
        for kp, vp in pools:
            kp, vp = copy_page(kp, vp, np.int32(old), np.int32(new))
            new_pools.append((kp, vp))
        self.caches = (new_pools, pt)

    def _reserve_admit(self, slot: int, plen: int, cfg) -> None:
        """Claim the pages the admission will need UP FRONT (reserved:
        the worst case; optimistic: prompt + one page), so a chunked
        admission can never fail for capacity halfway through."""
        self.alloc.ensure(
            slot, self._reserved(plen, cfg)
            if self.admission_mode == "reserved"
            else self._optimistic_claim(plen, cfg))

    def _install_mini(self, slot: int, mini, plen: int) -> None:
        """Install a prefilled mini cache (a warm hit's, a chunked
        admission's) into ``slot``'s pages."""
        from .paged_cache import install_prompt

        # int8: reset freshly claimed pages' scale rows BEFORE the
        # quantized install runs its running absmax against them
        self._flush_fresh_scales()
        info = self._prefix_stash.get(slot)
        if info is not None and info["c_map"] > 0:
            self._install_mini_warm(slot, mini, plen, info)
        else:
            # COLD scatter of a mini that outlived its programs (a
            # chunked admission's): the whole mini in ONE program, the
            # scatter the fused prefill ends with. Rows past plen land
            # on reserved-but-unwritten positions the decode mask
            # hides and decode writes overwrite, or drop (unmapped
            # pages; int8: everything past plen)
            pt = self._device_tables()
            pools, _ = self.caches
            self.caches = (install_prompt(pools, pt, np.int32(slot),
                                          np.int32(plen), mini), pt)
        self._index_prompt(slot, plen)

    def _index_prompt(self, slot: int, plen: int) -> None:
        """Prefix cache: a cold admission POPULATES the cache, a warm
        one extends it — either way the prompt's fully-written private
        blocks become future hits (in the admission's adapter
        namespace). Runs once the rows are in the pages."""
        info = self._prefix_stash.pop(slot, None)
        if info is None:
            return
        ps = self.page_size
        self.alloc.register_blocks(
            slot, info["hashes"], info["ids"][0],
            info["c_map"] // ps, plen // ps,
            salt=info.get("salt", b""))
        if info["c_map"] > 0:
            self.alloc.count_prefix_hit(info["saved"])

    def _install_mini_warm(self, slot: int, mini, plen: int,
                           info) -> None:
        """Install a warm admission's UNCACHED suffix: copy-on-write
        the shared page the first write would land in (divergent
        suffix mid-block — or, fully-cached prompts, the partial tail
        page decode will append into), then scatter exactly the rows
        ``[c_map, plen)``. Shared pages are never written: positions
        below the cached coverage are masked out of the scatter, and
        the garbage tail past ``plen`` lands only in private headroom
        pages or drops on unmapped ones."""
        from .paged_cache import scatter_rows, scatter_rows_q

        ps = self.page_size
        c_map = info["c_map"]
        # first position this slot will EVER write: the uncached
        # suffix's start, or (fully cached) decode's first append
        p0 = c_map if c_map < plen else plen
        if p0 % ps and self.alloc.needs_cow(slot, p0):
            self._cow_page(slot, p0 // ps)
        pt = self._device_tables()
        if c_map < plen:
            mini_len = mini[0][0].shape[1]
            width = (plen - c_map if self.prefill_buckets is None
                     else _bucket_for(self.prefill_buckets,
                                      plen - c_map))
            width = min(width, mini_len)
            pools, _ = self.caches
            new_pools = []
            if self.kv_dtype == "int8":
                # masked-out rows drop from the quantized scatter too,
                # so shared read-only pages keep rows AND scales; the
                # CoW'd partial page's copied scales seed the running
                # absmax for the suffix rows landing in it
                for (kp, vp, ks, vs), (mk, mv) in zip(pools, mini):
                    kp, vp, ks, vs = scatter_rows_q(
                        kp, vp, ks, vs, pt, np.int32(slot),
                        np.int32(c_map), np.int32(plen), mk, mv,
                        width=width)
                    new_pools.append((kp, vp, ks, vs))
            else:
                for (kp, vp), (mk, mv) in zip(pools, mini):
                    kp, vp = scatter_rows(
                        kp, vp, pt, np.int32(slot), np.int32(c_map),
                        np.int32(plen), mk, mv, width=width)
                    new_pools.append((kp, vp))
            self.caches = (new_pools, pt)
        else:
            pools, _ = self.caches
            self.caches = (pools, pt)

    def _abort_admit(self, slot: int) -> None:
        """Undo a failed admission's capacity claim: adapter reference
        released, slot back to the free list, its pages back to the
        pool."""
        aidx = self._aidx_stash.pop(slot, 0)
        if aidx and self.adapters is not None:
            self.adapters.release(aidx)
        heapq.heappush(self._free, slot)
        self._prefix_stash.pop(slot, None)
        self.alloc.free_slot(slot)   # release any reserved pages

    def _retire(self, slot, event: str = "finished"):
        rid = self._slot_req.pop(slot)
        # lint: allow-host-sync(host-list copy: _tokens is python-side
        # bookkeeping, no device read happens here)
        self._finished[rid] = np.asarray(self._tokens.pop(rid), np.int32)
        del self._budget[rid]
        self._plen.pop(rid, None)
        self._cfg.pop(rid, None)
        self._spec.pop(rid, None)
        aidx = self._rid_aidx.pop(rid, 0)
        if aidx and self.adapters is not None:
            # last live reference completes a deferred unload; the
            # device vector keeps the stale index for this dead slot —
            # harmless (dead rows are masked, and the index is only
            # rewritten when a future load recycles it)
            self.adapters.release(aidx)
        # no device write: the segment programs take the live mask from
        # ``_slot_req`` (`_active_mask`) and mask the sampled flags by
        # it, so an all-greedy batch regains `_sample_rows`' fast path
        # once sampled requests retire
        # heap, not append+sort: retire/abort run in the latency-critical
        # inter-segment gap, and admission must stay deterministic
        # (lowest free slot first) without an O(n log n) sort per event
        heapq.heappush(self._free, slot)
        if monitor.enabled():
            monitor.counter(
                "paddle_tpu_requests_total",
                "serving requests by lifecycle event",
                ("event",)).labels(event=event).inc()
        self.alloc.free_slot(slot)

    def _evict_active(self, rid: int, event: str):
        """Shared reclaim for the early-removal paths (cancel, preempt):
        retire ``rid``'s slot — capacity back to the pool, request never
        in ``collect_finished()`` — and return its partial tokens
        (np.int32), or None when ``rid`` is not active."""
        slot = next((s for s, r in self._slot_req.items() if r == rid),
                    None)
        if slot is None:
            return None
        out = np.asarray(self._tokens[rid], np.int32)
        self._retire(slot, event=event)
        self._finished.pop(rid, None)
        return out

    def cancel_request(self, rid: int):
        """Cancel an ACTIVE request and reclaim its capacity: the slot
        and its pages return to the pool immediately and the
        request never appears in ``collect_finished()``. Returns the
        partial tokens generated so far (np.int32), or None when ``rid``
        is not active (unknown, already finished, or already cancelled).

        Call only from the thread driving the engine, BETWEEN decode
        segments — the serving scheduler applies user ``cancel()`` flags
        at the next inter-segment gap, which is what keeps cancelled
        slots from leaking mid-segment."""
        return self._evict_active(rid, "cancelled")

    def preempt_request(self, rid: int, reason: str = "pressure"):
        """Preempt an ACTIVE request under memory pressure: reclaim its
        slot AND pages immediately (mirroring ``cancel_request``'s
        reclaim) and return the partial tokens generated so far
        (np.int32) — the caller owns parking them and replaying
        ``prompt + tokens`` through normal admission later (greedy
        replay is bitwise-identical to an unpreempted run; see the
        serving scheduler's replay machinery). Returns None when
        ``rid`` is not active. The request never appears in
        ``collect_finished()``; the retirement event and the pool's
        ``paddle_tpu_kv_preemptions_total{reason}`` counter record it.

        Like ``cancel_request``: call only from the thread driving the
        engine, BETWEEN decode segments."""
        out = self._evict_active(rid, "preempted")
        if out is not None:
            self.alloc.count_preemption(reason)
        return out

    def partial_tokens(self, rid: int, start: int = 0):
        """Copy of the tokens generated so far for an ACTIVE request,
        from position ``start`` (the token-streaming hook: schedulers
        pass the count they already pushed so each inter-segment gap
        copies one segment's delta, not the whole growing history), or
        None when ``rid`` is not active."""
        toks = self._tokens.get(rid)
        return None if toks is None else list(toks[start:])

    # -- supervised recovery (host-driven, engine-owning thread only) --------
    def reset_state(self) -> None:
        """Drop EVERY request and rebuild the engine's device-side
        decode state from scratch: fresh caches, lengths, done/active
        flags, per-slot sampling vectors, a full free-slot list and the
        whole page pool. Compiled programs are KEPT — after
        an engine-scoped fault (:class:`EngineFault`, a device error mid
        ``decode_segment``) the device arrays are suspect but the jitted
        programs are not, so a supervised restart pays device re-init
        plus replay prefills, never a recompile.

        In-flight requests are forgotten, not finished: the caller (the
        serving scheduler's recovery path) owns replaying them from
        their stored prompt + tokens emitted so far. ``_next_req`` is
        NOT reset — request ids stay unique across restarts, so a stale
        pre-restart rid can never alias a replayed request."""
        # every slot's pages go back to the pool BEFORE the rebuild
        # reads alloc.page_table into the fresh cache tuple — a restart
        # must leave zero pages leaked no matter what the fault
        # interrupted
        for slot in range(self.max_batch):
            self.alloc.free_slot(slot)
        # the pools are rebuilt from zeros below: every cached block's
        # KV is gone, so the content index must go with it (parked
        # pages return to the free heap)
        self.alloc.clear_prefix_index()
        # the fresh pools below start at floor scales: pending resets
        # refer to arrays about to be dropped
        self.alloc.take_fresh_scales()
        self._prefix_stash.clear()
        self._growth_stamp = None
        self._gap_sync = None
        # drop the old pool BEFORE the rebuild allocates the new one:
        # both alive at once would double peak KV HBM at the exact
        # moment (device-fault recovery, pool sized near capacity) a
        # second pool cannot fit
        self.caches = None
        self._init_decode_state()
        self._slot_req.clear()
        self._tokens.clear()
        self._budget.clear()
        self._plen.clear()
        self._cfg.clear()
        self._spec.clear()
        self._finished.clear()
        # every live adapter reference was just forgotten with its
        # slot; the bank and name map SURVIVE (adapters are weights —
        # a supervised restart must not lose them), deferred unloads
        # complete now that nothing references them
        self._aidx_stash.clear()
        self._rid_aidx.clear()
        if self.adapters is not None:
            self.adapters.release_all()
        if monitor.enabled():
            monitor.counter(
                "paddle_tpu_requests_total",
                "serving requests by lifecycle event",
                ("event",)).labels(event="engine_reset").inc()

    # -- multi-tenant LoRA (host-driven, between segments) -------------------
    def load_adapter(self, name: str, params: dict, alpha=None) -> int:
        """Hot-load one LoRA adapter into the device bank; returns its
        bank index. ``params`` maps target projection names to
        ``(A, B)`` factor pairs (see
        :meth:`~paddle_tpu.serving.adapters.AdapterRegistry.load`).
        Only rewrites bank ROWS — the compiled serving programs are
        untouched, so a load costs zero recompiles (post-``warmup``,
        zero compiles at all).

        Like ``cancel_request``: call only from the thread driving the
        engine, BETWEEN decode segments — the serving scheduler's
        ``Server.load_adapter`` marshals into the inter-segment gap."""
        if self.adapters is None:
            raise RuntimeError(
                "engine built without lora_capacity; pass "
                "lora_capacity=K at construction")
        return self.adapters.load(name, params, alpha=alpha)

    def unload_adapter(self, name: str) -> bool:
        """Hot-unload an adapter. Returns True when its bank index
        freed immediately; False when live requests still decode under
        it — the unload DEFERS (new requests naming it are rejected at
        admission; the index frees, and becomes recyclable, when the
        last live slot retires). Same thread contract as
        :meth:`load_adapter`."""
        if self.adapters is None:
            raise RuntimeError(
                "engine built without lora_capacity; pass "
                "lora_capacity=K at construction")
        return self.adapters.unload(name)

    def export_kv_pages(self, tokens, salt: bytes = b"") -> dict:
        """Export the resident cached KV pages covering a prompt's
        longest FULL-BLOCK prefix: the read half of a cross-process
        page handoff (disaggregated prefill/decode). Returns a payload
        of chain-hashed blocks plus per-layer page rows — raw pool
        dtype (int8 rows ship with their per-page scales), so the
        transfer is a page COPY, never a format conversion.

        Must run on the scheduler thread in the inter-segment gap
        (``Server.export_kv`` marshals there): the pools are DONATED
        by device writes, so no other thread may read ``self.caches``.
        Partial-block tails never export — the importer parks blocks
        refcount-0 with no CoW discipline attached, so only token-
        complete, hash-verified pages are safe to ship."""
        from .paged_cache import _chain_root

        ids = np.ascontiguousarray(
            np.asarray(tokens).reshape(-1), np.int32)
        pids, cov, hashes = self.alloc.lookup_prefix(ids, salt=salt)
        ps = self.page_size
        nfull = min(len(pids), cov // ps, len(hashes))
        pids = pids[:nfull]
        root = _chain_root(salt)
        blocks = []
        for b in range(nfull):
            blocks.append({
                "hash": hashes[b].hex(),
                "parent": (hashes[b - 1] if b else root).hex(),
                "tokens": ids[b * ps:(b + 1) * ps].tolist()})
        pools, _pt = self.caches
        idx = np.asarray(pids, np.int32)
        layers = []
        for pool in pools:
            if self.kv_dtype == "int8":
                kp, vp, ks, vs = pool
                layers.append({"k": np.asarray(kp[idx]),
                               "v": np.asarray(vp[idx]),
                               "k_scale": np.asarray(ks[idx]),
                               "v_scale": np.asarray(vs[idx])})
            else:
                kp, vp = pool
                layers.append({"k": np.asarray(kp[idx]),
                               "v": np.asarray(vp[idx])})
        return {"version": 1, "kv_dtype": self.kv_dtype,
                "page_size": ps, "salt": salt.hex(),
                "coverage": nfull * ps, "blocks": blocks,
                "layers": layers}

    def import_kv_pages(self, payload: dict) -> dict:
        """Install exported KV pages into this engine's pools and
        prefix index: the write half of the cross-process handoff.
        Every block re-derives its chain hash from (parent, tokens)
        before adoption — a corrupted or mis-framed page can never
        enter the content index — and an already-resident hash is a
        dedup no-op (``PageAllocator.adopt_block``), which makes a
        replayed handoff idempotent. Imported pages PARK (refcount 0,
        LRU-reclaimable): the next admission of the matching prompt
        warm-hits them read-only through the ordinary prefix-cache
        path. Same gap-only threading contract as
        :meth:`export_kv_pages`. Returns
        ``{"imported", "deduped", "coverage"}``."""
        from .paged_cache import (_block_hash, install_page,
                                  install_page_q)

        if payload.get("kv_dtype") != self.kv_dtype:
            raise ValueError(
                f"kv_dtype mismatch: payload "
                f"{payload.get('kv_dtype')!r} vs engine "
                f"{self.kv_dtype!r} — KV handoff is a page copy, "
                f"never a format conversion")
        if int(payload.get("page_size", -1)) != self.page_size:
            raise ValueError(
                f"page_size mismatch: payload "
                f"{payload.get('page_size')} vs engine "
                f"{self.page_size}")
        pools, _pt = self.caches
        layers = payload.get("layers") or []
        if len(layers) != len(pools):
            raise ValueError(
                f"layer count mismatch: payload {len(layers)} vs "
                f"engine {len(pools)}")
        blocks = payload.get("blocks") or []
        kp0 = pools[0][0]
        for lay in layers:
            for key in (("k", "v", "k_scale", "v_scale")
                        if self.kv_dtype == "int8" else ("k", "v")):
                arr = lay.get(key)
                if arr is None or len(arr) != len(blocks):
                    raise ValueError(
                        f"payload layer missing/short {key!r} rows")
            if (tuple(lay["k"].shape[1:]) != tuple(kp0.shape[1:])
                    or lay["k"].dtype != kp0.dtype):
                raise ValueError(
                    f"page geometry mismatch: payload "
                    f"{lay['k'].dtype}{lay['k'].shape[1:]} vs pool "
                    f"{kp0.dtype}{tuple(kp0.shape[1:])}")
        imported = deduped = 0
        for b, blk in enumerate(blocks):
            h = bytes.fromhex(blk["hash"])
            parent = bytes.fromhex(blk["parent"])
            toks = np.ascontiguousarray(
                np.asarray(blk["tokens"]).reshape(-1), np.int32)
            if _block_hash(parent, toks) != h:
                raise ValueError(
                    f"block {b}: chain hash does not match "
                    f"(parent, tokens) — corrupted handoff rejected")
            pid = self.alloc.adopt_block(h, parent, toks)
            if pid is None:
                deduped += 1
                continue
            pools, pt = self.caches
            new_pools = []
            if self.kv_dtype == "int8":
                for (kp, vp, ks, vs), lay in zip(pools, layers):
                    kp, vp, ks, vs = install_page_q(
                        kp, vp, ks, vs, np.int32(pid),
                        lay["k"][b], lay["v"][b],
                        lay["k_scale"][b], lay["v_scale"][b])
                    new_pools.append((kp, vp, ks, vs))
                self.caches = (new_pools, pt)
                self.alloc.note_scale_copied(pid)
            else:
                for (kp, vp), lay in zip(pools, layers):
                    kp, vp = install_page(kp, vp, np.int32(pid),
                                          lay["k"][b], lay["v"][b])
                    new_pools.append((kp, vp))
                self.caches = (new_pools, pt)
            imported += 1
        return {"imported": imported, "deduped": deduped,
                "coverage": len(blocks) * self.page_size}

    # -- chunked admission (host-driven, one chunk per inter-segment gap) ----
    # lint: hot-path
    def begin_admit(self, prompt_ids, cfg: GenerationConfig):
        """Start a CHUNKED admission: claim the slot AND the request's
        pages up front — the existing
        ``_can_admit``/``_abort_admit`` contract, so a partial admission
        can never leak capacity or fail for capacity halfway through —
        then return the admission object. The caller (the serving
        scheduler's gap) drives ONE fixed-shape prefill chunk per
        :meth:`admit_chunk` call, interleaving decode segments between
        chunks so a long prompt never monopolizes the gap.

        Raises like ``add_request`` when the request cannot be admitted
        RIGHT NOW (probe :meth:`can_admit` first) and RuntimeError when
        the engine was built without ``prefill_chunk``."""
        if self.prefill_chunk is None:
            raise RuntimeError(
                "chunked admission needs an engine built with "
                "prefill_chunk=<tokens>")
        if not self._free:
            raise RuntimeError("no free slot; drain with decode_segment()")
        ids = _prompt_ids(prompt_ids)
        plen = ids.shape[1]
        if plen + cfg.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt({plen}) + max_new_tokens({cfg.max_new_tokens}) "
                f"exceeds engine max_len({self.max_len})")
        if not self._can_admit(plen, cfg):
            raise RuntimeError(
                "page pool exhausted; drain with decode_segment()")
        aidx = self._acquire_adapter(cfg)
        slot = heapq.heappop(self._free)
        # the adapter reference is claimed for the WHOLE chunked
        # admission (an unload defers while chunks are still running);
        # _register transfers it to the rid, _abort_admit releases it
        self._aidx_stash[slot] = aidx
        try:
            mini, start = self._begin_admit_cache(slot, ids, plen, cfg)
        except BaseException:
            self._abort_admit(slot)
            raise
        rid = self._next_req
        self._next_req += 1
        self._count_prefill("chunked")
        return _ChunkedAdmission(rid, slot, ids, plen, cfg, mini,
                                 off=start)

    def _begin_admit_cache(self, slot: int, ids, plen: int, cfg):
        """Claim a chunked admission's capacity and build its mini
        cache; returns ``(mini, chunk_start)``. Chunk programs are
        keyed on the FIXED (chunk, max_len) shapes, so every chunked
        admission shares one compiled program (at the price of a
        transient max_len mini slab for the admission's lifetime).
        With the prefix cache on, cached prefix pages are mapped first
        and chunking starts past them."""
        if not self.prefix_cache or self.prefix_pause:
            with trace.span("engine.reserve"):
                self._reserve_admit(slot, plen, cfg)
            with trace.span("engine.mini_cache"):
                return self._mini_cache(self.max_len), 0
        pids, c_map, hashes, salt = self._lookup_degraded(slot, ids,
                                                          plen, cfg)
        C = self.prefill_chunk
        # chunk windows must stay C-aligned (an overhanging window
        # would clamp and corrupt earlier KV), so the cursor starts at
        # the cached coverage aligned DOWN — the [start, c_map) sliver
        # recomputes but its writes are masked out at install
        start = (min(c_map, plen - 1) // C) * C
        self._prefix_stash[slot] = {"ids": ids, "c_map": c_map,
                                    "hashes": hashes, "saved": start,
                                    "salt": salt}
        with trace.span("engine.reserve"):
            self.alloc.map_shared(slot, pids)
            self._reserve_admit(slot, plen, cfg)
            # copy-on-write the partial shared page EAGERLY, while the
            # claim is atomic with the reservation — install runs gaps
            # later, and the spare page must not be stolen by growth or
            # another admission in between
            p0 = c_map if c_map < plen else plen
            if p0 % self.page_size and self.alloc.needs_cow(slot, p0):
                self._cow_page(slot, p0 // self.page_size)
        with trace.span("engine.mini_cache"):
            mini = self._mini_cache(self.max_len)
            if pids:
                # full cached coverage gathered (fixed-shape program);
                # rows the chunks recompute from `start` just overwrite
                # their gathered copies with bitwise-identical values
                mini = self._gather_mini(mini, pids)
        return mini, start

    # lint: hot-path
    def admit_chunk(self, adm: _ChunkedAdmission, *,
                    on_dispatch: Optional[Callable[[], None]] = None
                    ) -> bool:
        """Run ONE fixed-shape prefill chunk of an admission started
        with :meth:`begin_admit`. Returns True when the admission
        completed — the request is live in its slot under ``adm.rid``
        (its first token is in ``partial_tokens``). On ANY failure the
        claimed capacity is reclaimed and the admission is closed.
        ``on_dispatch``: as :meth:`add_request`'s, on the final chunk."""
        if adm.closed:
            raise RuntimeError("admission already completed or aborted")
        C = self.prefill_chunk
        try:
            aidx = self._aidx_stash.get(adm.slot, 0)
            chunk = adm.ids[:, adm.off:adm.off + C]
            r = chunk.shape[1]
            last = adm.off + r >= adm.plen
            if r < C:       # only the FINAL chunk may be partial
                chunk = _pad_ids(chunk, C)
            # ``cached``: the prompt tokens already in the mini
            with self._prefill_span(adm.off + r, C, cached=adm.off):
                adm.last_logits, adm.mini = self._prefill_chunk(
                    self.params, chunk, adm.mini, np.int32(adm.off),
                    np.int32(r - 1), self._bank(), np.int32(aidx))
            adm.off += C
            adm.chunks_done += 1
            if monitor.enabled():
                monitor.counter(
                    "paddle_tpu_prefill_chunks_total",
                    "fixed-shape prefill chunks run by chunked "
                    "admissions", ("engine",)).labels(
                    engine=self._monitor_engine).inc()
            if not last:
                return False
            with trace.span("engine.install"):
                self._install_mini(adm.slot, adm.mini, adm.plen)
        except BaseException:
            adm.closed = True
            self._abort_admit(adm.slot)
            raise
        adm.closed = True       # _first_token reclaims on ITS failures
        self._first_token(adm.slot, adm.rid, adm.ids, adm.plen,
                          adm.last_logits, adm.cfg, aidx, adm.t0,
                          on_dispatch)
        return True

    def abort_admit(self, adm: _ChunkedAdmission) -> None:
        """Abandon an in-flight chunked admission (client cancelled mid
        prefill): the slot and any page reservation return to the pool.
        Idempotent; the admission is closed either way."""
        if adm.closed:
            return
        adm.closed = True
        self._abort_admit(adm.slot)

    # -- warmup (off the request path) ---------------------------------------
    def warmup(self, segment_steps: Optional[int] = None):
        """Pre-compile every program a request can hit on the serving
        path — one prefill per bucket, the chunked-prefill program, the
        cache-install and slot-state programs, and (when
        ``segment_steps`` is given) the decode segment — so no user
        request ever pays an XLA compile inside the latency-critical
        gap. Compile time lands on the existing ``monitored_jit``
        counters (``paddle_tpu_jit_cache_miss_total`` /
        ``jit_compile_seconds_total``). Only valid on an IDLE engine;
        returns {program_name: seconds}.
        """
        if self._slot_req:
            raise RuntimeError("warmup() needs an idle engine")
        t_all = time.perf_counter()
        out = {}
        # with bucketing DISABLED prompt lengths (and so prefill
        # programs) are unbounded — warmup cannot cover them, so it
        # warms only the length-independent programs
        widths = self.prefill_buckets or ()
        logits = None
        for w in widths:
            t0 = time.perf_counter()
            logits = self._warmup_prefill(w)
            out[f"prefill_{w}"] = time.perf_counter() - t0
        if self.prefill_chunk is not None:
            t0 = time.perf_counter()
            logits, mini = self._prefill_chunk(
                self.params, np.zeros((1, self.prefill_chunk), np.int32),
                self._mini_cache(self.max_len), np.int32(0),
                np.int32(0), self._bank(), np.int32(0))
            # and the install of a max_len mini (into the free slot 0)
            self._install_mini(0, mini, self.prefill_chunk)
            out["prefill_chunk"] = time.perf_counter() - t0
        if logits is not None:
            # the admission tail's program (first token + slot state),
            # fed a warmed prefill's own logits so that it compiles for
            # the type and placement a real admission hands it. Slot 0
            # stays free: no live mask names it, and its next admission
            # rewrites every value this installs
            t0 = time.perf_counter()
            self._install_state(0, 0, logits,
                                GenerationConfig(max_new_tokens=1))
            out["admit_state"] = time.perf_counter() - t0
        mb = self.max_batch
        idle = np.zeros((mb,), bool)
        if segment_steps is not None:
            # with every slot inactive the segment is a semantic no-op
            # (live rows mask to nothing), so running it only compiles
            t0 = time.perf_counter()
            (_, self.last, self.lens, self.done_dev, self.caches, _) = \
                self._segment_fn(segment_steps)(
                    self.params, self.last, self.lens, self.done_dev,
                    idle, self.samp, self._bank(), self.caches,
                    _u32(0), _u32(0))
            out[f"segment_{segment_steps}"] = time.perf_counter() - t0
        if self.draft_k and self.spec_mode == "host":
            # the widened speculative verify step: with every slot
            # inactive (live mask all-False) acceptance is 0 and every
            # KV write drops, so running it only compiles
            t0 = time.perf_counter()
            (_, _, self.last, self.lens, self.caches) = \
                self._spec_step_fn()(
                    self.params, self.last, self.lens, idle,
                    self.samp, self._bank(), self.caches, _u32(0),
                    _u32(0), np.zeros((mb, self.draft_k), np.int32),
                    idle, np.zeros((mb,), np.int32))
            out[f"spec_step_{self.draft_k}"] = time.perf_counter() - t0
        if (self.draft_k and self.spec_mode == "device"
                and segment_steps is not None):
            # the fused device-resident speculative segment: like the
            # plain segment warm, all-inactive rows make every step a
            # masked no-op, so running it only compiles — the program
            # a speculating request hits is hot before the first
            # admission
            t0 = time.perf_counter()
            (_, self.last, self.lens, self.done_dev, self.hist,
             self.hist_len, self.caches) = \
                self._spec_segment_device_fn(segment_steps)(
                    self.params, self.last, self.lens, self.done_dev,
                    idle, self.samp, self._bank(), self.caches,
                    self.hist, self.hist_len,
                    np.zeros((mb,), np.int32),
                    np.zeros((mb,), np.int32), _u32(0), _u32(0))
            out[f"spec_segment_{segment_steps}"] = \
                time.perf_counter() - t0
        if self.adapters is not None:
            # per-target bank-row install programs: the first hot
            # load() in a serving gap must not pay an XLA compile
            t0 = time.perf_counter()
            self.adapters.warmup()
            out["lora_install"] = time.perf_counter() - t0
        out.update(self._warmup_prefix())
        out["total"] = time.perf_counter() - t_all
        if monitor.enabled():
            monitor.gauge(
                "paddle_tpu_prefill_warmup_seconds",
                "wall seconds engine.warmup() spent pre-compiling the "
                "serving-path programs", ("engine",)).labels(
                engine=self._monitor_engine).set(out["total"])
        return out

    def _warmup_prefill(self, width: int):
        """Run what a cold admission of a ``width``-token prompt runs
        (the jitted program directly, not the dispatch helpers); returns
        its logits. Slot 0 is free and maps no page, so every row it
        scatters drops."""
        return self._prefill_install(0, np.zeros((1, width), np.int32),
                                     width, 0)

    def _warmup_prefix(self) -> dict:
        """Pre-compile every program a WARM admission can hit — the
        page gather, the CoW page copy, and one tail-prefill + masked
        scatter per prefill bucket — so the first cache hit never pays
        an XLA compile inside the latency-critical gap. All calls are
        value-neutral: nothing is mapped, every scatter row is masked
        out (limit 0), and the page-0 self-copy happens before any
        request owns it. Under int8 the fresh-scale flush program
        warms here too (all-False mask — a no-op write)."""
        out = {}
        if self.kv_dtype == "int8":
            t0 = time.perf_counter()
            pools, pt = self.caches
            self.caches = (self._reset_scales(
                pools, jnp.zeros((self.num_pages,), bool)), pt)
            out["reset_scales"] = time.perf_counter() - t0
        if not self.prefix_cache:
            return out
        from .paged_cache import (copy_page, copy_page_q, scatter_rows,
                                  scatter_rows_q)

        quant = self.kv_dtype == "int8"
        t0 = time.perf_counter()
        mini = self._gather_mini(self._mini_cache(self.max_len), [])
        pools, pt = self.caches
        new_pools = []
        for entry in pools:
            if quant:
                new_pools.append(copy_page_q(*entry, np.int32(0),
                                             np.int32(0)))
            else:
                new_pools.append(copy_page(*entry, np.int32(0),
                                           np.int32(0)))
        self.caches = (new_pools, pt)
        out["prefix_gather_copy"] = time.perf_counter() - t0
        pt_dev = self._device_tables()
        for w in (self.prefill_buckets or ()):
            t0 = time.perf_counter()
            _, mini = self._prefill_chunk(
                self.params, np.zeros((1, w), np.int32), mini,
                np.int32(0), np.int32(0), self._bank(),
                np.int32(0))
            pools, _ = self.caches
            new_pools = []
            for entry, (mk, mv) in zip(pools, mini):
                if quant:
                    new_pools.append(scatter_rows_q(
                        *entry, pt_dev, np.int32(0), np.int32(0),
                        np.int32(0), mk, mv, width=w))
                else:
                    new_pools.append(scatter_rows(
                        *entry, pt_dev, np.int32(0), np.int32(0),
                        np.int32(0), mk, mv, width=w))
            self.caches = (new_pools, pt)
            out[f"prefix_warm_{w}"] = time.perf_counter() - t0
        return out

    def _next_key_args(self, cfg):
        """Advance the engine's PRNG stream position and return the
        ``(seed, counter)`` scalars a segment program makes its key
        from (`_segment_key`): every segment, and every verify step of
        a host-mode speculative one, draws fresh sampling noise even
        when no request was admitted in between. ``cfg`` seeds the
        shared base stream (None: 0)."""
        self._segments_run += 1
        return (_u32(cfg.seed if cfg is not None else 0),
                _u32(self._segments_run))

    def _segment_fn(self, n_steps: int):
        # keyed on n_steps ALONE: sampling parameters AND the LoRA
        # adapter index ride as per-slot device vectors (_sample_rows /
        # the bank gather), so a server facing arbitrary per-request
        # GenerationConfigs — any adapter mix included — never
        # recompiles the segment
        if n_steps not in self._segment_cache:
            max_len = self.max_len

            def segment(params, last, lens, done, active, samp, bank,
                        caches, seed, counter):
                lora = (bank, samp["adapter"]) if bank else None
                samp = _live_samp(samp, active)
                key = _segment_key(seed, counter)

                def step(carry, _):
                    last, lens, done, caches, key = carry
                    live = active & ~done & (lens < max_len)
                    logits, caches, aux = self._fwd_ragged(
                        params, last[:, None], caches, lens, live,
                        lora)
                    key, sub = jax.random.split(key)
                    nxt = _sample_rows(logits[:, 0], sub, samp)
                    nxt = jnp.where(live, nxt, last)
                    lens = lens + live.astype(jnp.int32)
                    done = done | (live & (samp["eos"] >= 0)
                                   & (nxt == samp["eos"]))
                    done = done | (lens >= max_len)
                    return (nxt, lens, done, caches, key), (nxt, aux)

                (last, lens, done, caches, _), (toks, aux) = jax.lax.scan(
                    step, (last, lens, done, caches, key), None,
                    length=n_steps)
                if aux is not None:
                    aux = {k: v.sum(axis=0) for k, v in aux.items()}
                return (jnp.swapaxes(toks, 0, 1), last, lens, done,
                        caches, aux)

            self._segment_cache[n_steps] = monitor.monitored_jit(
                segment, name="cb_segment",
                owner=self._monitor_engine, donate_argnums=(7,))
        return self._segment_cache[n_steps]

    def _fwd_spec(self, params, inp, caches, lens, live, lora=None):
        """W-token verify forward at per-row offsets through the page
        table. Returns ``(logits, caches, aux)`` — ``aux`` is the
        window-write rows the int8 path hands back for the
        post-acceptance commit (:meth:`_commit_spec_rows`); None per
        layer on float pools, whose rejected rows are plain overwritten
        garbage."""
        from ..core.autograd import no_grad

        pools, pt = caches
        with substituted_state(self.model, params), no_grad():
            logits, pools, aux = \
                self.model.forward_decode_spec_paged(
                    Tensor(inp), pools, pt, lens, live,
                    **self._fwd_kwargs(lora))
        return (logits.value if isinstance(logits, Tensor) else logits,
                (pools, pt), aux)

    def _commit_spec_rows(self, caches, aux, n_acc):
        """Post-acceptance KV commit for the verify window: restore
        each layer's pre-window snapshot (touched pages + scale
        tables), then REPLAY only the accepted rows (``i < n_acc[b]``)
        sequentially through the running-absmax int8 primitive.

        The verify forward stored the whole W-window with running
        scales so in-window reads match sequential plain decode
        bitwise on acceptance-matched positions — but a rejected
        draft's absmax must never persist in a page's MONOTONIC
        running scale (the plain path never writes those rows).
        Restore-then-replay makes the persistent pool/scale state
        byte-for-byte what W single-token decode stores of the
        accepted tokens would have produced: same scale-growth events,
        same requant cascades, same rounding order — so spec-vs-plain
        token parity survives quantization. No-op on float pools
        (``aux`` is None — their rejected rows are exact-overwritten
        garbage, nothing persists)."""
        if aux is None or not any(a is not None for a in aux):
            return caches
        from ..quantization.kv import quant_store_rows

        pools, pt = caches
        new_pools = []
        for (kp, vp, ks, vs), \
                (snap_k, snap_v, snap_ks, snap_vs,
                 kh, vh, page, offs) in zip(pools, aux):
            w = page.shape[1]
            pf = page.reshape(-1)
            # un-write the window: duplicate pages in the snapshot
            # gathered identical pre-store bytes, so duplicate
            # scatter-backs are deterministic
            kp = kp.at[pf].set(snap_k, mode="drop")
            vp = vp.at[pf].set(snap_v, mode="drop")
            ks, vs = snap_ks, snap_vs
            for i in range(w):
                pg = jnp.where(jnp.asarray(i, jnp.int32) < n_acc,
                               page[:, i], kp.shape[0])
                kp, ks = quant_store_rows(kp, ks, pg, offs[:, i],
                                          kh[:, i])
                vp, vs = quant_store_rows(vp, vs, pg, offs[:, i],
                                          vh[:, i])
            new_pools.append((kp, vp, ks, vs))
        return new_pools, pt

    def _spec_step_fn(self):
        """ONE compiled speculative verify step, keyed on the engine's
        ``draft_k`` alone: every slot — speculating, plain greedy, or
        sampled — rides the same program.

        Each row's input window is ``[last, d_0..d_{k-1}]`` (W = k+1
        positions at its own offset). The forward writes all W K/V
        rows and returns logits per position; position i's greedy
        token g_i was computed from the true prefix whenever the
        drafts matched up to i, so the emitted tokens are ALWAYS
        ``g_0..g_{n_acc-1}`` — the model's own greedy continuation —
        and acceptance only decides HOW MANY are sound:

        - ``m`` = leading draft/greedy matches, capped per row at its
          ``spec_k`` (0 for plain rows → exactly one token per step);
        - ``n_acc = min(m + 1, lim - lens)`` — ``lim`` is the host's
          per-row absolute cap (budget + page coverage + max_len), so
          accepted tokens always have VALID cache writes behind them
          (writes past coverage/max_len are dropped; the positions
          whose logits they'd poison are exactly the capped-away
          ones);
        - sampled rows take ``_sample_rows`` on position 0 and force
          ``n_acc = 1`` (their spec_k is 0).

        Rejected-draft K/V past ``lens + n_acc`` is stale by the same
        convention the offline path documents: every read is
        length-masked and later writes overwrite it."""
        key_ = ("spec_step", self.draft_k)
        if key_ not in self._segment_cache:
            k = self.draft_k

            def spec_step(params, last, lens, active, samp, bank,
                          caches, seed, counter, drafts, live_in, lim):
                b = last.shape[0]
                lora = (bank, samp["adapter"]) if bank else None
                samp = _live_samp(samp, active)
                key = _segment_key(seed, counter)
                live = live_in & active & (lens < self.max_len)
                inp = jnp.concatenate([last[:, None], drafts], axis=1)
                logits, caches, aux = self._fwd_spec(
                    params, inp, caches, lens, live, lora)
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                key, sub = jax.random.split(key)
                g0 = jnp.where(samp["sample"],
                               _sample_rows(logits[:, 0], sub, samp),
                               greedy[:, 0])
                toks = jnp.concatenate([g0[:, None], greedy[:, 1:]],
                                       axis=1)            # [B, W]
                iw = jnp.arange(k, dtype=jnp.int32)[None]
                match = ((drafts == greedy[:, :k])
                         & (iw < samp["spec_k"][:, None]))
                m = jnp.sum(jnp.cumprod(match.astype(jnp.int32),
                                        axis=1), axis=1)
                n_acc = jnp.minimum(m + 1,
                                    jnp.maximum(lim - lens, 0))
                n_acc = jnp.where(live, n_acc, 0)
                caches = self._commit_spec_rows(caches, aux, n_acc)
                new_last = jnp.where(
                    n_acc > 0,
                    toks[jnp.arange(b), jnp.maximum(n_acc - 1, 0)],
                    last)
                return toks, n_acc, new_last, lens + n_acc, caches

            self._segment_cache[key_] = monitor.monitored_jit(
                spec_step, name="cb_spec_step",
                owner=self._monitor_engine, donate_argnums=(6,))
        return self._segment_cache[key_]

    def _coverage_limit(self, slot: int) -> int:
        """Absolute position this slot's cache writes are valid up to
        (its mapped pages): the spec step may only ACCEPT tokens whose
        KV writes landed in mapped pages, so a window reaching past
        grown coverage degrades to fewer accepted tokens, never to
        reads of writes the sentinel dropped."""
        return min(self.alloc.covered_tokens(slot), self.max_len)

    def _spec_segment_device_fn(self, n_steps: int):
        """ONE fused compiled speculative segment
        (``spec_mode="device"``): propose → W-position verify → accept
        → KV-write for ``n_steps`` steps inside a single ``lax.scan``,
        keyed on ``(n_steps, draft_k)`` alone (plus the engine-level
        ``spec_draft`` source, an idle-only knob). The draft source is
        the per-slot history ring — ``ngram.propose_device``, the host
        proposer's windowed twin — or, under ``spec_draft="self"``,
        the previous verify's trailing greedy tokens (EAGLE-lite; the
        ring still bootstraps each segment's first step). Budget, eos
        and page-coverage caps are device masks per step (``bud`` /
        ``cov`` are per-row vectors from pure host bookkeeping —
        coverage is FIXED across a segment because page growth only
        happens in the inter-segment gap), so the host reads back once
        per SEGMENT instead of once per verify step.

        Acceptance is byte-for-byte the host path's
        (:meth:`_spec_step_fn`): emitted tokens are always the model's
        own greedy picks ``g_0..g_{n_acc-1}``, drafts only decide HOW
        MANY — which is why device/host/plain greedy parity is
        structural, even for a context that outgrew the ring. Per-step
        tokens, acceptance counts, liveness AND the final done flags
        all ride one packed int32 output tensor, so collection is
        literally one readback."""
        key_ = ("spec_device", n_steps, self.draft_k, self.spec_draft)
        if key_ not in self._segment_cache:
            k = self.draft_k
            W = k + 1
            max_len = self.max_len
            n_max = self.ngram_max
            H = self.spec_history
            self_draft = self.spec_draft == "self"

            def spec_segment(params, last, lens, done, active, samp,
                             bank, caches, hist, hl, bud, cov, seed,
                             counter):
                b = last.shape[0]
                lora = (bank, samp["adapter"]) if bank else None
                samp = _live_samp(samp, active)
                key = _segment_key(seed, counter)
                rows = jnp.arange(b)
                iw = jnp.arange(k, dtype=jnp.int32)[None]

                def step(carry, _):
                    (last, lens, done, caches, hist, hl, drafts,
                     emitted, key) = carry
                    live = (active & ~done & (lens < max_len)
                            & (emitted < bud))
                    if not self_draft:
                        drafts = propose_device(hist, hl, k, n_max)
                    inp = jnp.concatenate([last[:, None], drafts],
                                          axis=1)
                    logits, caches, aux = self._fwd_spec(
                        params, inp, caches, lens, live, lora)
                    greedy = jnp.argmax(logits, axis=-1).astype(
                        jnp.int32)
                    key, sub = jax.random.split(key)
                    g0 = jnp.where(
                        samp["sample"],
                        _sample_rows(logits[:, 0], sub, samp),
                        greedy[:, 0])
                    toks = jnp.concatenate([g0[:, None], greedy[:, 1:]],
                                           axis=1)          # [B, W]
                    match = ((drafts == greedy[:, :k])
                             & (iw < samp["spec_k"][:, None]))
                    m = jnp.sum(jnp.cumprod(match.astype(jnp.int32),
                                            axis=1), axis=1)
                    # per-row absolute cap, fused: remaining budget
                    # (bud - emitted) + page coverage; cov is already
                    # min(coverage, max_len) host-side
                    lim = jnp.minimum(
                        lens + jnp.maximum(bud - emitted, 0), cov)
                    n_acc = jnp.minimum(m + 1,
                                        jnp.maximum(lim - lens, 0))
                    n_acc = jnp.where(live, n_acc, 0)
                    # eos mid-accepted-draft: truncate at the FIRST
                    # accepted eos and freeze the row — the host
                    # loop's cut, as a device mask
                    hit = ((samp["eos"][:, None] >= 0)
                           & (toks == samp["eos"][:, None])
                           & (jnp.arange(W)[None] < n_acc[:, None]))
                    any_hit = hit.any(axis=1)
                    n_acc = jnp.where(
                        any_hit,
                        jnp.argmax(hit, axis=1).astype(jnp.int32) + 1,
                        n_acc)
                    done = done | any_hit
                    # int8 paged pools: running-absmax commit of the
                    # FINAL accepted prefix only (post-eos-truncation)
                    # — rejected rows stay scale-frozen
                    caches = self._commit_spec_rows(caches, aux, n_acc)
                    new_last = jnp.where(
                        n_acc > 0,
                        toks[rows, jnp.maximum(n_acc - 1, 0)], last)
                    lens = lens + n_acc
                    done = done | (lens >= max_len)
                    emitted = emitted + n_acc
                    # history-ring append of the VARIABLE per-row
                    # accepted count: masked scatter into an H+W
                    # extension (out-of-range columns drop), then a
                    # per-row gather shift keeps the last H tokens
                    ext = jnp.concatenate(
                        [hist, jnp.zeros((b, W), jnp.int32)], axis=1)
                    cols = hl[:, None] + jnp.arange(W)[None]
                    cols = jnp.where(
                        jnp.arange(W)[None] < n_acc[:, None], cols,
                        H + W)
                    ext = ext.at[rows[:, None], cols].set(toks,
                                                          mode="drop")
                    shift = jnp.maximum(hl + n_acc - H, 0)
                    hist = jnp.take_along_axis(
                        ext, jnp.arange(H)[None] + shift[:, None],
                        axis=1)
                    hl = jnp.minimum(hl + n_acc, H)
                    if self_draft:
                        # next drafts = this verify's trailing greedy
                        # tokens past the accepted prefix (clamped to
                        # the window) — position lens+n_acc's
                        # continuation guess came from THIS forward
                        nxt = jnp.take_along_axis(
                            toks, jnp.clip(n_acc[:, None] + iw, 0, k),
                            axis=1)
                        drafts = jnp.where(live[:, None], nxt, drafts)
                    ys = jnp.concatenate(
                        [toks, n_acc[:, None],
                         live.astype(jnp.int32)[:, None]], axis=1)
                    return ((new_last, lens, done, caches, hist, hl,
                             drafts, emitted, key), ys)

                drafts0 = (propose_device(hist, hl, k, n_max)
                           if self_draft
                           else jnp.zeros((b, k), jnp.int32))
                carry = (last, lens, done, caches, hist, hl, drafts0,
                         jnp.zeros((b,), jnp.int32), key)
                (last, lens, done, caches, hist, hl, _, _, _), seg = \
                    jax.lax.scan(step, carry, None, length=n_steps)
                # final done flags ride the SAME packed tensor as the
                # per-step tokens: collection is one readback
                tail = jnp.zeros((1, b, W + 2),
                                 jnp.int32).at[0, :, 0].set(
                    done.astype(jnp.int32))
                return (jnp.concatenate([seg, tail], axis=0), last,
                        lens, done, hist, hl, caches)

            self._segment_cache[key_] = monitor.monitored_jit(
                spec_segment, name="cb_spec_device_segment",
                owner=self._monitor_engine, donate_argnums=(7,))
        return self._segment_cache[key_]

    # lint: hot-path
    def _decode_segment_spec_device(self, n_steps: int, cfg, sp,
                                    on_dispatch):
        """Device-resident speculative decode segment: ONE dispatch of
        the fused :meth:`_spec_segment_device_fn` program, then ONE
        readback for collection — no per-verify-step host round-trip
        (``spec_stats()["host_syncs"]`` stays 0 in this mode; that
        round-trip is exactly what ``spec_mode="host"`` pays).

        The per-row budget/coverage caps ship as fixed-shape device
        vectors built from pure host bookkeeping — never a device
        pull, never a recompile — and the segment's speculative
        accounting (proposed/accepted/slot_steps) is derived ONCE from
        the packed per-step tallies the program returns, preserving
        the ``emitted == slot_steps + accepted`` identity across both
        modes."""
        t0 = time.perf_counter()
        k = self.draft_k
        W = k + 1
        fn = self._spec_segment_device_fn(n_steps)
        # the plain segment's three phases, a span each
        with trace.span("engine.dispatch") as dsp:
            mb = self.max_batch
            bud = np.zeros((mb,), np.int32)
            cov = np.zeros((mb,), np.int32)
            for slot, rid in self._slot_req.items():
                bud[slot] = max(self._budget[rid], 0)
                cov[slot] = min(self._coverage_limit(slot), self.max_len)
            # fresh noise per segment, like the plain scan (the program
            # splits per step; sampled rows fold their own seed in)
            args = (self.params, self.last, self.lens, self.done_dev,
                    self._active_mask(), self.samp, self._bank(),
                    self.caches, self.hist, self.hist_len, bud, cov,
                    *self._next_key_args(cfg))
            (seg, self.last, self.lens, self.done_dev, self.hist,
             self.hist_len, self.caches) = fn(*args)
            if trace.enabled():
                dsp.set(args=len(jax.tree_util.tree_leaves(args)))
        if on_dispatch is not None:
            on_dispatch()
        with trace.span("engine.wait"):
            # lint: allow-host-sync(collection itself: ONE readback per
            # FUSED segment — n_steps x (tokens, acceptance, liveness)
            # plus the final done flags ride one packed tensor; this is
            # the plain path's once-per-segment collect pull, not the
            # host-mode per-verify-step sync)
            seg = np.asarray(seg)
        with trace.span("engine.collect"):
            done_h = seg[-1, :, 0].astype(bool)
            total = proposed = accepted = slot_steps = 0
            steps_live = np.zeros((n_steps,), bool)
            for slot, rid in list(self._slot_req.items()):
                live_s = seg[:n_steps, slot, W + 1].astype(bool)
                acc_s = seg[:n_steps, slot, W]
                sk = self._spec_k_of(rid)
                seq = []
                for s in range(n_steps):
                    if not live_s[s]:
                        continue
                    steps_live[s] = True
                    slot_steps += 1
                    proposed += sk
                    na = int(acc_s[s])
                    seq.extend(int(t) for t in seg[s, slot, :na])
                    accepted += max(na - 1, 0)
                self._tokens[rid].extend(seq)
                self._budget[rid] -= len(seq)
                total += len(seq)
                if self._budget[rid] <= 0 or bool(done_h[slot]):
                    self._retire(slot)
            # forwards counts verify steps that served at least one live
            # row — the host loop's early-exit semantics; the fused
            # program's trailing all-dead steps are masked no-ops
            forwards = int(steps_live.sum())
            self._spec_totals["proposed"] += proposed
            self._spec_totals["accepted"] += accepted
            self._spec_totals["forwards"] += forwards
            self._spec_totals["slot_steps"] += slot_steps
            self._spec_totals["emitted"] += total
            if monitor.enabled():
                dt = time.perf_counter() - t0
                monitor.counter(
                    "paddle_tpu_generated_tokens_total",
                    "tokens generated by the continuous-batching engines "
                    "(admission first-token + decode segments)").inc(total)
                self._tokens_per_sec_gauge().labels(
                    engine=self._monitor_engine).set(
                    total / dt if dt > 0 else 0.0)
                if proposed:
                    c = self._spec_tokens_counter()
                    c.labels(engine=self._monitor_engine,
                             outcome="proposed").inc(proposed)
                    c.labels(engine=self._monitor_engine,
                             outcome="accepted").inc(accepted)
            if trace.enabled():
                sp.set(mode="device", forwards=forwards,
                       proposed=proposed, accepted=accepted,
                       emitted=total, host_syncs=0)
        return len(self._slot_req)

    @staticmethod
    def _spec_tokens_counter():
        return monitor.counter(
            "paddle_tpu_spec_draft_tokens_total",
            "speculative-decode draft tokens by engine and outcome "
            "(proposed = host n-gram drafts sent to verification; "
            "accepted = drafts the model's own greedy continuation "
            "confirmed — acceptance rate is accepted/proposed)",
            ("engine", "outcome"))

    def spec_stats(self) -> dict:
        """Engine-lifetime speculative-decoding accounting, host-side
        and monitor-independent: proposed/accepted draft tokens,
        verify forwards, slot participations, tokens emitted (spec
        segments only — plain segments keep their 1/step cadence).

        ``tokens_per_forward`` is PER-SLOT — ``emitted / slot_steps``,
        one slot's tokens per verify forward it rode (1.0 = plain
        cadence; the batch-level tokens/forward would conflate batch
        size with speculation). At B=1 it reduces to the offline
        path's ``tokens/forwards`` metric.

        ``host_syncs`` counts blocking per-verify-step device→host
        readbacks (``spec_mode="host"``'s documented price — one per
        verify forward); ``host_syncs_per_token`` normalizes by
        emitted tokens and is structurally 0.0 under
        ``spec_mode="device"``, where the fused segment reads back
        once per segment like the plain path."""
        t = dict(self._spec_totals)
        t["acceptance_rate"] = (t["accepted"] / t["proposed"]
                                if t["proposed"] else 0.0)
        t["tokens_per_forward"] = (t["emitted"] / t["slot_steps"]
                                   if t["slot_steps"] else 0.0)
        t["host_syncs_per_token"] = (t["host_syncs"] / t["emitted"]
                                     if t["emitted"] else 0.0)
        return t

    # lint: hot-path
    def _decode_segment_spec(self, n_steps: int, cfg, sp, on_dispatch):
        """Speculative decode segment: ``n_steps`` verify steps of the
        ONE compiled ``_spec_step_fn`` program, with the host loop in
        between — propose fresh drafts from each slot's proposer,
        read back acceptance, stream/cut per slot (budget, eos)
        exactly like the plain path's collection does.

        The host round-trip per verify step is the price of host-side
        proposers (``spec_mode="host"``; ``"device"`` fuses the whole
        segment and pays NO per-step sync — see
        :meth:`_decode_segment_spec_device`); each forward yields up
        to ``spec_k + 1`` tokens for accepting rows, which is the
        trade this path exists to make (decode is HBM-bound on TPU, so
        accepted tokens/forward ≈ wall speedup there). Plain and
        sampled slots ride along at one token per step — a mixed batch
        never splits programs."""
        t0 = time.perf_counter()
        k = self.draft_k
        mb = self.max_batch
        fn = self._spec_step_fn()
        # lint: allow-host-sync(spec_mode="host" only — one lens/done
        # pull per SEGMENT: the host proposers need real lengths to
        # place drafts; tracked incrementally below, not re-pulled per
        # step. Device mode ships no per-row pulls at all.)
        lens_h, done_h = jax.device_get((self.lens, self.done_dev))
        lens_h = lens_h.copy()
        active = self._active_mask()
        emitted = {rid: [] for rid in self._slot_req.values()}
        finished = set()
        forwards = 0
        proposed = accepted = slot_steps = 0
        for _ in range(n_steps):
            drafts = np.zeros((mb, k), np.int32)
            live = np.zeros((mb,), bool)
            lim = np.zeros((mb,), np.int32)
            for slot, rid in self._slot_req.items():
                if rid in finished or bool(done_h[slot]):
                    continue
                rem = self._budget[rid] - len(emitted[rid])
                if rem <= 0 or int(lens_h[slot]) >= self.max_len:
                    continue
                live[slot] = True
                lim[slot] = min(int(lens_h[slot]) + rem,
                                self._coverage_limit(slot),
                                self.max_len)
                prop = self._spec.get(rid)
                if prop is not None:
                    d = prop.propose()
                    drafts[slot, :len(d)] = d
                    proposed += prop.k
            if not live.any():
                break
            slot_steps += int(live.sum())
            # fresh noise per verify step, like the plain scan's
            # per-step key split (sampled rows fold their own seed in)
            toks, n_acc, self.last, self.lens, self.caches = fn(
                self.params, self.last, self.lens, active,
                self.samp, self._bank(), self.caches,
                *self._next_key_args(cfg), drafts, live, lim)
            forwards += 1
            if forwards == 1 and on_dispatch is not None:
                on_dispatch()
            # lint: allow-host-sync(the spec_mode="host" branch's
            # per-verify-step readback — host n-gram proposers must
            # see acceptance before drafting again. This is exactly
            # the sync spec_mode="device" eliminates; spec_stats'
            # host_syncs counts it, and it reads 0 in device mode.)
            toks_h, acc_h = jax.device_get((toks, n_acc))
            for slot, rid in self._slot_req.items():
                if not live[slot]:
                    continue
                na = int(acc_h[slot])
                lens_h[slot] += na
                seq = toks_h[slot, :na].tolist()
                rcfg = self._cfg[rid]
                if (rcfg.eos_token_id is not None
                        and rcfg.eos_token_id in seq):
                    # eos mid-accepted-draft: truncate host-side and
                    # finish the request — the stale device tail past
                    # eos dies with the slot's retirement
                    seq = seq[:seq.index(rcfg.eos_token_id) + 1]
                    finished.add(rid)
                emitted[rid].extend(int(t) for t in seq)
                prop = self._spec.get(rid)
                if prop is not None:
                    prop.extend(seq)
                    acc = max(len(seq) - 1, 0)
                    prop.accepted += acc
                    accepted += acc
        # collection: mirror the plain path's budget/eos retirement
        total = 0
        for slot, rid in list(self._slot_req.items()):
            seq = emitted.get(rid, [])
            self._tokens[rid].extend(seq)
            self._budget[rid] -= len(seq)
            total += len(seq)
            if (self._budget[rid] <= 0 or rid in finished
                    or bool(done_h[slot])):
                self._retire(slot)
        self._spec_totals["proposed"] += proposed
        self._spec_totals["accepted"] += accepted
        self._spec_totals["forwards"] += forwards
        self._spec_totals["slot_steps"] += slot_steps
        self._spec_totals["emitted"] += total
        # one blocking device→host readback per verify forward — the
        # host-mode price serve_bench's host-syncs-per-token record
        # surfaces (structurally 0 on the device-mode path)
        self._spec_totals["host_syncs"] += forwards
        if monitor.enabled():
            dt = time.perf_counter() - t0
            monitor.counter(
                "paddle_tpu_generated_tokens_total",
                "tokens generated by the continuous-batching engines "
                "(admission first-token + decode segments)").inc(total)
            self._tokens_per_sec_gauge().labels(
                engine=self._monitor_engine).set(
                total / dt if dt > 0 else 0.0)
            if proposed:
                c = self._spec_tokens_counter()
                c.labels(engine=self._monitor_engine,
                         outcome="proposed").inc(proposed)
                # inc(0) still creates the series: the acceptance rate
                # stays derivable (accepted/proposed) even at 0
                c.labels(engine=self._monitor_engine,
                         outcome="accepted").inc(accepted)
        if trace.enabled():
            # per-segment speculative accounting: acceptance explains
            # why a segment's emitted count beat (or matched) its
            # verify-forward count
            sp.set(mode="host", forwards=forwards, proposed=proposed,
                   accepted=accepted, emitted=total,
                   host_syncs=forwards)
        return len(self._slot_req)

    # lint: hot-path
    def decode_segment(self, n_steps: int,
                       cfg: Optional[GenerationConfig] = None, *,
                       on_dispatch: Optional[Callable[[], None]] = None):
        """Run ``n_steps`` ragged decode steps over the current slots;
        collect per-request tokens and retire finished requests. Returns
        the number of still-active requests.

        ``on_dispatch``, if given, runs once the segment's program has
        been handed to the device and before the host waits for it (in
        host-mode speculation, after the first verify step's call): the
        caller's own host work then overlaps the device's. Not called
        when no program runs (no live slot).

        Each request decodes under ITS OWN GenerationConfig (installed
        at ``add_request``) — including its seed, which every sampling
        step folds into the per-row noise key, so a request's sampled
        trajectory is a function of its own config, not of its
        batchmates. ``cfg`` is optional and only seeds the segment's
        SHARED base stream (back-compat with the one-config ``serve()``
        driver — omitted, the base stream is seeded from 0)."""
        if not self._slot_req:
            return 0
        if self.admission_mode == "optimistic":
            # final guard: a driver that skipped pressure relief must
            # fail LOUDLY here, not let write_tokens silently drop KV
            # writes past the mapped range and corrupt the request's
            # decode. When the scheduler's gap already ran a clean
            # grow_for_segment(n_steps) (stamp matches, slot set
            # unchanged since), the re-check — two blocking device
            # fetches + an O(active) allocator pass — is skipped; the
            # stamp is single-shot because this segment advances lens
            short = ([] if self._growth_stamp == n_steps
                     else self.grow_for_segment(n_steps))
            self._growth_stamp = None
            self._gap_sync = None    # the segment advances lens/done
            if short:
                raise PagePoolExhausted(
                    short,
                    f"page pool exhausted in the inter-segment gap: "
                    f"requests {short} cannot grow for the next "
                    f"{n_steps}-step segment "
                    f"({self.alloc.available_pages} pages reclaimable) "
                    f"— preempt victims (preempt_request) or grow "
                    f"num_pages")
        # int8: pages the gap claimed (growth, reserves) get their
        # scale rows floored before this segment's quantized writes
        self._flush_fresh_scales()
        # reserved mode: admission reserved every running request's
        # worst case, so no growth can fail — just ship the table
        if self.alloc.debug:
            self.alloc.check()
            if self.kv_dtype == "int8":
                # device half of the scale invariants: every live
                # page's scales finite and positive (layer 0 stands
                # for all layers — one program writes them all)
                pools, _ = self.caches
                self.alloc.check_scales(pools[0][2], pools[0][3])
            # write_tokens drops out-of-mapping writes SILENTLY (one
            # compiled program) and a forgotten copy-on-write would
            # mutate a shared page other requests read — both surface
            # as wrong tokens far downstream. Under debug_pages the gap
            # re-asserts, per live slot, that the live length is inside
            # the mapped pages and the imminent write lands in a
            # private page.
            # lint: allow-host-sync(debug_pages-only invariant check —
            # never on the production path; the pull is the price of
            # validating coverage before a silent-drop write)
            lens = np.asarray(self.lens)
            # lint: allow-host-sync(same debug_pages-only pull)
            done = np.asarray(self.done_dev)
            for slot, rid in self._slot_req.items():
                if bool(done[slot]):
                    continue
                # a speculating row's imminent writes span its whole
                # draft window, not just the next position — the
                # shared-page (missing-CoW) net must cover all of it
                self.alloc.check_coverage(
                    slot, int(lens[slot]),
                    write_ahead=1 + self._spec_k_of(rid))
        pools, _ = self.caches
        # ``changed``: the device does not hold this table yet (the last
        # upload, a segment's or an admission's, sent another); reckoned
        # before the span opens, which times the upload alone
        changed = (trace.enabled()
                   and self._tables_bytes() != self._tables_sent)
        with trace.span("engine.tables") as tsp:
            self.caches = (pools, self._device_tables())
            if trace.enabled():
                tsp.set(changed=int(changed))
        run, phase = self._decode_segment_plain, "engine.segment"
        if self._spec:
            # at least one live slot is speculating: the whole batch
            # rides ONE widened verify program (plain/sampled rows at
            # 1 token/step). Device mode fuses all n_steps into one
            # compiled segment; host mode drives the per-step loop
            # its host proposers need.
            run = (self._decode_segment_spec_device
                   if self.spec_mode == "device"
                   else self._decode_segment_spec)
            phase = "engine.spec_segment"
        sp = trace.NULL_SPAN
        if trace.enabled():
            # the counters a reader needs to reckon the KV the segment
            # must read, from host bookkeeping alone (no device pull):
            # live rows, and their prompt + generated tokens now
            sp = trace.span(
                phase, engine=self._monitor_engine, steps=n_steps,
                rows=len(self._slot_req),
                ctx_tokens=sum(self._plen[rid] + len(self._tokens[rid])
                               for rid in self._slot_req.values()))
        with sp:
            return run(n_steps, cfg, sp, on_dispatch)

    def _kv_pages_copied(self, ctx):
        """Pages the first step's ``paged_decode`` calls copy, K and V
        each, summed over layers and the live rows of contexts ``ctx``,
        by the kernel's own block arithmetic
        (``paged_attention.pages_copied``). A layer whose first pool is
        ``[pages, page, Hkv, D]`` calls the kernel; latent rows
        (``[pages, page, row]``) and a row's state call none. A window
        layer is handed its lengths counted from the window's first
        page."""
        from ..ops.paged_attention import pages_copied

        pools, _ = self.caches
        win = self._ring["window"] if self._ring is not None else None
        n = 0
        for i, entry in enumerate(pools):
            if ((self._state_layers is not None and self._state_layers[i])
                    or entry[0].ndim != 4):
                continue
            ps, hkv = entry[0].shape[1], entry[0].shape[2] // self.tp_degree
            if win is not None and self._ring["window_layers"][i]:
                lens = [c - max(c - win, 0) // ps * ps for c in ctx]
                n += pages_copied(lens, ps, hkv, window=win)
            else:
                n += pages_copied(ctx, ps, hkv)
        return n

    # lint: hot-path
    def _decode_segment_plain(self, n_steps: int, cfg, sp, on_dispatch):
        if trace.enabled():
            ctx = [self._plen[rid] + len(self._tokens[rid])
                   for rid in self._slot_req.values()]
            sp.set(kv_pages_copied=self._kv_pages_copied(ctx))
            if self._ring is not None:
                # what the two geometries hold at the segment's start,
                # and the tokens a window layer's attention reads (a
                # full layer's: ctx_tokens)
                w = self._ring["window"]
                held = [self.alloc.held_pages(slot)
                        for slot in self._slot_req]
                sp.set(ctx_tokens_window=sum(min(c, w) for c in ctx),
                       pages_full=sum(h[0] for h in held),
                       pages_window=sum(h[1] for h in held))
            else:
                # the pages the live rows' contexts span at the
                # segment's start (what ``paged_decode`` walks), of the
                # table's
                sp.set(pages_live=sum(-(-c // self.page_size) for c in ctx),
                       pages_table=self.alloc.page_table.size)
        t0 = time.perf_counter()
        fn = self._segment_fn(n_steps)
        # the host's three phases of a segment, a span each: handing
        # the program over, waiting for it, the bookkeeping after it
        with trace.span("engine.dispatch") as dsp:
            args = (self.params, self.last, self.lens, self.done_dev,
                    self._active_mask(), self.samp, self._bank(),
                    self.caches, *self._next_key_args(cfg))
            toks, self.last, self.lens, self.done_dev, self.caches, aux = \
                fn(*args)
            if trace.enabled():
                dsp.set(args=len(jax.tree_util.tree_leaves(args)))
        if on_dispatch is not None:
            on_dispatch()
        with trace.span("engine.wait"):
            # lint: allow-host-sync(collection itself: ONE readback per
            # n_steps-step segment — tokens must reach handles/streams;
            # the done flags and, traced, the step's own counters
            # (routing) come in the same transfer; lens stays on the
            # device)
            toks, done, aux = jax.device_get(
                (toks, self.done_dev, aux if trace.enabled() else None))
        if aux is not None:
            sp.set(**{k: int(v) for k, v in aux.items()})
        with trace.span("engine.collect"):
            emitted = 0
            for slot, rid in list(self._slot_req.items()):
                rcfg = self._cfg[rid]
                take = min(self._budget[rid], n_steps)
                seq = toks[slot, :take].tolist()
                if (rcfg.eos_token_id is not None
                        and rcfg.eos_token_id in seq):
                    seq = seq[:seq.index(rcfg.eos_token_id) + 1]
                self._tokens[rid].extend(int(t) for t in seq)
                self._budget[rid] -= len(seq)
                emitted += len(seq)
                if (self._budget[rid] <= 0 or bool(done[slot])
                        or len(seq) < take):
                    self._retire(slot)
            if monitor.enabled():
                dt = time.perf_counter() - t0
                monitor.counter(
                    "paddle_tpu_generated_tokens_total",
                    "tokens generated by the continuous-batching engines "
                    "(admission first-token + decode segments)").inc(
                    emitted)
                self._tokens_per_sec_gauge().labels(
                    engine=self._monitor_engine).set(
                    emitted / dt if dt > 0 else 0.0)
            if trace.enabled():
                sp.set(emitted=emitted)
        return len(self._slot_req)

    @staticmethod
    def _tokens_per_sec_gauge():
        return monitor.gauge(
            "paddle_tpu_decode_tokens_per_sec",
            "emitted tokens / wall time of the latest decode "
            "segment (includes host collect), per engine", ("engine",))

    def close(self):
        """Retire this engine's per-instance monitor series (idempotent;
        a dropped engine must not export its last tokens/sec forever)."""
        try:
            self._tokens_per_sec_gauge().remove(
                engine=self._monitor_engine)
        except Exception:
            pass
        # per-engine prefill series retire with the engine too, else a
        # dropped engine's label values accumulate in the registry (the
        # bucket dimension is open-ended, so retire by engine label)
        for name in ("paddle_tpu_prefill_requests_total",
                     "paddle_tpu_prefill_chunks_total",
                     "paddle_tpu_prefill_warmup_seconds",
                     "paddle_tpu_spec_draft_tokens_total"):
            try:
                monitor.remove_series(name, engine=self._monitor_engine)
            except Exception:
                pass
        # the program ledger rows this engine owned (prefill/chunk/
        # admit/segment/spec/quant/lora_install programs) and their
        # {program=...} series retire with it — same contract as the
        # per-engine series above
        try:
            from ..monitor import ledger

            ledger.release(self._monitor_engine)
        except Exception:
            pass
        reg = getattr(self, "adapters", None)   # __del__-safe: a
        if reg is not None:                     # half-built engine has
            reg.close()                         # no registry attr yet
        alloc = getattr(self, "alloc", None)
        if alloc is not None:
            alloc.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def collect_finished(self):
        out, self._finished = self._finished, {}
        return out

    # -- optimistic-mode memory pressure (host-side, between segments) -------
    def grow_for_segment(self, n_steps: int):  # lint: hot-path
        """Grow every live slot's page mapping to cover the coming
        ``n_steps``-step decode segment (optimistic mode; a no-op in
        reserved mode, where admission pre-claimed the worst case).
        Returns the request ids whose growth could NOT be satisfied —
        the pool is dry and the caller must preempt victims (or accept
        :class:`PagePoolExhausted` from ``decode_segment``).

        OLDEST request first (ascending rid — admission order), so
        pressure always lands on the youngest work: combined with a
        scheduler that never preempts the oldest survivor, the head of
        the line always makes forward progress and pressure can never
        deadlock the loop. A row's target is capped by its remaining
        budget: a segment emits at most ``min(n_steps, budget)`` kept
        tokens, whose last cache write lands at position
        ``len + min(n_steps, budget) - 1`` — device steps past the
        budget write into (and read from) uncovered positions, but
        every token they produce is discarded host-side at collection,
        so capping is safe and saves pages. NO partial growth: a slot
        either covers the full target or joins the short list —
        partially covered steps would emit garbage tokens the host
        KEEPS."""
        if self.admission_mode != "optimistic" or not self._slot_req:
            return []
        if self._gap_sync is None:
            # lint: allow-host-sync(ONE cached lens/done pull per gap —
            # growth decisions need real lengths; decode_segment's
            # re-check reuses this exact pull via _gap_sync)
            self._gap_sync = (np.asarray(self.lens),
                              np.asarray(self.done_dev))
        lens, done = self._gap_sync
        short = []
        for slot, rid in sorted(self._slot_req.items(),
                                key=lambda kv: kv[1]):
            if bool(done[slot]):
                continue       # frozen rows never write
            # a SPECULATING row can accept up to spec_k+1 tokens per
            # verify step, so its per-segment growth target scales by
            # its window width (still budget-capped: acceptance never
            # outruns the tokens the host will keep). Draft-scratch
            # writes past the target drop harmlessly — the spec step
            # caps acceptance at the grown coverage.
            w = self._spec_k_of(rid) + 1
            target = min(int(lens[slot])
                         + min(n_steps * w, self._budget[rid]),
                         self.max_len)
            if self.alloc.can_fit(slot, target):
                self.alloc.ensure(slot, target)
            else:
                short.append(rid)
        # a clean pass covers the coming segment: decode_segment(n_steps)
        # may skip its re-check until the slot set changes (_register) or
        # the segment runs (lens advance)
        self._growth_stamp = n_steps if not short else None
        if short and trace.enabled():
            # ENGINE rids (not serving trace keys): the pool could not
            # cover these rows' growth — the preemptions that follow in
            # the flight ring are this event's consequence
            trace.event("engine.grow_short",
                        engine=self._monitor_engine,
                        engine_rids=tuple(short),
                        free_pages=self.alloc.free_pages)
        return short

    def serve(self, prompts, cfg: Optional[GenerationConfig] = None,
              segment_steps: int = 8):
        """Continuous-batching driver: admits requests as slots free up,
        decoding in fixed segments. Returns generated ids (prompt NOT
        included) in submission order.

        Under an optimistic-mode paged engine this driver handles KV
        memory pressure the same way the serving scheduler does: each
        inter-segment gap grows live mappings and, when the pool is
        dry, preempts the YOUNGEST of its own requests (never the
        oldest — forward progress) and re-queues ``prompt + generated``
        with the budget reduced, so a tight pool degrades to lower
        concurrency instead of raising away completed results (greedy
        resume is bitwise-identical to an unpreempted run). Only a
        request the pool cannot hold even alone still raises
        :class:`PagePoolExhausted` — the same workload would fail
        reserved-mode admission too."""
        cfg = cfg or GenerationConfig()
        pending = list(enumerate(prompts))
        cfgs = {}      # idx -> replay cfg (budget reduced); else ``cfg``
        prefix = {}    # idx -> tokens emitted before its preemption(s)
        order = {}
        results = {}
        foreign = {}   # requests admitted outside this serve() call

        def _settle(idx, seq):
            pre = prefix.pop(idx, None)
            results[idx] = (seq if pre is None else np.concatenate(
                [np.asarray(pre, np.int32), np.asarray(seq, np.int32)]))

        while len(results) < len(prompts):
            while pending and self._free:
                idx0, p0 = pending[0]
                if not self._can_admit(_prompt_len(p0),
                                       cfgs.get(idx0, cfg)):
                    if not self._slot_req:
                        # nothing active to drain: the request can NEVER
                        # fit — let add_request raise its loud error
                        idx, p = pending.pop(0)
                        order[self.add_request(
                            p, cfgs.get(idx, cfg))] = idx
                    break  # transient: defer to the next segment gap
                idx, p = pending.pop(0)
                order[self.add_request(p, cfgs.get(idx, cfg))] = idx
            # inter-segment gap: memory-pressure relief (see docstring)
            while True:
                short = self.grow_for_segment(segment_steps)
                if not short:
                    break
                ours = sorted(r for r in self._slot_req.values()
                              if r in order)
                if len(ours) < 2:
                    # our only (oldest-surviving) request, or a foreign
                    # row we must not touch: decode_segment's guard
                    # raises the loud typed error if it stays short
                    break
                toks = self.preempt_request(ours[-1])   # youngest
                idx = order.pop(ours[-1])
                pre = list(prefix.pop(idx, [])) + [int(t) for t in toks]
                # budget against the ORIGINAL cfg: ``pre`` is the full
                # emitted history, so measuring it against an earlier
                # replay's already-reduced max_new_tokens would
                # double-subtract the first preemption's prefix and
                # silently truncate a twice-preempted request
                remaining = cfg.max_new_tokens - len(pre)
                if remaining < 1 or (cfg.eos_token_id is not None
                                     and pre
                                     and pre[-1] == cfg.eos_token_id):
                    results[idx] = np.asarray(pre, np.int32)
                    continue    # already finished: nothing to replay
                prefix[idx] = pre
                kw = dict(vars(cfg))
                kw["max_new_tokens"] = remaining
                cfgs[idx] = GenerationConfig(**kw)
                # replays re-admit BEFORE new work (they held capacity
                # when pressure hit); greedy re-prefill of the same
                # prefix is bitwise-identical to the uninterrupted run
                pending.insert(0, (idx, np.concatenate(
                    [np.asarray(prompts[idx], np.int32).reshape(-1),
                     np.asarray(pre, np.int32)])))
            self.decode_segment(segment_steps, cfg)
            for rid, seq in self.collect_finished().items():
                if rid in order:
                    _settle(order.pop(rid), seq)
                else:
                    foreign[rid] = seq
        # foreign requests finished during our segments stay collectable
        self._finished.update(foreign)
        return [results[i] for i in range(len(prompts))]
