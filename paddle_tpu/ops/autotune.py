"""Kernel autotune cache (reference analog: paddle/phi/kernels/autotune/
— cache.h AlgorithmsCache + auto_tune_base.h tuner that times candidate
kernels and caches the winner per input signature).

TPU-native shape: Pallas kernels are compiled per block config, so the
tunable is the BLOCK SIZE tuple, not a cuDNN algo id. Because kernels are
normally called inside ``jit`` traces (where timing is impossible), tuning
runs eagerly and out-of-band — ``tune(...)`` benchmarks candidates on the
real device once, and the winning config is consulted at trace time from a
process-wide (optionally persisted) cache.

    from paddle_tpu.ops import autotune
    autotune.tune("flash_attention", (8, 8, 2048, 128), candidates=...,
                  runner=...)         # or autotune.tune_flash(...)
    # subsequent flash_attention calls pick up the tuned blocks

``FLAGS_use_autotune`` (framework.flags) gates lookup. The table picks
block sizes and so changes the compiled program, which must be built
from what git would commit: the ONLY file read is the in-repo
``.autotune_cache.json`` (none is committed today, so kernels run their
defaults), and nothing is written unless ``set_cache_path`` named a file
(``experiments/exp_autotune_sweep.py`` names the in-repo one, on a TPU).
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, Iterable, Optional, Sequence, Tuple

__all__ = ["AutoTuneCache", "get_cache", "lookup", "record", "tune",
           "tune_flash", "tune_decode_mha", "decode_signature",
           "set_cache_path"]

def _repo_cache_path() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".autotune_cache.json")


class AutoTuneCache:
    """(op, signature) -> winning config dict, with hit/miss counters
    (reference cache.h keeps the same stats)."""

    def __init__(self, path: Optional[str] = None):
        self._table: Dict[str, dict] = {}
        self._hits = 0
        self._misses = 0
        self._path = path

    @staticmethod
    def _key(op: str, signature: Sequence) -> str:
        return f"{op}:{','.join(str(s) for s in signature)}"

    def lookup(self, op: str, signature: Sequence) -> Optional[dict]:
        rec = self._table.get(self._key(op, signature))
        if rec is None:
            self._misses += 1
            return None
        self._hits += 1
        return rec

    def record(self, op: str, signature: Sequence, config: dict):
        self._table[self._key(op, signature)] = dict(config)

    @property
    def stats(self):
        return {"hits": self._hits, "misses": self._misses,
                "size": len(self._table)}

    # -- persistence -------------------------------------------------------
    def save(self, path: Optional[str] = None):
        """Atomic write (temp + rename): a sweep trial can be group-killed
        mid-save, and a truncated committed cache would poison every later
        trial's merge-load."""
        path = path or self._path
        if path is None:
            raise ValueError("AutoTuneCache.save: no path given and none "
                             "set (set_cache_path)")
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self._table, f, indent=1, sort_keys=True)
        os.replace(tmp, path)

    def load(self, path: Optional[str] = None) -> bool:
        path = path or self._path
        if path is None or not os.path.exists(path):
            return False
        with open(path) as f:
            self._table.update(json.load(f))
        return True


_GLOBAL = AutoTuneCache()
_loaded = [False]


def get_cache() -> AutoTuneCache:
    if not _loaded[0]:
        _loaded[0] = True
        # the in-repo table and nothing else: a file outside the
        # checkout must not change what the checkout compiles to
        _GLOBAL.load(_repo_cache_path())
    return _GLOBAL


def set_cache_path(path: str):
    _GLOBAL._path = path


def _enabled() -> bool:
    from ..framework.flags import get_flags

    return bool(get_flags("FLAGS_use_autotune").get("FLAGS_use_autotune",
                                                    True))


def lookup(op: str, signature: Sequence) -> Optional[dict]:
    if not _enabled():
        return None
    return get_cache().lookup(op, signature)


def record(op: str, signature: Sequence, config: dict):
    get_cache().record(op, signature, config)


def tune(op: str, signature: Sequence, candidates: Iterable[dict],
         runner: Callable[[dict], None], warmup: int = 1, iters: int = 3,
         save: bool = True) -> dict:
    """Time ``runner(config)`` for every candidate, record the winner.

    ``runner`` must execute the kernel to completion (end on a host
    readback or ``block_until_ready``): dispatch is asynchronous.
    ``save`` persists the table only where ``set_cache_path`` named a
    file.
    """
    best_cfg, best_t = None, float("inf")
    results = []
    for cfg in candidates:
        try:
            for _ in range(warmup):
                runner(cfg)
            t0 = time.perf_counter()
            for _ in range(iters):
                runner(cfg)
            dt = (time.perf_counter() - t0) / iters
        except Exception as e:  # candidate doesn't compile/fit — skip
            results.append({**cfg, "error": str(e)[:120]})
            continue
        results.append({**cfg, "ms": dt * 1e3})
        if dt < best_t:
            best_cfg, best_t = dict(cfg), dt
    if best_cfg is None:
        raise RuntimeError(f"autotune: no candidate for {op} worked: "
                           f"{results}")
    best_cfg["ms"] = best_t * 1e3
    record(op, signature, best_cfg)
    if save and get_cache()._path is not None:
        get_cache().save()
    return best_cfg


# -- flash attention ------------------------------------------------------

FLASH_BLOCK_CANDIDATES = ((1024, 1024), (512, 1024), (1024, 512),
                          (512, 512), (256, 1024), (512, 2048))


def flash_signature(sq: int, sk: int, d: int, causal: bool,
                    dtype="bfloat16") -> Tuple:
    # dtype is part of the key: a block config tuned for bf16 has half the
    # VMEM footprint of the same config at fp32
    return ("sq", sq, "sk", sk, "d", d, "causal", int(causal),
            "dtype", str(dtype))


def tune_flash(b: int, h: int, s: int, d: int, causal: bool = True,
               dtype="bfloat16", candidates=FLASH_BLOCK_CANDIDATES,
               grad: bool = True) -> dict:
    """Benchmark flash block sizes at [b, h, s, d] and cache the winner
    (keyed by sequence/head-dim — batch/head count only scale the grid)."""
    import jax
    import jax.numpy as jnp

    from .flash_attention_kernel import flash_attention_bhsd

    key = jax.random.PRNGKey(0)
    dt = jnp.dtype(dtype)
    q = jax.random.normal(key, (b, h, s, d), dt)
    k = jax.random.normal(key, (b, h, s, d), dt)
    v = jax.random.normal(key, (b, h, s, d), dt)

    def runner(cfg):
        bq, bk = cfg["block_q"], cfg["block_k"]
        if grad:
            def f(q, k, v):
                return jnp.sum(flash_attention_bhsd(
                    q, k, v, causal=causal, block_q=bq,
                    block_k=bk).astype(jnp.float32))
            out = jax.grad(f)(q, k, v)
            float(jnp.sum(out))  # host readback barrier
        else:
            out = flash_attention_bhsd(q, k, v, causal=causal,
                                       block_q=bq, block_k=bk)
            float(jnp.sum(out.astype(jnp.float32)))

    cands = [{"block_q": bq, "block_k": bk} for bq, bk in candidates
             if bq <= s and bk <= s]
    return tune("flash_attention", flash_signature(s, s, d, causal, dtype),
                cands, runner)


# -- decode attention -----------------------------------------------------

DECODE_BLOCK_CANDIDATES = (256, 512, 1024, 2048)


def decode_signature(s_max: int, h: int, d: int, dtype="bfloat16") -> Tuple:
    return ("s_max", s_max, "h", h, "d", d, "dtype", str(dtype))


def tune_decode_mha(b: int, h: int, s_max: int, d: int, dtype="bfloat16",
                    candidates=DECODE_BLOCK_CANDIDATES) -> dict:
    """Benchmark decode_mha S-block sizes at [b, h, s_max, d] over a
    mixed-length batch (the serving shape) and cache the winner."""
    import jax
    import jax.numpy as jnp

    from .pallas_kernels import decode_mha

    key = jax.random.PRNGKey(0)
    dt = jnp.dtype(dtype)
    q = jax.random.normal(key, (b, h, d), dt)
    kc = jax.random.normal(key, (b, s_max, h, d), dt)
    vc = jax.random.normal(key, (b, s_max, h, d), dt)
    lens = jnp.linspace(s_max // 8, s_max, b).astype(jnp.int32)

    def runner(cfg):
        out = decode_mha(q, kc, vc, lens, block_s=cfg["block_s"])
        float(jnp.sum(out.astype(jnp.float32)))   # host readback barrier

    cands = [{"block_s": bs} for bs in candidates if bs <= s_max]
    return tune("decode_mha", decode_signature(s_max, h, d, dtype),
                cands, runner)
