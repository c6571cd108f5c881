"""Per-backend peak compute/bandwidth table — the roofline's ceiling.

The program ledger (``paddle_tpu.monitor.ledger``) turns XLA
``cost_analysis()`` FLOPs/bytes plus measured dispatch time into
achieved FLOP/s and bytes/s; THIS module supplies the denominator —
the peak the hardware could do — so MFU and the roofline verdict
(memory-bound vs compute-bound) mean the same thing across backends:

- **TPU**: a static table keyed by the EXACT ``device_kind`` string the
  chip reports (bf16 dense peak + HBM bandwidth, with the source of the
  numbers). A TPU that is not listed raises — a wrong or invented
  denominator is worse than none.
- **Anything else** (the tier-1/test backend is the CPU): no meaningful
  datasheet number exists, so the peak is CALIBRATED once per process —
  a small timed matmul for FLOP/s, a timed device-array copy for
  bytes/s — and cached. Calibrated MFU is only comparable within one
  host, which is exactly what a CPU A/B needs (and why the record
  carries ``source: "calibrated"``).
- Environment overrides ``PADDLE_TPU_PEAK_FLOPS`` /
  ``PADDLE_TPU_PEAK_BYTES`` win over both (``source: "env"``) — the
  way to run on unlisted hardware or against a deliberately pinned
  baseline; an unlisted TPU needs both.

``machine_balance`` (peak FLOPs / peak bytes, FLOP-per-byte) is the
roofline ridge: a program whose arithmetic intensity sits below it is
memory-bound — more MXU would not help; feeding it would.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, Optional

__all__ = ["peaks", "peak_flops", "machine_balance", "TPU_PEAKS"]

# device_kind (exact) -> (bf16 dense FLOP/s, HBM bytes/s).
# "TPU v5 lite" is what a v5e chip reports. Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16 (394 is the int8 figure),
# 16 GB HBM at 819 GB/s.
TPU_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
}

_lock = threading.Lock()
_cache: Optional[Dict[str, Any]] = None


def _calibrate_cpu() -> Dict[str, float]:
    """One-shot peak probe for a backend with no datasheet (the CPU): best-of-3 timed f32 matmul (2·n³ FLOPs)
    and device-array copy (2·nbytes moved). ~100 ms once per process;
    runs at ledger enable / first profile read, never on a dispatch."""
    import jax
    import jax.numpy as jnp

    n = 512
    x = jnp.ones((n, n), jnp.float32)
    # lint: allow-recompile(one-shot probe, result cached per process)
    mm = jax.jit(lambda a: a @ a)
    # lint: allow-recompile(one-shot probe, result cached per process)
    cp = jax.jit(lambda a: a + 0.0)
    mm(x).block_until_ready()           # compile outside the clock
    cp(x).block_until_ready()
    best_mm = best_cp = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        mm(x).block_until_ready()
        best_mm = min(best_mm, time.perf_counter() - t0)
        t0 = time.perf_counter()
        cp(x).block_until_ready()
        best_cp = min(best_cp, time.perf_counter() - t0)
    flops = 2.0 * n ** 3 / max(best_mm, 1e-9)
    byts = 2.0 * x.nbytes / max(best_cp, 1e-9)   # read + write
    return {"peak_flops": flops, "peak_bytes_per_s": byts}


def peaks(refresh: bool = False) -> Dict[str, Any]:
    """The backend peak record, cached per process::

        {"device_kind", "platform", "peak_flops", "peak_bytes_per_s",
         "machine_balance", "source": "table" | "calibrated" | "env"}

    Raises ``KeyError`` on a TPU whose ``device_kind`` is not in
    ``TPU_PEAKS`` (unless the environment overrides supply both
    numbers); backend errors propagate."""
    global _cache
    with _lock:
        if _cache is not None and not refresh:
            return _cache
    import jax

    dev = jax.devices()[0]
    platform = dev.platform
    kind = dev.device_kind
    env_f = os.environ.get("PADDLE_TPU_PEAK_FLOPS")
    env_b = os.environ.get("PADDLE_TPU_PEAK_BYTES")
    if env_f and env_b:
        flops = byts = None
    elif platform == "tpu":
        if kind not in TPU_PEAKS:
            raise KeyError(
                f"no peak FLOP/s and bytes/s listed for device_kind "
                f"{kind!r} (listed: {sorted(TPU_PEAKS)}); add it to "
                f"paddle_tpu/device/peaks.py with its source, or set "
                f"PADDLE_TPU_PEAK_FLOPS and PADDLE_TPU_PEAK_BYTES")
        flops, byts = TPU_PEAKS[kind]
        source = "table"
    else:
        cal = _calibrate_cpu()
        flops, byts = cal["peak_flops"], cal["peak_bytes_per_s"]
        source = "calibrated"
    if env_f or env_b:
        source = "env"
        flops = float(env_f) if env_f else flops
        byts = float(env_b) if env_b else byts
    rec = {"device_kind": kind, "platform": platform,
           "peak_flops": flops, "peak_bytes_per_s": byts,
           "machine_balance": flops / byts, "source": source}
    with _lock:
        _cache = rec
    return rec


def peak_flops() -> float:
    """Shorthand for ``peaks()["peak_flops"]``."""
    return peaks()["peak_flops"]


def machine_balance() -> float:
    """The roofline ridge point in FLOP/byte: programs below it are
    memory-bound on this backend, above it compute-bound."""
    return peaks()["machine_balance"]
