"""From per-request records to the end-to-end metrics. No JAX, no program.

A record is what ``client.py`` writes for one request: ``due_s`` (when
it was due, from the window's start), ``lag_s`` (how late it was sent),
``ok``, ``n`` tokens, ``t_first_s`` / ``t_last_s`` / ``t_end_s`` (client
clock, from the window's start). Every latency is taken from the DUE
instant, so a late generator or a stalled server cannot hide queueing.
"""
from __future__ import annotations

import math


def percentile(xs, q: float) -> float:
    """Nearest rank on the sorted values (copied from
    tools/serve_bench.py ``_percentile``)."""
    if not xs:
        return float("nan")
    xs = sorted(xs)
    i = min(len(xs) - 1, int(round(q / 100.0 * (len(xs) - 1))))
    return xs[i]


def beyond(n: int, q: float) -> int:
    """How many of n samples lie beyond the q-th percentile. A tail is
    worth reporting when this is at least ten."""
    return int(math.floor(n * (1.0 - q / 100.0) + 1e-9))


def ttft_ms(records) -> list:
    """First token at the client minus the due instant, in ms; +inf for
    a request that was rejected, failed or never produced a token."""
    return [(r["t_first_s"] - r["due_s"]) * 1e3
            if r["ok"] and r["t_first_s"] is not None else math.inf
            for r in records]


def tpot_ms(records) -> list:
    """(t_last - t_first) / (n - 1) per finished request of >= 2 tokens."""
    return [(r["t_last_s"] - r["t_first_s"]) * 1e3 / (r["n"] - 1)
            for r in records if r["ok"] and r["n"] >= 2]


def tokens_per_s(records) -> float:
    """Tokens generated for the window's requests over (last completion
    - window start)."""
    done = [r for r in records if r["ok"]]
    if not done:
        return 0.0
    return sum(r["n"] for r in done) / max(r["t_end_s"] for r in done)


def lateness_ms(records) -> dict:
    lags = [r["lag_s"] * 1e3 for r in records if r["lag_s"] is not None]
    return {"p50": percentile(lags, 50), "max": max(lags, default=0.0)}


def serve_metrics(records) -> dict:
    return {"serve_ttft_p90_ms": percentile(ttft_ms(records), 90),
            "serve_tpot_p50_ms": percentile(tpot_ms(records), 50),
            "serve_tokens_per_s": tokens_per_s(records)}
