"""The one general traffic generator: a mix's data file in, work out.

A mix (``benchmark/traffic/<mix>.json``) is parameters only. ``kind:
"train"`` draws token batches; ``kind: "serve"`` draws an open-loop
schedule of requests. Nothing here imports the program or JAX.

Steadiness rule: a serving mix is a recorded trace replayed. The sizes
and the instants of its requests are drawn from the mix's own
``shape_seed`` and are the same for every ``--seed``; the run's seed draws
the token ids (and the weights). Tails depend on which long request meets
which burst, so a schedule that changed with the seed would move a p90 by
tens of per cent between runs of the same code (seen on the chip, PR 24:
337 and 548 ms on two orders of one set), far more than any change a
later PR has to show. With the schedule fixed, a spread between runs is
the system's, not the draw's.
"""
from __future__ import annotations

import math
import random

import numpy as np


def draw_len(rng: random.Random, dist: str, lo: int, hi: int) -> int:
    """One length. ``lognormal`` (copied from tools/serve_bench.py
    ``_draw_len``): centred on the geometric mean of lo and hi, with
    (ln hi - ln lo) / 4 as sigma, clipped to [lo, hi]: many short, a long
    tail. ``uniform``: whole numbers lo..hi."""
    if dist == "lognormal":
        mu = (math.log(lo) + math.log(hi)) / 2.0
        sigma = max((math.log(hi) - math.log(lo)) / 4.0, 1e-6)
        return min(hi, max(lo, int(round(rng.lognormvariate(mu, sigma)))))
    if dist == "uniform":
        return rng.randint(lo, hi)
    raise ValueError(f"unknown length distribution {dist!r}")


def _gaps(rng: random.Random, arrivals: str, n: int, rate: float,
          cv: float = 1.0) -> list:
    """n gaps between arrivals with mean 1/rate. ``poisson``: exponential
    gaps. ``gamma``: gamma gaps with coefficient of variation ``cv``
    (bursts for cv > 1)."""
    if arrivals == "poisson":
        return [rng.expovariate(rate) for _ in range(n)]
    if arrivals == "gamma":
        shape = 1.0 / (cv * cv)
        return [rng.gammavariate(shape, 1.0 / (rate * shape))
                for _ in range(n)]
    raise ValueError(f"unknown arrival process {arrivals!r}")


def serve_schedule(mix: dict, seed: int, seconds: float, vocab: int,
                   rate: float = None) -> list:
    """Requests due in [0, seconds): ``[{"due_s", "prompt",
    "max_new_tokens"}]`` in due order. ``rate`` overrides the mix's
    ``rate_per_s`` (the sweep). A draw whose prompt + answer would pass
    the engine's positions keeps its prompt and has its answer clipped."""
    rate = float(mix["rate_per_s"] if rate is None else rate)
    n = max(1, int(round(rate * seconds)))
    shape = random.Random(int(mix["shape_seed"]))
    p, a = mix["prompt_len"], mix["answer_len"]
    eng = mix["engine"]
    positions = eng["page_size"] * eng["max_pages"]
    sizes = []
    for _ in range(n):
        plen = min(draw_len(shape, p["dist"], p["lo"], p["hi"]),
                   positions - 1)
        alen = draw_len(shape, a["dist"], a["lo"], a["hi"])
        sizes.append((plen, max(1, min(alen, positions - plen))))
    gaps = _gaps(shape, mix.get("arrivals", "poisson"), n, rate,
                 float(mix.get("arrival_cv", 1.0)))
    scale = seconds / sum(gaps)       # the n gaps fill the window exactly
    ids = np.random.RandomState(seed % (2 ** 32))
    out, due = [], 0.0
    for (plen, alen), gap in zip(sizes, gaps):
        out.append({"due_s": due,
                    "prompt": ids.randint(1, vocab, (plen,)).tolist(),
                    "max_new_tokens": alen})
        due += gap * scale
    return out


def warmup_requests(mix: dict, vocab: int, steps: int) -> list:
    """One request per prefill bucket (a prompt that fills it), due at
    once, each decoding a little over two segments: every program of the
    serving path runs once before the window."""
    eng = mix["engine"]
    positions = eng["page_size"] * eng["max_pages"]
    ids = np.random.RandomState(int(mix["shape_seed"]) % (2 ** 32))
    out = []
    for w in eng["prefill_buckets"]:
        plen = min(w, positions - 2 * steps - 2)
        out.append({"due_s": 0.0,
                    "prompt": ids.randint(1, vocab, (plen,)).tolist(),
                    "max_new_tokens": 2 * steps + 1})
    return out


def train_batch(seed: int, step: int, batch: int, seq: int, vocab: int):
    """(ids, labels) of one step: fresh from the seed and the step."""
    rng = np.random.RandomState((seed + 1000003 * (step + 1)) % (2 ** 32))
    both = rng.randint(0, vocab, (2, batch, seq)).astype(np.int32)
    return both[0], both[1]
