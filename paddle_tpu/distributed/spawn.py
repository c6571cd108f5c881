"""paddle.distributed.spawn parity (reference: distributed/spawn.py) —
multiprocess helper for CPU-simulation of multi-process training.

CPU-only for now: a chip belongs to one process at a time, and the
workers inherit the parent's whole environment, so on a TPU host every
worker (and a parent that has touched JAX) would claim every chip. On
TPU proper, one process owns all chips; spawn exists for the reference's
process-per-worker tests."""
from __future__ import annotations

import multiprocessing as mp
import os
from typing import Optional, Tuple

__all__ = ["spawn"]


def _worker(func, rank, nprocs, args, env):
    os.environ.update(env)
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    func(rank, *args) if _takes_rank(func) else func(*args)


def _takes_rank(func) -> bool:
    import inspect

    try:
        params = inspect.signature(func).parameters
        return len(params) >= 1 and next(iter(params)) in ("rank", "local_rank")
    except (TypeError, ValueError):
        return False


def spawn(func, args: Tuple = (), nprocs: int = 1, join: bool = True,
          daemon: bool = False, **options):
    ctx = mp.get_context(options.get("start_method", "spawn"))
    procs = []
    env = {k: v for k, v in os.environ.items()}
    for rank in range(nprocs):
        p = ctx.Process(target=_worker,
                        args=(func, rank, nprocs, args, env), daemon=daemon)
        p.start()
        procs.append(p)

    class Context:
        processes = procs

        def join(self, timeout: Optional[float] = None):
            for p in procs:
                p.join(timeout)
            codes = [p.exitcode for p in procs]
            if any(c not in (0, None) for c in codes):
                raise RuntimeError(f"spawned process failed: exit codes {codes}")
            return all(c == 0 for c in codes)

    c = Context()
    if join:
        c.join()
    return c
