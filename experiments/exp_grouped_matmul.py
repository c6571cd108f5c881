"""The routed experts' products on the chip: the megablox kernel at a few
tilings against jax.lax.ragged_dot, at the decode shape (32 rows x 8
choices over 128 experts) and at a prefill bucket's. Prints one JSON line
a variant: ms a call and, for decode, GB/s of expert weights streamed.

    python experiments/exp_grouped_matmul.py
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from jax.experimental.pallas.ops.tpu.megablox import gmm  # noqa: E402

E, H, M = 128, 2048, 1024


def bench(fn, *args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def main():
    assert jax.devices()[0].platform == "tpu"
    key = jax.random.PRNGKey(0)
    up = jax.random.normal(key, (E, H, M), jnp.bfloat16) * 0.02
    down = jax.random.normal(key, (E, M, H), jnp.bfloat16) * 0.02
    rs = np.random.RandomState(0)
    for rows, tokens in ((256, 32), (4096, 512), (65536, 8192)):
        # top-8 of random scores: the sizes a uniform router gives
        sel = np.argsort(rs.rand(tokens, E), axis=1)[:, :8].ravel()
        sizes = jnp.asarray(np.bincount(sel, minlength=E), jnp.int32)
        hit = int((np.asarray(sizes) > 0).sum())
        x = jax.random.normal(key, (rows, H), jnp.bfloat16)
        xm = jax.random.normal(key, (rows, M), jnp.bfloat16)
        variants = {"ragged_dot": None}
        for tm in ((128, 256) if rows == 256 else (256, 512)):
            for tn in (512, 1024):
                variants[f"gmm_{tm}_2048_{tn}"] = (tm, 2048, tn)
        for name, tiling in variants.items():
            if tiling is None:
                f_up = jax.jit(lambda a, w, s: jax.lax.ragged_dot(
                    a, w, s, preferred_element_type=jnp.bfloat16))
                f_dn = jax.jit(lambda a, w, s: jax.lax.ragged_dot(
                    a, w, s, preferred_element_type=jnp.float32))
            else:
                tm, tk, tn = tiling
                f_up = jax.jit(lambda a, w, s, t=tiling: gmm(
                    a, w, s, preferred_element_type=jnp.bfloat16, tiling=t))
                f_dn = jax.jit(lambda a, w, s, t=(tm, 1024, tn): gmm(
                    a, w, s, preferred_element_type=jnp.float32, tiling=t))
            try:
                ms_up = bench(f_up, x, up, sizes)
                ms_dn = bench(f_dn, xm, down, sizes)
            except Exception as e:  # a tiling the compiler refuses
                print(json.dumps({"rows": rows, "variant": name,
                                  "error": repr(e)[:200]}), flush=True)
                continue
            gbs = hit * H * M * 2 / 1e9
            print(json.dumps({
                "rows": rows, "variant": name, "experts_hit": hit,
                "up_ms": round(ms_up, 4), "down_ms": round(ms_dn, 4),
                "up_GBps": round(gbs / (ms_up / 1e3), 1),
                "down_GBps": round(gbs / (ms_dn / 1e3), 1),
                "up_TFLOPs": round(2 * rows * H * M / ms_up / 1e9, 1)}),
                flush=True)


if __name__ == "__main__":
    main()
