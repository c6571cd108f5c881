"""The routed experts' products on the chip, kernel alone: the megablox
kernel at the tiles ``_gmm_tiling`` picks and at the alternatives that
fit VMEM, for each expert configuration of the benchmark, at its decode
row count and at one prefill token block. Gate/up products sweep ``tk``
(their output is bf16), down products sweep ``tn`` (float32 output), as
``routed_experts_ffn`` calls them.

Prints one JSON line a (configuration, rows, product, tiles): ms a call
(the best of three timed loops), GB/s of the hit experts' weights and
that rate's share of the chip's HBM roofline, and TFLOP/s of the rows in
a group (most of the latent model's rows chose experts held elsewhere);
``picked`` marks the tiles ``_gmm_tiling`` gives. The lines are also
written to
``chiprun_out/exp_grouped_matmul.jsonl``. Needs a TPU:

    python experiments/exp_grouped_matmul.py

``--hashes [--against DIR]`` needs no chip: it lowers ``grouped_matmul``
for a described v5e at every (configuration, rows, product) above and
prints a sha256 of each program, the kernel's body taken without its
source locations (they name the checkout's files and lines); with
``--against`` the checkout at DIR is lowered too, by this script, and the
two columns are printed side by side:

    JAX_PLATFORMS=cpu python experiments/exp_grouped_matmul.py \
        --hashes --against PARENT
"""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if "--root" in sys.argv:    # lower another checkout's grouped_matmul
    ROOT = os.path.abspath(sys.argv[sys.argv.index("--root") + 1])
sys.path.insert(0, ROOT)
from jax.experimental.pallas.ops.tpu.megablox import gmm  # noqa: E402

from paddle_tpu.ops import pallas  # noqa: E402

HBM_BYTES_PER_S = 819e9     # v5e (benchmark/lib/peaks.py)

# name: (experts routed over, experts held, hidden, expert width, top-k,
#        decode rows, prefill tokens of one block)
CONFIGS = {
    # benchmark/configs/trinity-mini.json: 32 rows x 8 at decode
    "trinity-mini": (128, 128, 2048, 1024, 8, 32, 2048),
    # benchmark/configs/smallthinker-21b-a3b.json: 24 rows x 6
    "smallthinker-21b-a3b": (64, 64, 2560, 768, 6, 24, 2048),
    # benchmark/configs/deepseek-v3.2.json: 16 of 256 held, 16 rows x 8,
    # a prefill's token block of 2,048
    "deepseek-v3.2": (256, 16, 7168, 2048, 8, 16, 2048),
}


def bench(fn, *args, n=30, reps=3):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t) / n)
    return best * 1e3


def candidates(dim, old, tiles_of):
    """The old tile, and every multiple of 128 from 512 up that divides
    ``dim`` and whose tiles, ``tiles_of(t)``, fit VMEM."""
    return sorted({old} | {
        t for t in range(512, dim + 1, 128) if dim % t == 0
        and pallas._gmm_vmem_bytes(*tiles_of(t)) <= pallas.GMM_VMEM_BYTES})


def group_sizes(rs, tokens, routed, held, top_k):
    """Uniform top-k of random scores; choices of experts held elsewhere
    belong to no group here, as ``routed_experts_ffn`` sorts them."""
    sel = np.argsort(rs.rand(tokens, routed), axis=1)[:, :top_k].ravel()
    return np.bincount(sel[sel < held], minlength=held).astype(np.int32)


def main():
    assert jax.devices()[0].platform == "tpu", jax.devices()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out",
                            "exp_grouped_matmul.jsonl"), "w")
    key = jax.random.PRNGKey(0)
    rs = np.random.RandomState(0)
    for name, (routed, held, h, w, top_k, rows, block) in CONFIGS.items():
        gate = jax.random.normal(key, (held, h, w), jnp.bfloat16) * 0.02
        down = jax.random.normal(key, (held, w, h), jnp.bfloat16) * 0.02
        for phase, tokens in (("decode", rows), ("prefill", block)):
            m = tokens * top_k
            sizes = group_sizes(rs, tokens, routed, held, top_k)
            hit, in_groups = int((sizes > 0).sum()), int(sizes.sum())
            sizes = jnp.asarray(sizes)
            x = jax.random.normal(key, (m, h), jnp.bfloat16)
            mid = jax.random.normal(key, (m, w), jnp.bfloat16)
            for product, lhs, rhs, out_dtype, (k, n) in (
                    ("gate_up", x, gate, jnp.bfloat16, (h, w)),
                    ("down", mid, down, jnp.float32, (w, h))):
                picked = pallas._gmm_tiling(m, k, n)
                tm, tk, tn = picked
                if product == "gate_up":
                    dim, old = k, min(k, 2048)
                    tiles_of = lambda t: (tm, t, tn)  # noqa: E731
                else:
                    dim, old = n, min(n, 1024)
                    tiles_of = lambda t: (tm, tk, t)  # noqa: E731
                tilings = [tiles_of(t)
                           for t in candidates(dim, old, tiles_of)]
                pad = -m % tm
                a = jnp.pad(lhs, ((0, pad), (0, 0))) if pad else lhs
                for tiling in tilings:
                    fn = jax.jit(lambda a_, w_, s_, t=tiling, o=out_dtype:
                                 gmm(a_, w_, s_, preferred_element_type=o,
                                     tiling=t))
                    rec = {"config": name, "phase": phase, "rows": m,
                           "product": product, "tiling": list(tiling),
                           "picked": tiling == picked,
                           "ragged": bool(k % tiling[1] or n % tiling[2]),
                           "experts_hit": hit, "rows_in_groups": in_groups}
                    try:
                        ms = bench(fn, a, rhs, sizes)
                    except Exception as e:  # a tiling the compiler refuses
                        rec["error"] = repr(e)[:200]
                    else:
                        gbs = hit * k * n * 2 / (ms / 1e3) / 1e9
                        rec.update(ms=round(ms, 4), GBps=round(gbs, 1),
                                   roofline=round(100 * gbs * 1e9
                                                  / HBM_BYTES_PER_S, 1),
                                   TFLOPs=round(2 * in_groups * k * n / ms
                                                / 1e9, 1))
                    line = json.dumps(rec)
                    print(line, flush=True)
                    log.write(line + "\n")
    log.close()



def shapes():
    """(label, groups, m, k, n, output dtype) of every product above."""
    for name, (_, held, h, w, top_k, rows, block) in CONFIGS.items():
        for phase, tokens in (("decode", rows), ("prefill", block)):
            for product, k, n, out in (("gate_up", h, w, jnp.bfloat16),
                                       ("down", w, h, jnp.float32)):
                yield (f"{name} {phase} {product}", held, tokens * top_k,
                       k, n, out)


def hashes():
    """{label: sha256 of grouped_matmul lowered for a described v5e}."""
    import base64
    import hashlib
    import re

    from jax._src.lib import tpu as tpu_dialect
    from jax._src.lib.mlir import ir
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    def body(match):    # the Mosaic kernel, printed without locations
        with ir.Context() as ctx:
            tpu_dialect.register_dialect(ctx)
            ctx.allow_unregistered_dialects = True
            asm = ir.Module.parse(base64.b64decode(match.group(1))) \
                .operation.get_asm(enable_debug_info=False)
        return hashlib.sha256(asm.encode()).hexdigest()

    pallas._on_tpu = lambda: True
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    out = {}
    for label, groups, m, k, n, dtype in shapes():
        args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
            ((m, k), jnp.bfloat16), ((groups, k, n), jnp.bfloat16),
            ((groups,), jnp.int32))]
        text = jax.jit(lambda x, w, s, o=dtype: pallas.grouped_matmul(
            x, w, s, preferred_element_type=o)).lower(*args).as_text()
        text = re.sub(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22", body, text)
        out[f"{label} {m}x{k}x{n}"] = \
            hashlib.sha256(text.encode()).hexdigest()[:16]
    return out


def print_hashes(against):
    theirs = {}
    if against is not None:    # first: one process at a time holds libtpu
        theirs = json.loads(subprocess.run(
            [sys.executable, __file__, "--hashes-json", "--root", against],
            check=True, stdout=subprocess.PIPE,
            text=True).stdout.splitlines()[-1])
    for label, h in hashes().items():
        if against is None:
            print(f"{label:52s} {h}")
            continue
        t = theirs.get(label, "-")
        print(f"{label:52s} {t} {h} {'same' if t == h else 'DIFFERS'}")


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if "--hashes-json" in sys.argv:
        print(json.dumps(hashes()))
    elif "--hashes" in sys.argv:
        print_hashes(sys.argv[sys.argv.index("--against") + 1]
                     if "--against" in sys.argv else None)
    else:
        main()
