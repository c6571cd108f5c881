"""The latent cell's fused cold admission (``jit_prefill_one``) alone on the
chip, at the cell's real size: seconds a run of the 16,384 and 8,192 buckets
for prompts that fill a part of the bucket, with the prefill's row-wise work
in blocks of 512 / 1,024 / 2,048 rows (``models/deepseek_v32.py``'s
``PREFILL_ROW_BLOCK``, set here before each trace). The table that chose
the block is in PERF.md section 6 (PR 35). Prints one JSON line a case.

The model, its weights and the engine are the cell's own
(``benchmark/configs/deepseek-v3.2.json``, ``benchmark/traffic/
longctx-steady.json``). A run is the engine's own call (the page-table
upload and the ONE program); ``--reps`` runs are dispatched back to back,
each taking the pools the last one gave, and the host waits once at the
end, so a dispatch's millisecond is not in the time.

``--repo DIR`` times another checkout (the parent's: its module has no
``PREFILL_ROW_BLOCK`` and runs the bucket's rows whatever the prompt, one
variant, ``block`` null).

    python experiments/exp_prefill_rows.py [--repo DIR] [--blocks 512,1024,2048]

``--rehearse`` runs the same control flow on a tiny model on any device;
its times mean nothing.
"""
import argparse
import json
import os
import sys
import time

import numpy as np


def top_ops(run, n):
    """[[operation, seconds of self time]] of one traced ``run()``, by the
    benchmark's own reduction of the profiler's device plane."""
    import tempfile

    import jax

    from benchmark.lib import trace_reduce as tr
    from benchmark.run import profiler_options

    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=profiler_options())
        try:
            jax.block_until_ready(run())
        finally:
            jax.profiler.stop_trace()
        raw = tr.load_xplane(tr.find_xplane(tmp))
        # a rehearsal on the CPU has no device plane to read
        return tr.top_ops(raw, n) if tr.device_planes(raw) else []


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..")))
    ap.add_argument("--blocks", default="512,1024,2048")
    ap.add_argument("--buckets", default="16384,8192")
    ap.add_argument("--plens", default="2049,8706,13243,16384")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=3500000101)
    ap.add_argument("--profile", type=int, default=0,
                    help="also trace ONE run of each case and print its N "
                    "operations of most self time")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    repo = os.path.abspath(args.repo)
    sys.path.insert(0, repo)
    os.chdir(repo)
    import jax

    import paddle_tpu as paddle
    from benchmark.run import (build_config, load_json, program_seed,
                               resolve)
    from paddle_tpu.inference.generation import (
        PagedContinuousBatchingEngine, _pad_ids)
    from paddle_tpu.models import deepseek_v32 as dsv

    config = load_json("benchmark", "configs", "deepseek-v3.2.json")
    mix = load_json("benchmark", "traffic", "longctx-steady.json")
    blocks = [int(b) for b in args.blocks.split(",")]
    buckets = [int(b) for b in args.buckets.split(",")]
    plens = [int(p) for p in args.plens.split(",")]
    geometry = dict(mix["engine"])
    if args.rehearse:
        config = dict(config, hidden_size=64, intermediate_size=128,
                      moe_intermediate_size=32, num_attention_heads=4,
                      num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
                      qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                      index_n_heads=16, index_head_dim=16, index_topk=8,
                      num_hidden_layers=2, vocab_size=256)
        blocks, buckets, plens = [8, 16], [64, 32], [9, 33, 64]
        geometry = dict(max_batch=2, page_size=4, max_pages=16, num_pages=64,
                        prefill_buckets=buckets)
    elif jax.devices()[0].platform != "tpu":
        raise SystemExit(f"times mean something on a TPU only; JAX found "
                         f"{jax.devices()} (--rehearse runs anywhere)")
    if not hasattr(dsv, "PREFILL_ROW_BLOCK"):
        blocks = [None]                 # the parent: the bucket's rows
    cfg = build_config(config)
    paddle.seed(program_seed(args.seed))
    model = resolve(config["model_class"])(cfg)
    model.eval()
    eng = PagedContinuousBatchingEngine(model, **geometry)
    eng.alloc.ensure(0, max(buckets))
    rs = np.random.RandomState(args.seed % 2 ** 31)
    ids = rs.randint(1, cfg.vocab_size, (1, max(buckets))).astype(np.int32)
    for block in blocks:
        if block is not None:
            dsv.PREFILL_ROW_BLOCK = block
            jax.clear_caches()          # the next call traces with it
        for bucket in buckets:
            for plen in (p for p in plens if p <= bucket):
                padded = _pad_ids(ids[:, :plen], bucket)
                t = time.perf_counter()
                jax.block_until_ready(
                    eng._prefill_install(0, padded, plen, 0))
                first = time.perf_counter() - t     # compile + one run
                t = time.perf_counter()
                for _ in range(args.reps):
                    out = eng._prefill_install(0, padded, plen, 0)
                jax.block_until_ready(out)
                print(json.dumps({
                    "repo": os.path.basename(repo), "block": block,
                    "bucket": bucket, "plen": plen, "reps": args.reps,
                    "s_per_run": round(
                        (time.perf_counter() - t) / args.reps, 4),
                    "first_call_s": round(first, 1),
                    "finite": bool(np.isfinite(np.asarray(out)).all()),
                    "device": jax.devices()[0].device_kind,
                    "rehearsal": args.rehearse}), flush=True)
                if args.profile:
                    print(json.dumps({"block": block, "bucket": bucket,
                                      "plen": plen, "top_ops": top_ops(
                        lambda: eng._prefill_install(0, padded, plen, 0),
                        args.profile)}), flush=True)
    eng.close()


if __name__ == "__main__":
    main()
