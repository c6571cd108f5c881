"""On-TPU autotune sweep: block sizes for flash fwd+bwd
and decode_mha at the llama bench/serving shapes, persisted to the
IN-REPO cache (.autotune_cache.json) so `bench.py` picks tuned blocks on
first run. Commit the file after a successful sweep.

Run: python experiments/exp_autotune_sweep.py        (TPU; ~3-5 min)

Each tune target runs in its OWN subprocess with a wall-clock budget
(EXP_TRIAL_SECS, default 900) and saves its winner into the repo cache
INCREMENTALLY (AutoTuneCache.load merges): with per-trial isolation a
hung compile costs one entry, not the sweep. Trials run one at a time
and this parent never touches JAX, so one process at a time wants the
chip.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# flash at the two bench configs (350M: h8 d128 s2048; 1.3B: h16 d128).
# grad=True ONLY: the cache key has no fwd/bwd distinction (the router
# consults one key for both), so the tuned config must optimize the
# TRAINING (fwd+bwd) path — a later fwd-only tune would clobber it.
TARGETS = [
    {"kind": "flash", "b": 8, "h": 8, "s": 2048, "d": 128},
    {"kind": "flash", "b": 4, "h": 16, "s": 2048, "d": 128},
    {"kind": "flash", "b": 8, "h": 8, "s": 1024, "d": 128},
    # decode at serving shapes (engine max_len 2048/4096)
    {"kind": "decode", "b": 8, "h": 8, "s_max": 2048, "d": 128},
    {"kind": "decode", "b": 8, "h": 8, "s_max": 4096, "d": 128},
]


def tune_one(spec: dict):
    import jax

    if os.environ.get("EXP_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")

    from paddle_tpu.device.compile_cache import use_compile_cache

    use_compile_cache()

    from paddle_tpu.ops import autotune

    # the in-repo file is the only table autotune reads; naming it as the
    # write path makes each trial's save an incremental merge into it
    autotune.set_cache_path(os.path.join(REPO, ".autotune_cache.json"))
    if jax.default_backend() != "tpu":
        print(json.dumps({"warning": "not on TPU — sweep would record "
                          "meaningless CPU timings; refusing to persist"}))
        return
    if spec["kind"] == "flash":
        cfg = autotune.tune_flash(spec["b"], spec["h"], spec["s"],
                                  spec["d"], causal=True,
                                  dtype="bfloat16", grad=True)
        label = f"flash s={spec['s']} h={spec['h']} fwd+bwd"
    else:
        cfg = autotune.tune_decode_mha(spec["b"], spec["h"],
                                       spec["s_max"], spec["d"],
                                       dtype="bfloat16")
        label = f"decode s_max={spec['s_max']}"
    autotune.get_cache().save()
    print(json.dumps({label: cfg, "saved": True}), flush=True)


def main():
    from _budget import run_budgeted

    budget = int(os.environ.get("EXP_TRIAL_SECS", "900"))
    saved = 0
    for spec in TARGETS:
        r = run_budgeted([sys.executable, "-u", os.path.abspath(__file__),
                          "--one", json.dumps(spec)], budget)
        if r.timed_out:
            print(json.dumps({str(spec): f"hung >{budget}s "
                              "(group killed)"}), flush=True)
        if r.err.strip():
            sys.stderr.write(f"--- {spec} stderr tail ---\n"
                             + r.err[-2000:] + "\n")
        for ln in r.out.splitlines():
            if ln.strip().startswith("{"):
                print(ln, flush=True)
                if '"saved": true' in ln:
                    saved += 1
    path = os.path.join(REPO, ".autotune_cache.json")
    entries = 0
    if os.path.exists(path):
        try:
            with open(path) as f:
                entries = len(json.load(f))
        except ValueError:
            entries = "unreadable"
    print(json.dumps({"cache_file": path, "entries": entries,
                      "trials_saved": saved, "of": len(TARGETS)}))


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--one":
        tune_one(json.loads(sys.argv[2]))
    else:
        main()
