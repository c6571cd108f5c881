"""The chip's compiler, asked before the chip, for the serving programs of
``benchmark/configs/deepseek-v3.2.json`` at the cell's real size: the fused
cold admission at each prefill bucket, ``jit_segment`` and the plain
reference's layer, compiled for a described ``TPU v5 lite`` (no device
attached; section 2 of the on-chip-measurement guide). Prints each
program's argument, output and temporary bytes and the kernels in its text.

    JAX_PLATFORMS=cpu python experiments/exp_aot_deepseek_v32.py \
        [segment] [reference] [bucket ...] [--text] [--pages N]

No argument: the segment and every bucket. ``--text`` keeps each program's
compiled text under ``chiprun_out/``; ``--pages N`` compiles with a pool of
N pages (one too large to fit makes the compiler list the largest
allocations).

Nothing runs and no weight is made: the model's parameters are shapes (a
global initializer that returns ``jax.ShapeDtypeStruct``), so the script
needs megabytes, not the model's 9 GB. A compile that passes is not a
chip run.
"""
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from paddle_tpu.nn import initializer  # noqa: E402
from paddle_tpu.ops import paged_attention  # noqa: E402


def main():
    from benchmark.run import build_config, load_json
    from paddle_tpu.models.deepseek_v32 import DeepseekV32ForCausalLM
    from paddle_tpu.inference.generation import \
        PagedContinuousBatchingEngine

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    paged_attention._interpret = lambda: False    # compile the kernels
    # grouped_matmul asks jax.default_backend(), which is the CPU here
    from paddle_tpu.ops import pallas
    pallas._on_tpu = lambda: True

    config = load_json("benchmark", "configs", "deepseek-v3.2.json")
    mix = load_json("benchmark", "traffic", "longctx-steady.json")
    cfg = build_config(config)

    def shape_only(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))

    initializer.set_global_initializer(shape_only, shape_only)
    # the model class itself: the benchmark's rescaling needs values
    model = DeepseekV32ForCausalLM(cfg)
    initializer.set_global_initializer(None, None)
    model.eval()
    pools_fn = model.init_paged_cache
    model.init_paged_cache = lambda *a, **k: jax.eval_shape(
        lambda: pools_fn(*a, **k))
    geometry = dict(mix["engine"])
    if "--pages" in sys.argv:       # a pool too large to fit: the compiler's
        # refusal lists the program's largest allocations
        geometry["num_pages"] = int(sys.argv[sys.argv.index("--pages") + 1])
    eng = PagedContinuousBatchingEngine(model, **geometry)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=chip), tree)

    params = on_chip(eng.params)
    pools, pt = eng.caches
    pools, pt = on_chip(pools), on_chip(pt)
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    print(json.dumps({"params": n_params, "param_bytes": sum(
        int(np.prod(v.shape)) * v.dtype.itemsize for v in params.values()),
        "pool_bytes": sum(int(np.prod(a.shape)) * a.dtype.itemsize
                          for pool in pools for a in pool)}))

    def report(name, lowered):
        t = time.time()
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        print(json.dumps({
            "program": name, "compile_s": round(time.time() - t, 1),
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "kernels": {k: text.count(f'"{k}') + text.count(f"{k}(")
                        for k in ("dsa_index_scores", "gmm")},
            "custom_calls": text.count("tpu_custom_call")}), flush=True)
        if "--text" in sys.argv:        # the compiled text, to read by hand
            out = os.path.join(ROOT, "chiprun_out",
                               name.replace(" ", "_") + ".hlo.txt")
            os.makedirs(os.path.dirname(out), exist_ok=True)
            with open(out, "w") as f:
                f.write(text)

    i32 = lambda: jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    what = [a for a in sys.argv[1:] if not a.startswith("--")]
    if "--pages" in sys.argv:
        what.remove(sys.argv[sys.argv.index("--pages") + 1])
    buckets = ([int(a) for a in what if a.isdigit()] if what
               else eng.prefill_buckets)
    if "segment" in what or not what:
        mb = eng.max_batch
        vec = lambda dt: jax.ShapeDtypeStruct((mb,), dt, sharding=chip)
        u32 = jax.ShapeDtypeStruct((), jnp.uint32, sharding=chip)
        report(f"jit_segment x{mix['server']['segment_steps']}",
               eng._segment_fn(mix["server"]["segment_steps"])._jitted.lower(
                   params, vec(jnp.int32), vec(jnp.int32), vec(bool),
                   vec(bool), on_chip(eng.samp), eng._bank(), (pools, pt),
                   u32, u32))
    for width in buckets:
        ids = jax.ShapeDtypeStruct((1, width), jnp.int32, sharding=chip)
        report(f"jit_prefill_one {width}", eng._prefill_paged._jitted.lower(
            params, ids, pools, pt, i32(), i32(), eng._bank(), i32()))
    if "reference" in what:
        from benchmark.run import load_module
        ref = load_module(os.path.join(ROOT, config["reference"]))
        names = dict(ref._ATTN, **ref._SPARSE)
        w = {k: params[f"model.layers.1.{n}"] for k, n in names.items()}
        x = jax.ShapeDtypeStruct((17408, cfg.hidden_size), jnp.float32,
                                 sharding=chip)
        report("reference layer 17408", ref._layer.lower(
            x, w, st=ref._Static(cfg)))


if __name__ == "__main__":
    main()
