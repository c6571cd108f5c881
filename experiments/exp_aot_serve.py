"""The chip's compiler, asked before the chip, for the serving programs of
one serving cell of ``BENCHMARK.json`` at its real size: the fused cold
admission at each prefill bucket and ``jit_segment``, compiled for a
described ``TPU v5 lite`` (no device attached, through
``jax.experimental.topologies``). Prints the parameters' and the pools' bytes,
then each program's argument, output and temporary bytes and the Pallas
kernels in its text.

    JAX_PLATFORMS=cpu python experiments/exp_aot_serve.py <cell> \
        [segment] [bucket ...] [--max-batch N]

No argument after the cell: the segment and every bucket. ``--max-batch
N`` compiles with N rows in place of the mix's. Nothing runs and no
weight is made (the parameters are shapes). A compile that passes is not
a chip run.
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


def main():
    from benchmark.run import build_config, load_json, resolve
    from paddle_tpu.inference.generation import \
        PagedContinuousBatchingEngine
    from paddle_tpu.ops import (flash_attention_kernel, paged_attention,
                                pallas)

    args = sys.argv[1:]
    cell_name = args.pop(0)
    bench = load_json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(entry["file"])
    mix = load_json("benchmark", "traffic", cell["traffic"] + ".json")
    geometry = dict(mix["engine"])
    if "--max-batch" in args:
        at = args.index("--max-batch")
        geometry["max_batch"] = int(args[at + 1])
        del args[at:at + 2]

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    # compile the kernels: each module asks jax.devices(), the CPU here
    paged_attention._interpret = lambda: False
    flash_attention_kernel._interpret = lambda: False
    pallas._on_tpu = lambda: True

    def shape_only(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))

    initializer.set_global_initializer(shape_only, shape_only)
    try:
        model = resolve(config["model_class"])(build_config(config))
    finally:
        initializer.set_global_initializer(None, None)
    model.eval()
    pools_fn = model.init_paged_cache
    model.init_paged_cache = lambda *a, **k: jax.eval_shape(
        lambda: pools_fn(*a, **k))
    eng = PagedContinuousBatchingEngine(model, **geometry)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=chip), tree)

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(tree))

    params = on_chip(eng.params)
    pools, pt = eng.caches
    pools, pt = on_chip(pools), on_chip(pt)
    print(json.dumps({
        "cell": cell_name, "max_batch": geometry["max_batch"],
        "params": sum(int(np.prod(v.shape)) for v in params.values()),
        "param_bytes": nbytes(params), "pool_bytes": nbytes(pools)}))

    def report(name, lowered):
        t = time.time()
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        print(json.dumps({
            "program": name, "compile_s": round(time.time() - t, 1),
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "custom_calls": compiled.as_text().count("tpu_custom_call")}),
            flush=True)

    i32 = lambda: jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)  # noqa
    if "segment" in args or not args:
        mb = eng.max_batch
        vec = lambda dt: jax.ShapeDtypeStruct((mb,), dt,  # noqa: E731
                                              sharding=chip)
        u32 = jax.ShapeDtypeStruct((), jnp.uint32, sharding=chip)
        steps = mix["server"]["segment_steps"]
        report(f"jit_segment x{steps}",
               eng._segment_fn(steps)._jitted.lower(
                   params, vec(jnp.int32), vec(jnp.int32), vec(bool),
                   vec(bool), on_chip(eng.samp), eng._bank(), (pools, pt),
                   u32, u32))
    buckets = [int(a) for a in args if a.isdigit()] or (
        [] if args else eng.prefill_buckets)
    for width in buckets:
        ids = jax.ShapeDtypeStruct((1, width), jnp.int32, sharding=chip)
        report(f"jit_prefill_one {width}", eng._prefill_paged._jitted.lower(
            params, ids, pools, pt, i32(), i32(), eng._bank(), i32()))


if __name__ == "__main__":
    main()
