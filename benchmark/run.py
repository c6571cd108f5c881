"""benchmark/run.py — one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell names a configuration
(``benchmark/configs/<config>.json``) and a traffic mix
(``benchmark/traffic/<mix>.json``); both, and every per-layer reader
(``benchmark/layers/<metric>.py``), are found BY NAME: this file lists
none of them and dispatches on the mix's ``kind`` only. The last line of
standard output is the contract's JSON object; earlier lines are
diagnostics, one JSON object each.

``--trace 0`` reports the cell's end-to-end metrics with no profiler and
no tracing; ``--trace 1`` runs the same window, wraps a few seconds of it
in the JAX profiler with ``paddle_tpu.tracing`` on, and reports the
cell's per-layer metrics.

Two modes that are not the driver's:

``--sweep r1,r2,...``  serving cells: set up once, run the window at each
                       offered rate, print one line per rate (completed
                       share, TTFT p90, queue depth through the window).
                       How the rate in a mix's file was found.
``--tiny``             lay ``benchmark/selfcheck/tiny.json`` over the
                       configuration and the mix and run on the CPU, to
                       rehearse the control flow. Refused on a TPU; prints
                       no metric.

It fails, printing no result, unless JAX's first device is a TPU and
there are as many chips as the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()      # set-up is counted from here

import argparse                    # noqa: E402
import dataclasses                 # noqa: E402
import importlib                   # noqa: E402
import importlib.util              # noqa: E402
import json                        # noqa: E402
import math                        # noqa: E402
import os                          # noqa: E402
import subprocess                  # noqa: E402
import sys                         # noqa: E402
import tempfile                    # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
sys.path.insert(0, ROOT)


def say(**kv) -> None:
    print(json.dumps(kv), flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = (overlay(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def resolve(spec: str):
    """``"package.module:name"`` -> the object."""
    mod, name = spec.split(":")
    return getattr(importlib.import_module(mod), name)


def load_module(path: str):
    """A file of the benchmark as a module, found by its path (a metric's
    name may hold dots, so it cannot be imported by name)."""
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, HERE))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def program_seed(seed: int) -> int:
    """The driver's seeds pass 2**31; the program's generators take an
    int32."""
    return seed % (2 ** 31 - 1)


# -- what every kind shares ----------------------------------------------------
class CompileCount:
    """Counts XLA compilations and persistent-cache loads in this process
    through ``jax.monitoring``: a program that was not ready before the
    window shows here whatever jitted it."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw) -> None:
        if "backend_compile" in event or "cache_retrieval" in event:
            self.n += 1


def build_config(config: dict):
    cls = resolve(config["config_class"])
    names = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in config.items() if k in names})


def device_record(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": max(peaks)}


def profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # device and runtime events only
    return opts


def per_layer(bench: dict, cell: str, ctx: dict) -> dict:
    """Run the reader of every per-layer metric this cell lists. A reader
    that finds nothing to read returns None and its metric is left out;
    one that fails is reported on a diagnostic line and left out. A trace
    with no device plane (a --tiny run on the CPU) has nothing for a
    ``device_trace`` metric to read."""
    from benchmark.lib import trace_reduce as tr

    on_device = bool(tr.device_planes(ctx["raw"]))
    out = {}
    for m in bench["per_layer"]:
        if not applies(m, cell) or (m["source"] == "device_trace"
                                    and not on_device):
            continue
        reader = load_module(os.path.join(HERE, "layers", m["name"] + ".py"))
        try:
            value = reader.read(ctx)
        except (KeyError, ValueError, ZeroDivisionError, IndexError) as e:
            say(phase="reader_error", metric=m["name"], error=repr(e))
            continue
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def finish(args, bench, cell, result: dict, end_to_end: dict, ctx: dict,
           trace_dir: str) -> dict:
    """The result's ``metrics``: with --trace 0 the cell's end-to-end
    metrics (units from BENCHMARK.json), with --trace 1 its per-layer
    metrics, the device's busy time and the breakdown."""
    from benchmark.lib import trace_reduce as tr

    if not args.trace:
        result["metrics"] = {
            m["name"]: {"value": end_to_end[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"] if applies(m, cell["name"])}
        return result
    raw = tr.load_xplane(tr.find_xplane(trace_dir))
    if args.dump_trace:                # for benchmark/selfcheck's fixture
        import gzip

        os.makedirs(os.path.dirname(os.path.abspath(args.dump_trace)),
                    exist_ok=True)
        with gzip.open(args.dump_trace, "wt") as f:
            json.dump(raw, f)
    result["metrics"] = per_layer(bench, cell["name"], dict(ctx, raw=raw))
    result["breakdown"] = {"device_ops": [], "idle_gaps": []}
    if tr.device_planes(raw):
        lo, hi = tr.window_ns(raw)
        result["device"].update(busy_s=tr.busy_ns(raw) / 1e9,
                                window_s=(hi - lo) / 1e9)
        result["breakdown"] = {"device_ops": tr.top_ops(raw, 10),
                               "idle_gaps": tr.idle_gaps(raw, 10)}
    return result


# -- kind: train -----------------------------------------------------------------
def run_train(args, bench, cell, config, mix, tmp) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from benchmark.lib import shapes, traffic
    from benchmark.lib.peaks import peaks

    compiles = CompileCount()
    cfg = build_config(config)
    tm = importlib.import_module(config["train_module"])
    batch, seq = mix["batch"], mix["seq"]

    t = time.monotonic()
    paddle.seed(program_seed(args.seed))
    model = resolve(config["model_class"])(cfg)
    params = {k: p.value for k, p in model.named_parameters()}
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    stacked, rest = tm.stack_params(params, cfg)
    del model, params                  # the stacked copy is the one trained
    step, init = tm.build_train_step(
        cfg, lr=mix["lr"], clip_norm=mix["clip_norm"], remat=mix["remat"],
        moment_dtype=getattr(jnp, mix["moment_dtype"])
        if mix.get("moment_dtype") else None)
    opt = init(stacked, rest)
    jax.block_until_ready(opt)
    weights_s = time.monotonic() - t

    def bench_train_step(stacked, rest, opt, ids, labels):
        return step(stacked, rest, opt, ids, labels)

    jitted = jax.jit(bench_train_step, donate_argnums=(0, 1, 2))

    def put(i):
        return jax.device_put(
            traffic.train_batch(args.seed, i, batch, seq, cfg.vocab_size))

    t = time.monotonic()
    for i in (-2, -1):                 # compile (or load), then one warm step
        stacked, rest, opt, loss = jitted(stacked, rest, opt, *put(i))
        float(loss)
    warm_s = time.monotonic() - t
    say(phase="setup", weights_s=weights_s, warm_s=warm_s, params=n_params,
        compiles=compiles.n)

    traced = range(2, 2 + int(mix["traced_steps"])) if args.trace else ()
    trace_dir = os.path.join(tmp, "trace")
    compiles0 = compiles.n
    losses, steps = [], 0
    nxt = put(0)
    t0 = time.monotonic()
    setup_s = t0 - T_PROCESS
    while True:
        if traced and steps == traced.start:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profiler_options())
        cur = nxt
        stacked, rest, opt, loss = jitted(stacked, rest, opt, *cur)
        nxt = put(steps + 1)           # the next batch, one step ahead
        losses.append(float(loss))     # the host read ends the step
        steps += 1
        if traced and steps == traced.stop:
            jax.profiler.stop_trace()
        now = time.monotonic()
        if now - t0 >= args.seconds and not (traced and steps < traced.stop):
            break
    elapsed = now - t0
    compiled_in_window = compiles.n - compiles0
    finite = [bool(math.isfinite(x)) for x in losses]
    device = device_record(cell["chips"])

    # the check, after the window, on the weights as trained: the moments
    # go first, so that the float32 reference fits beside the parameters
    del opt, nxt, cur
    chk = mix["check"]
    ids, _ = traffic.train_batch(args.seed, -3, 1, seq, cfg.vocab_size)
    last = int(chk["positions"])
    got = jax.jit(lambda s, r, i: tm.forward(s, r, i, cfg, remat=False)
                  [:, -last:].astype(jnp.float32))(stacked, rest, ids)
    reference = load_module(os.path.join(ROOT, config["reference"]))
    ref = reference.forward(reference.stacked_getter(stacked, rest), cfg, ids,
                            last=last)
    rel_rms = float(jnp.sqrt(jnp.mean((got - ref) ** 2))
                    / jnp.sqrt(jnp.mean(ref ** 2)))
    agree = float(jnp.mean((jnp.argmax(got, -1) == jnp.argmax(ref, -1))
                           .astype(jnp.float32)))
    say(phase="check", logits_rel_rms=rel_rms, tol=chk["rel_rms_tol"],
        argmax_agree=agree, positions=last, losses_first_last=[losses[0],
                                                               losses[-1]],
        compiled_in_window=compiled_in_window, steps=steps, elapsed_s=elapsed)
    correct = (all(finite) and compiled_in_window == 0
               and rel_rms <= chk["rel_rms_tol"])

    result = {"correct": bool(correct), "attempted": steps,
              "failed": finite.count(False), "device": device}
    ctx = {"spans": [], "config": config, "mix": mix,
           "peaks": None if args.tiny else peaks(device["kind"]),
           "run": {"train_flops_per_step":
                       shapes.train_flops_per_step(config, batch, seq),
                   "flash_flops_per_step":
                       shapes.flash_train_flops_per_step(config, batch, seq)}}
    return finish(args, bench, cell, result,
                  {"train_tokens_per_s": batch * seq * steps / elapsed,
                   "setup_s": setup_s}, ctx, trace_dir)


# -- kind: serve -----------------------------------------------------------------
def drive(tmp: str, tag: str, port: int, t0: float, requests: list,
          timeout_s: float):
    """Start the load generator (a child that imports only the standard
    library) on ``requests``; returns (process, path of its records)."""
    job, out = (os.path.join(tmp, f"{tag}.job.json"),
                os.path.join(tmp, f"{tag}.out.json"))
    with open(job, "w") as f:
        json.dump({"host": "127.0.0.1", "port": port, "t0": t0,
                   "timeout_s": timeout_s, "requests": requests}, f)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "lib", "client.py"), job, out],
        stdin=subprocess.DEVNULL)
    return proc, out


def collect(proc, out: str, timeout_s: float) -> list:
    try:
        rc = proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        raise RuntimeError("the load generator did not end in time")
    if rc != 0:
        raise RuntimeError(f"the load generator exited with {rc}")
    with open(out) as f:
        return json.load(f)["records"]


def window(srv, tmp, tag, port, requests, seconds, trace_at=None):
    """Offer ``requests`` (due in [0, seconds)), sample the queue while
    they are due, optionally trace a part of the window, wait for every
    one to finish. Returns (records, depth samples, t0)."""
    import jax

    t0 = time.monotonic() + 0.5
    proc, out = drive(tmp, tag, port, t0, requests, seconds + 150.0)
    depth, tracing_on, traced = [], False, False
    try:
        while True:
            now = time.monotonic() - t0
            if now >= seconds:
                break
            if trace_at and not traced and not tracing_on \
                    and now >= trace_at[0]:
                jax.profiler.start_trace(
                    os.path.join(tmp, "trace"),
                    profiler_options=profiler_options())
                tracing_on = True
            if tracing_on and now >= trace_at[1]:
                jax.profiler.stop_trace()
                tracing_on, traced = False, True
            if now >= 0:
                depth.append([round(now, 2), srv.queue.depth,
                              srv.num_active()])
            time.sleep(0.25)
        if tracing_on:                 # the traced part reached the close
            jax.profiler.stop_trace()
            tracing_on = False
        depth.append([round(time.monotonic() - t0, 2), srv.queue.depth,
                      srv.num_active()])
        records = collect(proc, out, 160.0)
    finally:
        if tracing_on:
            jax.profiler.stop_trace()
        if proc.poll() is None:        # never leave the child behind
            proc.kill()
            proc.wait()
    return records, depth, t0


def check_served(config, cfg, model, requests, records, chk) -> dict:
    """For a few sampled requests: the reference's float32 logits over
    prompt + served tokens must put every served token within
    ``logit_margin`` of the maximum at its position."""
    import jax.numpy as jnp

    ref = load_module(os.path.join(ROOT, config["reference"]))
    params = {k: p.value for k, p in model.named_parameters()}
    done = [r for r in records if r["ok"]]
    if not done:
        return {"worst_gap": math.inf, "checked": 0}
    pick = [done[0], done[len(done) // 2]][:int(chk["requests"])]
    worst, argmax_agree, total = 0.0, 0, 0
    for r in pick:
        prompt, served = requests[r["i"]]["prompt"], r["tokens"]
        ids = jnp.asarray([prompt + served[:-1]], jnp.int32)
        logits = ref.forward(params.__getitem__, cfg, ids,
                             last=len(served))[0]
        tok = jnp.asarray(served)
        gap = logits.max(-1) - jnp.take_along_axis(
            logits, tok[:, None], -1)[:, 0]
        worst = max(worst, float(gap.max()))
        argmax_agree += int((gap == 0).sum())
        total += len(served)
    return {"worst_gap": worst, "checked": len(pick),
            "tokens": total, "tokens_at_argmax": argmax_agree}


def run_serve(args, bench, cell, config, mix, tmp) -> dict:
    import jax

    import paddle_tpu as paddle
    from benchmark.lib import stats, traffic
    from paddle_tpu import monitor, serving
    from paddle_tpu import tracing as ptrace
    from paddle_tpu.inference.generation import \
        PagedContinuousBatchingEngine

    compiles = CompileCount()
    monitor.enable()                  # the jit miss counters need it
    cfg = build_config(config)
    t = time.monotonic()
    paddle.seed(program_seed(args.seed))
    model = resolve(config["model_class"])(cfg)
    model.eval()
    weights_s = time.monotonic() - t

    t = time.monotonic()
    eng = PagedContinuousBatchingEngine(model, **mix["engine"])
    srv = serving.Server(eng, warmup=True, **mix.get("server", {}))
    srv.wait_ready()
    if srv.status != "ok":
        raise RuntimeError(f"server is {srv.status!r} after start-up")
    httpd = serving.serve_http(srv)
    port = httpd.server_address[1]
    server_s = time.monotonic() - t
    try:
        # every program of the serving path once, through the front door
        t = time.monotonic()
        steps = srv.segment_steps
        warm = traffic.warmup_requests(mix, cfg.vocab_size, steps)
        proc, out = drive(tmp, "warm", port, time.monotonic(), warm, 300.0)
        bad = [r for r in collect(proc, out, 310.0) if not r["ok"]]
        if bad:
            raise RuntimeError(f"warm-up requests failed: {bad[:2]}")
        say(phase="setup", weights_s=weights_s, server_s=server_s,
            warm_round_s=time.monotonic() - t, compiles=compiles.n)

        if args.sweep:
            for rate in args.sweep:
                reqs = traffic.serve_schedule(mix, args.seed, args.seconds,
                                              cfg.vocab_size, rate=rate)
                recs, depth, _ = window(srv, tmp, f"sweep{rate}", port,
                                        reqs, args.seconds)
                third = max(1, len(depth) // 3)
                say(phase="sweep", rate_per_s=rate, offered=len(reqs),
                    completed_share=sum(r["ok"] for r in recs) / len(recs),
                    queue_depth_first_third=sum(
                        d[1] for d in depth[:third]) / third,
                    queue_depth_last_third=sum(
                        d[1] for d in depth[-third:]) / third,
                    queue_depth_at_close=depth[-1][1],
                    active_at_close=depth[-1][2],
                    lateness_ms=stats.lateness_ms(recs),
                    **stats.serve_metrics(recs))
            return None

        requests = traffic.serve_schedule(mix, args.seed, args.seconds,
                                          cfg.vocab_size)
        trace_at = None
        if args.trace:
            ptrace.enable()
            ptrace.clear()
            lo = 0.25 * args.seconds
            trace_at = (lo, min(lo + mix["traced_seconds"], args.seconds))
        compiles0, misses0 = compiles.n, monitor.jit_miss_by_fn()
        records, depth, t0 = window(srv, tmp, "run", port, requests,
                                    args.seconds, trace_at)
        setup_s = t0 - T_PROCESS
        misses1 = monitor.jit_miss_by_fn()
        new_misses = {k: v - misses0.get(k, 0) for k, v in misses1.items()
                      if v != misses0.get(k, 0)}
        compiled_in_window = compiles.n - compiles0
        spans = ptrace.events() if args.trace else []
        device = device_record(cell["chips"])
    finally:
        httpd.shutdown()
        httpd.server_close()
        srv.shutdown(drain=True, timeout=60.0)
        eng.close()

    n_ok = sum(r["ok"] for r in records)
    chk = check_served(config, cfg, model, requests, records, mix["check"])
    say(phase="check", **chk, margin=mix["check"]["logit_margin"],
        compiled_in_window=compiled_in_window, new_jit_misses=new_misses,
        requests=len(records), ok=n_ok,
        lateness_ms=stats.lateness_ms(records),
        ttft_ms={q: stats.percentile(stats.ttft_ms(records), q)
                 for q in (50, 90)},
        ttft_samples_beyond_p90=stats.beyond(len(records), 90),
        queue_depth_at_close=depth[-1][1], active_at_close=depth[-1][2],
        queue_depth_max=max(d[1] for d in depth),
        errors=[r["error"] or r["http"] for r in records if not r["ok"]][:3])
    correct = (n_ok == len(records) and compiled_in_window == 0
               and not new_misses and chk["checked"] > 0
               and chk["worst_gap"] <= mix["check"]["logit_margin"])
    result = {"correct": bool(correct), "attempted": len(records),
              "failed": len(records) - n_ok, "device": device}
    # a tail of failures is +inf; JSON has no such number
    end_to_end = {k: v if math.isfinite(v) else 1e12
                  for k, v in stats.serve_metrics(records).items()}
    ctx = {"spans": spans, "config": config, "mix": mix, "peaks": None,
           "run": {"segment_steps": steps}}
    return finish(args, bench, cell, result, dict(end_to_end, setup_s=setup_s),
                  ctx, os.path.join(tmp, "trace"))


KINDS = {"train": run_train, "serve": run_serve}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", type=lambda s: [float(x) for x in
                                               s.split(",")], default=None)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--dump-trace", default=None,
                    help="with --trace 1: also write the trace, as "
                         "lib/trace_reduce.py's plain dict, to this .json.gz")
    args = ap.parse_args(argv)

    bench = load_json("BENCHMARK.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(entry["file"])
    mix = load_json("benchmark", "traffic", cell["traffic"] + ".json")
    if args.tiny:
        tiny = load_json("benchmark", "selfcheck", "tiny.json")
        config = overlay(config, tiny["config"])
        over = dict(tiny[mix["kind"]])
        div = over.pop("len_divisor", None)
        mix = overlay(mix, over)
        if div:
            for key in ("prompt_len", "answer_len"):
                mix[key] = dict(mix[key],
                                lo=max(2, mix[key]["lo"] // div),
                                hi=max(4, mix[key]["hi"] // div))

    from paddle_tpu.device.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    import jax

    # every program into the persistent cache, the quick ones too, so that
    # a second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    platform = devs[0].platform
    if args.tiny:
        if platform == "tpu":
            raise SystemExit("--tiny is a CPU rehearsal; refused on a TPU")
    elif platform != "tpu":
        raise SystemExit(f"the benchmark measures a TPU; JAX found "
                         f"{platform!r} ({devs[0].device_kind})")
    if len(devs) < cell["chips"]:
        raise SystemExit(f"{cell['name']} needs {cell['chips']} chips; JAX "
                         f"found {len(devs)}")
    say(phase="start", workload=cell["name"], seed=args.seed,
        seconds=args.seconds, trace=args.trace, tiny=args.tiny,
        jax=jax.__version__, compile_cache=cache_dir,
        imports_s=time.monotonic() - T_PROCESS)

    with tempfile.TemporaryDirectory(prefix="bench_") as tmp:
        result = KINDS[mix["kind"]](args, bench, cell, config, mix, tmp)
    if result is None:                 # a sweep prints its own lines
        return 0
    if args.tiny:
        say(phase="tiny", note="CPU rehearsal: no metric is reported",
            correct=result["correct"], attempted=result["attempted"],
            failed=result["failed"], metrics=sorted(result["metrics"]))
        return 0
    order = ("correct", "attempted", "failed", "metrics", "device",
             "breakdown")
    print(json.dumps({k: result[k] for k in order if k in result}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
