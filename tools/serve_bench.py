#!/usr/bin/env python
"""Open-loop load generator for paddle_tpu.serving.

Drives a :class:`paddle_tpu.serving.Server` (in-process toy model by
default, or a remote HTTP endpoint via ``--url``) with Poisson arrivals
at ``--rate`` req/s and reports the serving-latency metrics PERF.md
defines:

- **TTFT** (time to first token): submit → first generated token at the
  client. Queueing + admission prefill + the first decode-segment share.
- **TPOT** (time per output token): (last token - first token) /
  (n_tokens - 1) per request — the steady decode cadence a streaming
  client observes.
- **throughput**: total generated tokens / wall time of the whole run.

OPEN loop: arrival times are drawn up front from the Poisson process
and each request is submitted at its scheduled time regardless of how
many are still in flight — closed-loop generators (wait-for-completion)
hide queueing collapse, which is exactly what the backpressure path
must be measured under. Rejected submissions (queue full) are counted,
not retried.

Usage::

    python tools/serve_bench.py --rate 16 --requests 64
    python tools/serve_bench.py --url http://127.0.0.1:8000 --rate 8
    python tools/serve_bench.py --monitor-out run.jsonl   # + monitor dump
    # bucketing A/B (PERF.md prefill-cost methodology): lognormal
    # prompt mix, report compiled prefill programs alongside TTFT/TPOT
    python tools/serve_bench.py --prompt-dist lognormal --prompt-len 4:96 \
        --warmup --prefill-chunk 32
    python tools/serve_bench.py --prompt-dist lognormal --prompt-len 4:96 \
        --prefill-buckets none
    # chaos soak (in-process only): inject seeded faults at the named
    # serving seams and report survival/restart/recovery numbers — the
    # fault-isolation acceptance run (README "Failure modes & recovery")
    python tools/serve_bench.py --fault-rate 0.1 --fault-site decode \
        --fault-kind engine --max-restarts 100
    # KV memory-pressure A/B (PERF.md utilization/throughput
    # methodology): same pool, reserved vs optimistic admission —
    # compare throughput + occupancy p50/p99 against the preemption
    # count and the preempted-request latency penalty
    python tools/serve_bench.py --num-pages 24 --admission-mode reserved
    python tools/serve_bench.py --num-pages 24 --admission-mode optimistic \
        --kv-watermark 0.9 --max-preemptions 10
    # automatic prefix caching A/B (PERF.md prefix-caching
    # methodology): every request shares a 64-token system prompt —
    # compare TTFT p50/p99, serve_kv_occupancy, and
    # serve_prefix_hit_rate / serve_prefill_tokens_saved across the
    # two runs
    python tools/serve_bench.py --shared-prefix-len 64 --cache-prefixes off
    python tools/serve_bench.py --shared-prefix-len 64 --cache-prefixes on
    # speculative-decoding A/B (PERF.md spec-serving methodology):
    # repetitive prompts (the accepting case) through the SAME load
    # three times — plain, host-mode spec, device-mode spec —
    # reporting serve_tpot_*_{plain,spec,specdev}, tokens/forward,
    # acceptance, serve_spec_host_syncs_per_token (0.0 on the device
    # arm) and serve_spec_mode_tpot_speedup (host/device)
    python tools/serve_bench.py --spec-ab --draft-k 6 --repeat-unit 4 \
        --prompt-len 16:24 --max-new 24 --warmup
    # fleet survival A/B (PERF.md fleet-survival methodology): the SAME
    # load + fault plan (kill replica 0 at t=2s) through 1 replica vs 3
    # — read serve_fleet_survival_rate, serve_failover_count,
    # serve_failover_latency_p99, serve_breaker_opens across the runs
    python tools/serve_bench.py --router --replicas 1 --kill-replica-at 2
    python tools/serve_bench.py --router --replicas 3 --kill-replica-at 2
    # cross-process fleet A/B (PERF.md cross-process-fleet
    # methodology): the SAME load
    # through one equal-silicon in-process server (2x pages/batch/
    # queue) vs a Router over 2 replica SUBPROCESSES speaking HTTP —
    # read serve_fleet_ttft_overhead / serve_fleet_tpot_overhead /
    # serve_fleet_throughput_ratio; add --kill-replica-at to SIGKILL a
    # replica process mid-run and watch failover replay + respawn
    python tools/serve_bench.py --fleet 2 --warmup
    python tools/serve_bench.py --fleet 2 --kill-replica-at 2
    # request-lifecycle tracing (PERF.md tracing methodology): capture
    # a Chrome-trace/Perfetto file of the whole run and report the
    # trace-derived TTFT decomposition (queue vs prefill vs gap share)
    python tools/serve_bench.py --trace-out /tmp/serve_trace.json --warmup
    # tracing-overhead A/B: IDENTICAL load twice — trace off then on —
    # reporting serve_tpot_* per arm plus serve_trace_tpot_overhead
    # (the "near-zero when disabled / cheap when on" claim, measured)
    python tools/serve_bench.py --trace-ab --warmup
    # quantized-KV A/B (PERF.md quantized-KV methodology): IDENTICAL
    # load through bf16 pools vs int8 pools AT EQUAL HBM (the int8 arm
    # gets 2x --num-pages) — compare serve_kv_occupancy_* (halved at
    # matched load = doubled capacity), serve_kv_quant_tpot_speedup,
    # serve_kv_quant_capacity_ratio, and the bounded-numerics records
    # serve_kv_quant_max_logit_div / serve_kv_quant_token_flips
    python tools/serve_bench.py --kv-ab --warmup
    python tools/serve_bench.py --kv-dtype int8   # single int8 run
    # multi-tenant LoRA (PERF.md multi-tenant-LoRA methodology): K
    # synthetic adapters hot-loaded into the engine's device bank,
    # each request drawn to one (uniform or zipf) — read
    # serve_lora_adapters_resident / serve_lora_mix_entropy, and A/B
    # the SAME pre-drawn load base-vs-LoRA for the per-token cost of
    # the batched-adapter gather (serve_lora_tpot_overhead)
    python tools/serve_bench.py --adapters 8 --adapter-dist zipf --warmup
    python tools/serve_bench.py --lora-ab --warmup   # K=0 vs K=8
    python tools/serve_bench.py --adapters 4 --tenant-quotas 2  # quotas

    # SLO/goodput capture (PERF.md SLO methodology): arm an SLOPolicy,
    # read serve_goodput + the digest-exact serve_slo_ttft_p99 /
    # serve_slo_tpot_p99 (per-tenant table on stdout; GET /stats is
    # the live equivalent) — and the off-vs-on recording overhead A/B
    python tools/serve_bench.py --slo-ttft 0.5 --slo-tpot 0.05 \
        --adapters 4 --adapter-dist zipf --warmup
    python tools/serve_bench.py --slo-ab --warmup

Output: one human table plus BENCH-shaped JSON records
(``{"metric": ..., "value": ..., "unit": ...}``) on stdout. Chaos runs
add ``serve_faults_injected`` / ``serve_requests_survived`` /
``serve_requests_failed`` / ``serve_restarts`` /
``serve_recovery_p{50,90,99}``.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time

# runnable as `python tools/serve_bench.py` from a checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _percentile(xs, q):
    if not xs:
        return float("nan")
    xs = sorted(xs)
    i = min(len(xs) - 1, int(round(q / 100.0 * (len(xs) - 1))))
    return xs[i]


class _Stats:
    def __init__(self):
        self.lock = threading.Lock()
        self.ttft = []
        self.tpot = []
        self.e2e = []
        self.e2e_preempted = []   # e2e of requests preempted >= once
        #                           (in-process mode only) — the
        #                           preemption latency penalty is the
        #                           mean gap vs the unpreempted ones
        self.e2e_failover = []    # e2e of requests that failed over to
        #                           another replica (--router mode) —
        #                           serve_failover_latency_p99 is the
        #                           tail a migrated request pays
        self.tokens = 0
        self.rejected = 0
        self.failed = 0
        self.shed = 0             # rejections with reason "shed" (the
        #                           burn-rate door, --overload-ab's
        #                           ctrlon arm) — a subset of rejected

    def record(self, ttft, tpot, e2e, n_tokens, preempted=False,
               failover=False):
        with self.lock:
            if ttft is not None:
                self.ttft.append(ttft)
            if tpot is not None:
                self.tpot.append(tpot)
            self.e2e.append(e2e)
            if preempted:
                self.e2e_preempted.append(e2e)
            if failover:
                self.e2e_failover.append(e2e)
            self.tokens += n_tokens

    def reject(self, shed=False):
        with self.lock:
            self.rejected += 1
            if shed:
                self.shed += 1

    def fail(self):
        with self.lock:
            self.failed += 1


def _drive_inproc(server, prompt, cfg, stats, tenant=None):
    from paddle_tpu.serving import RequestRejected

    t0 = time.monotonic()
    try:
        handle = server.submit(prompt, cfg, tenant=tenant)
    except RequestRejected as e:
        stats.reject(shed=getattr(e, "reason", None) == "shed")
        return
    first = last = None
    n = 0
    try:
        for _tok in handle.stream(timeout=120):
            now = time.monotonic()
            if first is None:
                first = now
            last = now
            n += 1
    except Exception:
        stats.fail()
        return
    if handle.status != "finished":
        stats.fail()
        return
    end = time.monotonic()
    stats.record(None if first is None else first - t0,
                 None if (n < 2 or first is None) else (last - first)
                 / (n - 1),
                 end - t0, n,
                 preempted=getattr(handle, "_preempts", 0) > 0,
                 failover=getattr(handle, "_failovers", 0) > 0)


def _drive_http(url, prompt, cfg_body, stats):
    import http.client
    from urllib.parse import urlsplit

    parts = urlsplit(url)
    t0 = time.monotonic()
    try:
        conn = http.client.HTTPConnection(parts.hostname, parts.port,
                                          timeout=120)
        body = dict(cfg_body)
        body["prompt"] = [int(t) for t in prompt]
        body["stream"] = True
        conn.request("POST", "/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status == 429 or resp.status == 503:
            stats.reject()
            return
        if resp.status != 200:
            stats.fail()
            return
        first = last = None
        n = 0
        ok = False
        while True:
            line = resp.readline()
            if not line:
                break
            rec = json.loads(line)
            if "token" in rec:
                now = time.monotonic()
                if first is None:
                    first = now
                last = now
                n += 1
            elif rec.get("done"):
                ok = rec.get("status") == "finished"
        conn.close()
    except Exception:
        stats.fail()
        return
    if not ok:
        stats.fail()
        return
    end = time.monotonic()
    stats.record(None if first is None else first - t0,
                 None if (n < 2 or first is None) else (last - first)
                 / (n - 1),
                 end - t0, n)


# the in-process toy preset's vocab: prompts are drawn BEFORE any
# server exists (so A/B arms replay identical load), and _run_arm
# asserts this against the model the server was actually built with —
# a drifting preset must fail loudly, not clamp token ids silently
_TOY_VOCAB = 256


def _toy_engine(args, speculative: bool = False):
    """Build one seeded toy engine from the CLI knobs — the ONE place
    the engine kwargs live, shared by the single-server and router
    builders (a knob added to one mode must not silently benchmark a
    differently-configured engine in the other). Returns
    (engine, vocab_size)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.generation import (
        PagedContinuousBatchingEngine)
    from paddle_tpu.models import LlamaForCausalLM, llama_config

    paddle.seed(0)
    cfg = llama_config(getattr(args, "preset", "tiny"),
                       num_hidden_layers=args.layers)
    model = LlamaForCausalLM(cfg)
    if args.prefill_buckets == "auto":
        buckets = "auto"
    elif args.prefill_buckets in ("none", "off"):
        buckets = None
    else:
        buckets = [int(x) for x in args.prefill_buckets.split(",")]
    eng = PagedContinuousBatchingEngine(
        model, max_batch=args.max_batch, num_pages=args.num_pages,
        page_size=args.page_size, max_pages=args.max_pages,
        prefill_buckets=buckets, prefill_chunk=args.prefill_chunk,
        admission_mode=args.admission_mode,
        kv_watermark=args.kv_watermark,
        prefix_cache=(args.cache_prefixes == "on"),
        kv_dtype=args.kv_dtype,
        draft_k=(args.draft_k if speculative else 0),
        spec_mode=getattr(args, "spec_mode", "host"),
        lora_capacity=args.adapters,
        lora_rank=args.lora_rank,
        lora_targets=tuple(t.strip()
                           for t in args.lora_targets.split(",")
                           if t.strip()),
        tp_degree=getattr(args, "tp", 1))
    return eng, cfg.vocab_size


def _toy_server_kwargs(args, max_restarts=None):
    """Server knobs from the CLI — shared by both builders."""
    slo_policy = None
    if (getattr(args, "slo_ttft", None) is not None
            or getattr(args, "slo_tpot", None) is not None):
        from paddle_tpu.monitor.slo import SLOPolicy

        slo_policy = SLOPolicy(ttft_p99_s=args.slo_ttft,
                               tpot_p99_s=args.slo_tpot)
    control_policy = None
    if getattr(args, "control_on", False):
        from paddle_tpu.serving import ControlPolicy

        # the ctrlon arm's plane: default ladder/shed thresholds, but
        # (a) shed_min_count scaled so only the HOT tenant (60% of the
        # mix) accumulates enough scored requests in the fast window
        # to shed — the thin-tenant guard keeps the 10% cold tenants
        # un-shed by construction (requests//8 sits between one cold
        # tenant's ~10% share and the hot tenant's 60%) — and (b) a
        # fast tick + short dwell so the plane reacts within a
        # seconds-long bench run
        control_policy = ControlPolicy(
            shed_min_count=max(8, args.requests // 8),
            tick_interval_s=0.1,
            rung_dwell_s=1.0)
    return dict(
        max_queue=args.max_queue, segment_steps=args.segment_steps,
        warmup=args.warmup,
        max_restarts=(args.max_restarts if max_restarts is None
                      else max_restarts),
        max_replays=args.max_replays,
        max_preemptions=args.max_preemptions,
        restart_backoff_s=args.restart_backoff,
        stall_timeout_s=args.stall_timeout,
        tenant_quotas=args.tenant_quotas,
        slo_policy=slo_policy,
        control_policy=control_policy)


def _build_toy_server(args, speculative: bool = False):
    from paddle_tpu.serving import Server

    eng, vocab = _toy_engine(args, speculative)
    plan = None
    if args.fault_rate > 0:
        from paddle_tpu.inference.generation import EngineFault
        from paddle_tpu.testing.faults import FaultPlan, FaultyEngine

        plan = FaultPlan()
        sites = [s.strip() for s in args.fault_site.split(",")
                 if s.strip()]
        if args.fault_kind == "request":
            from paddle_tpu.inference.generation import REQUEST_SITES
            batch_wide = [s for s in sites if s not in REQUEST_SITES]
            if batch_wide:
                # the scheduler escalates EVERY non-fatal fault at a
                # batch-wide seam to engine recovery (no single request
                # to pin it on) — a "request-kind" run there would
                # silently measure restarts, not containment
                print("warning: --fault-kind request at batch-wide "
                      f"site(s) {batch_wide} is escalated to engine "
                      "recovery; use admit/prefill/chunk to measure "
                      "per-request containment", file=sys.stderr)
        # engine-kind faults drive the supervised-recovery path;
        # request-kind ones (site-default classification) drive
        # per-request containment. A FACTORY, not an instance: every
        # injection over a long soak must raise a fresh exception
        exc = ((lambda: EngineFault("injected chaos fault"))
               if args.fault_kind == "engine" else None)
        plan.random_raises(sites, args.fault_rate, seed=args.seed,
                           exc=exc)
        eng = FaultyEngine(eng, plan)
    srv = Server(eng, speculative=speculative,
                 **_toy_server_kwargs(args))
    srv.wait_ready()   # warmup compiles are NOT part of the measured run
    return srv, vocab, plan


def _build_toy_router(args):
    """Fleet mode (--replicas N / --router): a Router over N in-process
    replica Servers built from one ReplicaSpec. Each replica gets its
    OWN seeded model (deterministic init -> bitwise-identical weights
    across the fleet, the property greedy failover parity rides on).
    With --kill-replica-at T, the FIRST build of replica 0 is wrapped
    in a FaultyEngine whose plan the timer kills mid-run; the
    supervisor's rebuild comes up clean. Returns
    (router, vocab, kill_fn)."""
    from paddle_tpu.serving import ReplicaSpec, Router
    from paddle_tpu.testing.faults import FaultPlan, FaultyEngine

    kill_plan = FaultPlan()
    builds = {"n": 0}
    vocab = {}

    def factory():
        i = builds["n"]
        builds["n"] += 1
        eng, vocab["size"] = _toy_engine(args)
        if i == 0 and args.kill_replica_at is not None:
            return FaultyEngine(eng, kill_plan)
        return eng

    spec = ReplicaSpec(factory, server_kwargs=_toy_server_kwargs(
        args,
        # a killed replica must DIE (the router absorbs it), not spin
        # its own restart budget against a permanent fault plan
        max_restarts=(0 if args.kill_replica_at is not None
                      else None)))
    router = Router(spec, replicas=args.replicas,
                    max_failovers=args.max_failovers,
                    breaker_threshold=args.breaker_threshold,
                    replica_backoff_s=args.replica_backoff,
                    monitor_interval_s=0.05)
    router.wait_ready()

    fired = {"kill": False}

    def kill_fn():
        fired["kill"] = True
        print(f"[chaos] killing replica 0 at t="
              f"{args.kill_replica_at}s", file=sys.stderr)
        kill_plan.kill("decode")

    kill_fn.fired = fired
    return router, vocab["size"], (
        kill_fn if args.kill_replica_at is not None else None)


def _build_fleet_router(args):
    """Cross-process fleet mode (--fleet N): a Router over N replica
    SUBPROCESSES (``python -m paddle_tpu.serving.remote``), each one
    an independently seeded engine at the base CLI knobs — the same
    deterministic-init property the in-process fleet rides on, so
    greedy failover replay stays bitwise-identical across processes.
    Only the knobs the replica entrypoint exposes are forwarded (main
    validates the rest are at defaults). With --kill-replica-at T, the
    timer SIGKILLs replica 0's process; the supervisor respawns it.
    Returns (router, vocab, kill_fn).

    CPU-only for now: the children inherit this process's whole
    environment, so on a TPU host each of the N would claim every chip
    (a chip belongs to one process at a time). A fleet on chips needs a
    per-child chip assignment this tool does not make yet."""
    from paddle_tpu.models import llama_config
    from paddle_tpu.serving import Router
    from paddle_tpu.serving.remote import RemoteReplicaSpec

    child = ["--preset", args.preset, "--layers", str(args.layers),
             "--max-batch", str(args.max_batch),
             "--num-pages", str(args.num_pages),
             "--page-size", str(args.page_size),
             "--max-pages", str(args.max_pages),
             "--kv-dtype", args.kv_dtype,
             "--max-queue", str(args.max_queue),
             "--segment-steps", str(args.segment_steps),
             "--prefix-cache", args.cache_prefixes,
             "--warmup", "on" if args.warmup else "off"]
    if args.prefill_chunk is not None:
        child += ["--prefill-chunk", str(args.prefill_chunk)]
    if args.slo_ttft is not None:
        child += ["--slo-ttft", str(args.slo_ttft)]
    if args.slo_tpot is not None:
        child += ["--slo-tpot", str(args.slo_tpot)]
    spec = RemoteReplicaSpec(
        args=child,
        # the children record their own SLO digests; the router MERGES
        # them over the wire — the serve_goodput/serve_slo_* records
        # below are fleet-exact, not averaged
        env={"FLAGS_enable_monitor": "1"})
    router = Router(spec, replicas=args.fleet,
                    max_failovers=args.max_failovers,
                    breaker_threshold=args.breaker_threshold,
                    replica_backoff_s=args.replica_backoff,
                    monitor_interval_s=0.05)
    router.wait_ready(timeout=240.0)

    fired = {"kill": False}

    def kill_fn():
        fired["kill"] = True
        print(f"[chaos] SIGKILL replica 0 process at t="
              f"{args.kill_replica_at}s", file=sys.stderr)
        victim = router._replicas[0].server
        if getattr(victim, "proc", None) is not None:
            victim.proc.kill()

    kill_fn.fired = fired
    vocab = llama_config(args.preset, num_hidden_layers=1).vocab_size
    return router, vocab, (
        kill_fn if args.kill_replica_at is not None else None)


def _draw_len(rng, dist: str, lo: int, hi: int) -> int:
    """One prompt length from the configured distribution. lognormal is
    the realistic serving shape (many short, a long tail) — the mix that
    exposes per-length prefill recompiles, which uniform draws over a
    narrow range can hide."""
    if dist == "lognormal":
        import math

        mu = (math.log(lo) + math.log(hi)) / 2.0
        sigma = max((math.log(hi) - math.log(lo)) / 4.0, 1e-6)
        return min(hi, max(lo, int(round(rng.lognormvariate(mu, sigma)))))
    return rng.randint(lo, hi)


def _prefill_program_stats():
    """Compiled-prefill-program counts + compile seconds from the live
    monitor registry (in-process mode): the bucketing win in numbers."""
    from paddle_tpu import monitor

    snap = monitor.snapshot()["metrics"]

    def by_fn(name):
        # sum per entry point: the counters carry ("fn", "program")
        # since the ledger split, and one fn compiles many programs
        out = {}
        for s in snap.get(name, {}).get("samples", []):
            fn = s["labels"]["fn"]
            out[fn] = out.get(fn, 0.0) + s["value"]
        return out

    misses = by_fn("paddle_tpu_jit_cache_miss_total")
    secs = by_fn("paddle_tpu_jit_compile_seconds_total")
    prefill_fns = ("cb_prefill", "cb_prefill_chunk")
    return (sum(int(misses.get(f, 0)) for f in prefill_fns),
            sum(secs.get(f, 0.0) for f in prefill_fns),
            sum(int(v) for v in misses.values()),
            sum(secs.values()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--url", default=None,
                    help="HTTP endpoint (default: in-process toy model)")
    ap.add_argument("--rate", type=float, default=8.0,
                    help="mean arrival rate, requests/s (Poisson)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--prompt-len", default="4:24", metavar="LO:HI",
                    help="prompt-length range")
    ap.add_argument("--prompt-dist", choices=("uniform", "lognormal"),
                    default="uniform",
                    help="prompt-length distribution over LO:HI "
                         "(lognormal = realistic many-short/long-tail "
                         "serving mix)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    # in-process toy engine knobs
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--preset", default="tiny",
                    help="llama_config preset for the in-process toy "
                         "engine (tiny default; 13b/65b are the "
                         "memory-fit configs a TP mesh exists to "
                         "serve — MEMORY_CONFIG3.json)")
    ap.add_argument("--tp", type=int, default=1, metavar="N",
                    help="tensor-parallel degree of the in-process "
                         "engine: weights + KV pools shard over an "
                         "N-device 'mp' mesh (CPU CI: force devices "
                         "with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--tp-ab", action="store_true",
                    help="A/B mode: run the SAME pre-drawn load "
                         "through a TP=1 engine then a TP=N engine "
                         "(N from --tp, default 2) and report "
                         "serve_tp_tpot_speedup + "
                         "serve_tp_max_model_bytes (the HBM capacity "
                         "a TP=N mesh adds at fixed per-chip memory)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--num-pages", type=int, default=48)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--max-pages", type=int, default=16)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--segment-steps", type=int, default=4)
    ap.add_argument("--prefill-buckets", default="auto",
                    metavar="auto|none|N,N,...",
                    help="prefill length buckets ('none' = exact-length "
                         "prefill, one compile per distinct length)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill chunk size (tokens); prompts "
                         "longer than this admit one chunk per gap")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-compile all prefill buckets + the segment "
                         "program before the measured run")
    # KV memory-pressure knobs (paged engine admission policy)
    ap.add_argument("--admission-mode", choices=("reserved",
                                                 "optimistic"),
                    default="reserved",
                    help="page-pool admission policy: reserved = "
                         "worst-case pages claimed up front (safe, "
                         "caps concurrency); optimistic = prompt + "
                         "one page, grow per gap, preempt-and-replay "
                         "under pressure (vLLM-style)")
    ap.add_argument("--kv-watermark", type=float, default=0.9,
                    help="optimistic mode: pause NEW admissions while "
                         "pool occupancy would exceed this fraction "
                         "(preemption stays the fallback, not the "
                         "steady state)")
    ap.add_argument("--max-preemptions", type=int, default=5,
                    help="memory-pressure preemptions one request may "
                         "absorb before it fails with "
                         "PreemptionBudgetExceeded")
    # prefix-cache A/B knobs (PERF.md prefix-caching methodology)
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    metavar="N",
                    help="prepend the SAME N seeded tokens to every "
                         "prompt (a shared system prompt); the "
                         "per-request tail still draws from "
                         "--prompt-len. A/B this against "
                         "--cache-prefixes on|off")
    ap.add_argument("--cache-prefixes", choices=("on", "off"),
                    default="off",
                    help="enable the paged engine's automatic prefix "
                         "cache (refcounted copy-on-write shared KV "
                         "pages): warm admissions map resident prompt "
                         "blocks instead of re-prefilling them")
    # speculative-decoding knobs (in-process mode; PERF.md spec-serving
    # methodology)
    ap.add_argument("--speculative", choices=("on", "off"),
                    default="off",
                    help="serve every greedy request speculatively "
                         "(per-slot n-gram proposers verified inside "
                         "the one widened decode-segment program)")
    ap.add_argument("--draft-k", type=int, default=6,
                    help="draft window (tokens proposed per verify "
                         "forward) when speculation is on")
    ap.add_argument("--spec-mode", choices=("host", "device"),
                    default="host",
                    help="where drafts come from when speculation is "
                         "on: 'host' round-trips the n-gram proposer "
                         "every verify step, 'device' runs the fused "
                         "propose+verify+accept segment program (one "
                         "host readback per SEGMENT)")
    ap.add_argument("--spec-ab", action="store_true",
                    help="A/B mode: run the SAME load three times — "
                         "plain, host-mode speculative, device-mode "
                         "speculative — and report serve_tpot_* per "
                         "arm plus the spec and host/device speedup "
                         "ratios")
    ap.add_argument("--repeat-unit", type=int, default=0, metavar="N",
                    help="build each prompt by tiling a seeded N-token "
                         "unit (self-repetitive text — the n-gram "
                         "proposer's accepting case; 0 = fully random "
                         "prompts, the adversarial floor)")
    # fleet knobs (--replicas N routes through paddle_tpu.serving.Router;
    # PERF.md fleet-survival methodology)
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica Servers behind a health-aware "
                         "Router (>1 implies --router)")
    ap.add_argument("--router", action="store_true",
                    help="route through a Router even with 1 replica "
                         "(measures the router's own overhead + the "
                         "no-spare-capacity fault baseline)")
    ap.add_argument("--kill-replica-at", type=float, default=None,
                    metavar="T",
                    help="kill replica 0 (permanent engine faults) T "
                         "seconds into the measured run; its requests "
                         "fail over, the supervisor rebuilds it "
                         "(--fleet mode: SIGKILLs the replica "
                         "PROCESS; the supervisor respawns it)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="cross-process A/B: run the SAME pre-drawn "
                         "load through (a) ONE in-process server with "
                         "N x --num-pages / N x --max-batch / N x "
                         "--max-queue (the equal-chip monolithic "
                         "baseline) then (b) a Router over N replica "
                         "SUBPROCESSES (paddle_tpu.serving.remote, "
                         "one engine each at the base knobs) — "
                         "reports per-arm serve_ttft/tpot/throughput "
                         "plus serve_fleet_* ratios, the price of the "
                         "HTTP hop + fan-out at equal silicon")
    ap.add_argument("--max-failovers", type=int, default=3,
                    help="replica migrations one request may survive "
                         "before FailoverBudgetExceeded")
    ap.add_argument("--breaker-threshold", type=int, default=3,
                    help="consecutive failures before a replica's "
                         "circuit breaker opens")
    ap.add_argument("--replica-backoff", type=float, default=0.25,
                    help="base of the supervisor's exponential "
                         "replica-restart backoff (s)")
    # chaos knobs (in-process mode only; paddle_tpu.testing.faults)
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="seeded per-call fault probability at each "
                         "--fault-site seam (0 = chaos off)")
    ap.add_argument("--fault-site", default="decode",
                    metavar="SITE[,SITE...]",
                    help="injection seams: admit, prefill, chunk, "
                         "decode, collect")
    ap.add_argument("--fault-kind", choices=("request", "engine"),
                    default="engine",
                    help="engine = EngineFault (supervised restart + "
                         "replay); request = site-default "
                         "classification (per-request containment at "
                         "admission seams)")
    ap.add_argument("--max-restarts", type=int, default=8,
                    help="server lifetime engine-restart budget")
    ap.add_argument("--max-replays", type=int, default=8,
                    help="per-request replay budget across restarts "
                         "(the Server default of 2 would fail "
                         "long-lived requests on a long soak and "
                         "corrupt the survival numbers)")
    ap.add_argument("--restart-backoff", type=float, default=0.01,
                    help="base of the exponential restart backoff (s)")
    ap.add_argument("--stall-timeout", type=float, default=None,
                    help="arm the stall watchdog (s; default off)")
    ap.add_argument("--monitor-out", default=None, metavar="JSONL",
                    help="also dump the in-process monitor registry "
                         "(in-process mode only)")
    # request-lifecycle tracing knobs (paddle_tpu.tracing; in-process)
    ap.add_argument("--trace-out", default=None, metavar="JSON",
                    help="enable FLAGS_enable_trace for the run and "
                         "write the Chrome-trace/Perfetto JSON of the "
                         "whole run here (also reports the "
                         "trace-derived TTFT decomposition records)")
    ap.add_argument("--trace-ab", action="store_true",
                    help="A/B mode: run the SAME load twice — tracing "
                         "off then on — and report serve_tpot_* per "
                         "arm plus serve_trace_tpot_overhead (the "
                         "tracing-overhead record PERF.md quotes)")
    # quantized-KV knobs (paged engine int8 pages, quantization.kv)
    ap.add_argument("--kv-dtype", choices=("bf16", "int8"),
                    default="bf16",
                    help="KV page storage dtype: int8 halves decode "
                         "read bytes and doubles pages at fixed HBM "
                         "(bounded, not bitwise, numerics)")
    ap.add_argument("--kv-ab", action="store_true",
                    help="A/B mode: run the SAME load twice — bf16 "
                         "pools, then int8 pools with --num-pages "
                         "DOUBLED (equal HBM) — and report per-arm "
                         "records plus serve_kv_quant_tpot_speedup, "
                         "serve_kv_quant_capacity_ratio and the "
                         "bounded-numerics divergence probe")
    # multi-tenant LoRA knobs (in-process single-server mode;
    # paddle_tpu.serving.adapters)
    ap.add_argument("--adapters", type=int, default=0, metavar="K",
                    help="hot-load K seeded synthetic LoRA adapters "
                         "and draw every request's adapter from them "
                         "(0 = base model only)")
    ap.add_argument("--adapter-dist", choices=("uniform", "zipf"),
                    default="uniform",
                    help="per-request adapter draw: uniform, or zipf "
                         "(s=1.1 — the realistic many-tenants shape: "
                         "a few hot fine-tunes, a long cold tail)")
    ap.add_argument("--lora-rank", type=int, default=4,
                    help="bank rank of the synthetic adapters")
    ap.add_argument("--lora-targets", default="q,v",
                    help="comma-separated LoRA target projections "
                         "(subset of q,k,v,o,gate,up,down)")
    ap.add_argument("--tenant-quotas", type=int, default=None,
                    metavar="N",
                    help="cap every tenant (= adapter) at N "
                         "concurrently admitted requests; a tenant "
                         "over quota defers without starving others")
    # SLO/goodput knobs (paddle_tpu.monitor.slo; in-process modes)
    ap.add_argument("--slo-ttft", type=float, default=None,
                    metavar="S",
                    help="per-request TTFT SLO threshold (s): arms an "
                         "SLOPolicy on the server(s) and reports "
                         "serve_goodput + the digest-exact "
                         "serve_slo_ttft_p99/serve_slo_tpot_p99")
    ap.add_argument("--slo-tpot", type=float, default=None,
                    metavar="S",
                    help="per-request TPOT SLO threshold (s); see "
                         "--slo-ttft")
    ap.add_argument("--slo-ab", action="store_true",
                    help="A/B mode: run the SAME pre-drawn load twice "
                         "— monitor+SLO recording OFF, then ON with "
                         "the --slo-ttft/--slo-tpot policy (defaults "
                         "1.0/0.25 s if unset) — and report "
                         "serve_slo_tpot_overhead (the PR 8 bar: "
                         "<= 1.02x, near-zero when off)")
    ap.add_argument("--lora-ab", action="store_true",
                    help="A/B mode: run the SAME pre-drawn load twice "
                         "— base model (K=0) then K adapters (default "
                         "8) — and report serve_lora_tpot_overhead "
                         "(the per-token price of the batched-adapter "
                         "gather)")
    # program-ledger knobs (paddle_tpu.monitor.ledger; in-process)
    ap.add_argument("--profile", action="store_true",
                    help="enable the program ledger "
                         "(FLAGS_enable_ledger) for the run and print "
                         "the per-program roofline table (dispatches, "
                         "compiles, FLOPs, MFU, memory/compute-bound "
                         "verdict) after the load drains")
    ap.add_argument("--profile-out", default=None, metavar="PATH",
                    help="also write the raw /profile JSON snapshot "
                         "(the Server.profile() shard) to PATH — feed "
                         "it to tools/monitor_report.py --profile or "
                         "archive it next to the BENCH records")
    ap.add_argument("--profile-ab", action="store_true",
                    help="A/B mode: run the SAME pre-drawn load twice "
                         "— ledger OFF, then ON — and report "
                         "serve_profile_tpot_overhead (the PR 15 "
                         "one-bool-branch bar: <= 1.05x)")
    # overload control plane knobs (paddle_tpu.serving.control)
    ap.add_argument("--overload-ab", action="store_true",
                    help="A/B mode: three arms on pre-drawn load with "
                         "a 60%%-hot tenant mix — 'cap' at --rate (the "
                         "at-capacity baseline), then 'ctrloff'/"
                         "'ctrlon' replaying the IDENTICAL load at "
                         "--overload-factor x that rate without/with "
                         "the SLO-driven control plane "
                         "(Server(control_policy=...)) — and report "
                         "serve_goodput_* per arm plus the cold-"
                         "tenant goodput retention verdict (the "
                         "overload bar: ctrlon cold goodput within "
                         "10%% of cap while the hot tenant sheds)")
    ap.add_argument("--overload-factor", type=float, default=2.0,
                    metavar="X",
                    help="overload multiple for the ctrloff/ctrlon "
                         "arms: arrival times are the cap arm's "
                         "schedule compressed by X (> 1; default 2.0)")
    ap.add_argument("--wire-chaos", action="store_true",
                    help="A/B mode over the REAL wire (serve_http + "
                         "RemoteReplica): 'wireclean' drives the "
                         "pre-drawn load unfaulted, 'wirechaos' "
                         "replays it through injected delay/drop/"
                         "half-close/corrupt at the generate and "
                         "kv_import seams — reports serve_wire_"
                         "resumes/failovers/reships/integrity_rejects"
                         "/survival_rate and the bitwise token-parity "
                         "verdict (a flaky network degrades latency, "
                         "never correctness)")
    args = ap.parse_args(argv)

    rng = random.Random(args.seed)
    lo, hi = (int(x) for x in args.prompt_len.split(":"))
    if args.url is not None and (args.fault_rate > 0 or args.spec_ab
                                 or args.speculative == "on"
                                 or args.trace_out or args.trace_ab
                                 or args.slo_ab
                                 or args.slo_ttft is not None
                                 or args.slo_tpot is not None):
        print("--fault-rate/--speculative/--spec-ab/--trace-out/"
              "--trace-ab/--slo-* need the in-process engine "
              "(no --url)", file=sys.stderr)
        return 2
    if sum([args.spec_ab, args.trace_ab, args.kv_ab,
            args.lora_ab, args.tp_ab, args.slo_ab,
            args.profile_ab, args.overload_ab,
            args.wire_chaos]) > 1:
        print("--spec-ab/--trace-ab/--kv-ab/--lora-ab/--tp-ab/--slo-ab/"
              "--profile-ab/--overload-ab/--wire-chaos are separate "
              "A/Bs; run them one at a time", file=sys.stderr)
        return 2
    if args.wire_chaos and (args.url is not None or args.router
                            or args.replicas > 1 or args.fleet
                            or args.fault_rate > 0):
        print("--wire-chaos builds its own wire (in-process servers "
              "behind serve_http); it composes with neither --url "
              "nor --router/--replicas/--fleet/--fault-rate",
              file=sys.stderr)
        return 2
    if (args.profile or args.profile_ab) and args.url is not None:
        print("--profile/--profile-ab need the in-process engine "
              "(no --url)", file=sys.stderr)
        return 2
    if args.slo_ab and args.slo_ttft is None and args.slo_tpot is None:
        # the on arm needs thresholds to score against; generous
        # defaults keep the A/B about RECORDING cost, not miss churn
        args.slo_ttft, args.slo_tpot = 1.0, 0.25
    if args.overload_ab:
        if (args.url is not None or args.router or args.replicas > 1
                or args.fleet):
            print("--overload-ab needs the single in-process engine "
                  "(no --url, no --router/--replicas/--fleet)",
                  file=sys.stderr)
            return 2
        if args.overload_factor <= 1.0:
            print("--overload-factor must be > 1", file=sys.stderr)
            return 2
        if args.slo_ttft is None and args.slo_tpot is None:
            # goodput/burn need a policy; TTFT is the queue-sensitive
            # dimension overload actually moves — TPOT stays off so a
            # big-batch cap arm does not pollute the baseline
            args.slo_ttft = 1.0
    if args.tp < 1:
        print("--tp must be >= 1", file=sys.stderr)
        return 2
    if args.tp_ab and (args.url is not None or args.router
                       or args.replicas > 1):
        print("--tp-ab needs the single in-process engine (no --url, "
              "no --router/--replicas)", file=sys.stderr)
        return 2
    if args.kv_ab and (args.url is not None or args.router
                       or args.replicas > 1):
        print("--kv-ab needs the single in-process engine (no --url, "
              "no --router/--replicas)", file=sys.stderr)
        return 2
    if args.replicas < 1:
        print("--replicas must be >= 1", file=sys.stderr)
        return 2
    if args.fleet < 0:
        print("--fleet must be >= 1 (0 = off)", file=sys.stderr)
        return 2
    if args.fleet:
        # the fleet arm's engines live in CHILD processes: the local
        # chaos/trace/ledger/adapter machinery cannot reach them, and
        # the replica entrypoint exposes the core engine knobs only
        if (args.url is not None or args.router or args.replicas > 1
                or args.fault_rate > 0 or args.speculative == "on"
                or args.adapters or args.tp > 1 or args.trace_out
                or args.profile
                or sum([args.spec_ab, args.trace_ab, args.kv_ab,
                        args.lora_ab, args.tp_ab, args.slo_ab,
                        args.profile_ab, args.overload_ab])):
            print("--fleet is its own A/B over subprocess replicas; "
                  "it composes with the load/engine-size/SLO knobs "
                  "only (no --url/--router/--replicas/--fault-rate/"
                  "--speculative/--adapters/--tp/--trace-out/"
                  "--profile/other --*-ab)", file=sys.stderr)
            return 2
    args.router = args.router or args.replicas > 1
    if args.router and (args.url is not None or args.fault_rate > 0
                        or args.spec_ab or args.speculative == "on"):
        print("--replicas/--router is in-process and drives its own "
              "chaos (--kill-replica-at); it composes with neither "
              "--url nor --fault-rate/--spec-ab/--speculative",
              file=sys.stderr)
        return 2
    if (args.kill_replica_at is not None and not args.router
            and not args.fleet):
        print("--kill-replica-at needs --router/--replicas > 1 "
              "or --fleet", file=sys.stderr)
        return 2
    if (args.adapters or args.lora_ab) and (args.url is not None
                                            or args.router):
        print("--adapters/--lora-ab need the single in-process engine "
              "(no --url, no --router/--replicas)", file=sys.stderr)
        return 2
    if args.adapters < 0:
        print("--adapters must be >= 0", file=sys.stderr)
        return 2

    # open loop: the full arrival schedule AND every prompt are drawn
    # BEFORE any server exists, so the --spec-ab arms replay IDENTICAL
    # load
    arrivals, t = [], 0.0
    for _ in range(args.requests):
        t += rng.expovariate(args.rate)
        arrivals.append(t)
    vocab = _TOY_VOCAB     # asserted against the model in _run_arm
    # the shared system prompt is drawn ONCE (seeded) so every request
    # carries an identical N-token head — the prefix-cache A/B's load
    # shape; the per-request tail keeps the configured distribution
    shared_prefix = [rng.randrange(vocab)
                     for _ in range(args.shared_prefix_len)]

    def _body(n):
        # --repeat-unit: self-repetitive prompt bodies (the n-gram
        # proposer's accepting case); each prompt tiles its OWN seeded
        # unit so prompts stay distinct across requests
        if args.repeat_unit > 0 and n > 0:
            u = [rng.randrange(vocab)
                 for _ in range(min(args.repeat_unit, n))]
            return (u * (n // len(u) + 1))[:n]
        return [rng.randrange(vocab) for _ in range(n)]

    prompts = [shared_prefix
               + _body(_draw_len(rng, args.prompt_dist, lo, hi))
               for _ in range(args.requests)]
    if args.wire_chaos:
        return _wire_chaos(args, prompts)
    # the per-request ADAPTER assignment is drawn up front too: the
    # --lora-ab arms replay the identical mix (the base arm just
    # ignores it), and the mix entropy record describes the LOAD, not
    # one arm's sampling
    n_adapters = args.adapters
    if args.lora_ab and n_adapters == 0:
        n_adapters = 8          # the PERF.md reference A/B: K=0 vs K=8
    if n_adapters:
        wts = ([1.0 / (j + 1) ** 1.1 for j in range(n_adapters)]
               if args.adapter_dist == "zipf" else None)
        assign = rng.choices([f"ad{j}" for j in range(n_adapters)],
                             weights=wts, k=args.requests)
    else:
        assign = [None] * args.requests
    # the per-request TENANT assignment for --overload-ab is drawn up
    # front too: all three arms replay the identical 60%-hot mix (one
    # hot tenant, four 10% cold ones), so the cold-goodput verdict
    # compares the SAME cold requests across arms
    tenants = [None] * args.requests
    if args.overload_ab:
        tenants = rng.choices(["hot", "c0", "c1", "c2", "c3"],
                              weights=[0.6, 0.1, 0.1, 0.1, 0.1],
                              k=args.requests)

    spec_def = args.speculative == "on"
    trace_def = args.trace_out is not None
    if args.spec_ab:
        # three arms on the identical pre-drawn load: "spec" is pinned
        # to host-mode drafting (the arm name existing baselines key
        # on), "specdev" runs the fused device-resident program
        arms = [("plain", False, trace_def), ("spec", True, trace_def),
                ("specdev", True, trace_def)]
    elif args.trace_ab:
        arms = [("traceoff", spec_def, False),
                ("traceon", spec_def, True)]
    elif args.kv_ab:
        arms = [("bf16", spec_def, trace_def),
                ("int8", spec_def, trace_def)]
    elif args.lora_ab:
        arms = [("base", spec_def, trace_def),
                ("lora", spec_def, trace_def)]
    elif args.slo_ab:
        arms = [("slooff", spec_def, trace_def),
                ("sloon", spec_def, trace_def)]
    elif args.profile_ab:
        arms = [("ledgeroff", spec_def, trace_def),
                ("ledgeron", spec_def, trace_def)]
    elif args.overload_ab:
        arms = [("cap", spec_def, trace_def),
                ("ctrloff", spec_def, trace_def),
                ("ctrlon", spec_def, trace_def)]
    elif args.tp_ab:
        tp_n = args.tp if args.tp > 1 else 2
        arms = [("tp1", spec_def, trace_def),
                (f"tp{tp_n}", spec_def, trace_def)]
    elif args.fleet:
        arms = [("mono", spec_def, trace_def),
                ("fleet", spec_def, trace_def)]
    else:
        arms = [("", spec_def, trace_def)]
    res = {}
    for arm, spec_on, trace_on in arms:
        arm_args = args
        if args.spec_ab:
            arm_args = argparse.Namespace(**vars(args))
            arm_args.spec_mode = ("device" if arm == "specdev"
                                  else "host")
        if args.kv_ab:
            # EQUAL HBM across the arms: int8 pages cost half the
            # bytes, so the int8 pool gets twice the pages — the
            # capacity half of the quantization win, visible as
            # halved serve_kv_occupancy at matched load
            arm_args = argparse.Namespace(**vars(args))
            arm_args.kv_dtype = arm
            if arm == "int8":
                arm_args.num_pages = 2 * args.num_pages
        if args.lora_ab:
            arm_args = argparse.Namespace(**vars(args))
            arm_args.adapters = 0 if arm == "base" else n_adapters
        if args.tp_ab:
            arm_args = argparse.Namespace(**vars(args))
            arm_args.tp = 1 if arm == "tp1" else tp_n
        if args.fleet:
            # EQUAL SILICON across the arms: the fleet arm holds N
            # engines of the base size in N processes; the monolithic
            # baseline gets the same total pool/batch/queue in ONE —
            # the per-chip memory wall is exactly what it does NOT
            # model, which is the fleet's whole reason to exist
            arm_args = argparse.Namespace(**vars(args))
            if arm == "mono":
                arm_args.fleet = 0
                arm_args.num_pages = args.num_pages * args.fleet
                arm_args.max_batch = args.max_batch * args.fleet
                arm_args.max_queue = args.max_queue * args.fleet
            else:
                arm_args.router = True   # fleet accounting in _run_arm
        if args.profile_ab:
            # the OFF arm is the disabled path the one-bool-branch
            # discipline promises is free; the ON arm pays the
            # signature-lookup + digest-observe cost being measured
            arm_args = argparse.Namespace(**vars(args))
            arm_args.profile = arm == "ledgeron"
        mon_on = True
        if args.slo_ab and arm == "slooff":
            # the OFF arm is the disabled path the PR 1/8 bar promises
            # is near-zero: FLAGS_enable_monitor off, no policy — the
            # serving seams pay one bool branch each
            arm_args = argparse.Namespace(**vars(args))
            arm_args.slo_ttft = arm_args.slo_tpot = None
            mon_on = False
        arm_arrivals = arrivals
        if args.overload_ab:
            # ctrloff/ctrlon replay the cap arm's schedule compressed
            # by --overload-factor: the IDENTICAL requests arrive at
            # 2x the at-capacity rate — the only knob that differs
            # between the overload arms is the control plane itself
            arm_args = argparse.Namespace(**vars(args))
            arm_args.control_on = arm == "ctrlon"
            if arm != "cap":
                arm_arrivals = [t / args.overload_factor
                                for t in arrivals]
        res[arm] = _run_arm(arm_args, arm, spec_on, trace_on, prompts,
                            arm_arrivals, assign, mon_on=mon_on,
                            tenants=tenants)
    if args.trace_ab:
        # the overhead verdict: decode cadence with the recorder on vs
        # off, on identical replayed load — the number that justifies
        # leaving tracing available in production serving
        a, b = res["traceoff"], res["traceon"]
        if a.get("tpot_p50") and b.get("tpot_p50"):
            print(json.dumps({"metric": "serve_trace_tpot_overhead",
                              "value": round(b["tpot_p50"]
                                             / a["tpot_p50"], 3),
                              "unit": "x (on/off)"}))
        if a.get("throughput") and b.get("throughput"):
            print(json.dumps(
                {"metric": "serve_trace_throughput_ratio",
                 "value": round(b["throughput"] / a["throughput"], 3),
                 "unit": "x (on/off)"}))
    if args.profile_ab:
        # the overhead verdict: decode cadence with the program ledger
        # on vs off, on identical replayed load — per dispatch the on
        # path pays one arg-signature tuple + dict hit + digest
        # observe; the bar is <= 1.05x (ISSUE 16 acceptance)
        a, b = res["ledgeroff"], res["ledgeron"]
        if a.get("tpot_p50") and b.get("tpot_p50"):
            print(json.dumps({"metric": "serve_profile_tpot_overhead",
                              "value": round(b["tpot_p50"]
                                             / a["tpot_p50"], 3),
                              "unit": "x (on/off)"}))
        if a.get("throughput") and b.get("throughput"):
            print(json.dumps(
                {"metric": "serve_profile_throughput_ratio",
                 "value": round(b["throughput"] / a["throughput"], 3),
                 "unit": "x (on/off)"}))
    if args.slo_ab:
        # the overhead verdict: decode cadence with the monitor + SLO
        # recording path on vs fully off, on identical replayed load —
        # the number that justifies leaving SLO scoring on in
        # production serving (PR 8 precedent: <= 1.02x is the bar)
        a, b = res["slooff"], res["sloon"]
        if a.get("tpot_p50") and b.get("tpot_p50"):
            print(json.dumps({"metric": "serve_slo_tpot_overhead",
                              "value": round(b["tpot_p50"]
                                             / a["tpot_p50"], 3),
                              "unit": "x (on/off)"}))
        if a.get("throughput") and b.get("throughput"):
            print(json.dumps(
                {"metric": "serve_slo_throughput_ratio",
                 "value": round(b["throughput"] / a["throughput"], 3),
                 "unit": "x (on/off)"}))
    if args.overload_ab:
        # the overload verdict (ISSUE 19 acceptance): under identical
        # 2x-capacity load, the control plane sheds the HOT tenant at
        # the door and the COLD tenants keep (>= 90% of) the
        # at-capacity goodput they had before the overload; without
        # it, the queue backs up and goodput collapses for everyone.
        # This prices the MECHANISM (admission-door discrimination),
        # not a speedup — no arm decodes any faster than another.
        cap, off, on = res["cap"], res["ctrloff"], res["ctrlon"]
        for name, a in (("ctrloff", off), ("ctrlon", on)):
            if cap.get("cold_goodput") and a.get("cold_goodput") \
                    is not None:
                print(json.dumps(
                    {"metric": f"serve_overload_cold_retention_{name}",
                     "value": round(a["cold_goodput"]
                                    / cap["cold_goodput"], 4),
                     "unit": f"x ({name}/cap cold goodput)"}))
        print(json.dumps({"metric": "serve_overload_factor",
                          "value": args.overload_factor,
                          "unit": "x capacity"}))
        if cap.get("cold_goodput") and on.get("cold_goodput") \
                is not None and off.get("cold_goodput") is not None:
            ret_on = on["cold_goodput"] / cap["cold_goodput"]
            ret_off = off["cold_goodput"] / cap["cold_goodput"]
            verdict = ("PASS" if ret_on >= 0.9 and on.get("sheds", 0)
                       else "FAIL")
            print(f"overload verdict: {verdict} — ctrlon cold-tenant "
                  f"goodput retention {ret_on:.3f} (bar >= 0.9, "
                  f"{on.get('sheds', 0)} hot sheds) vs ctrloff "
                  f"{ret_off:.3f}")
    if args.spec_ab:
        # the A/B verdict: decode cadence and throughput, spec over
        # plain, on the identical replayed load
        a, b = res["plain"], res["spec"]
        if a.get("tpot_p50") and b.get("tpot_p50"):
            print(json.dumps({"metric": "serve_spec_tpot_p50_speedup",
                              "value": round(a["tpot_p50"]
                                             / b["tpot_p50"], 3),
                              "unit": "x (plain/spec)"}))
        if a.get("throughput") and b.get("throughput"):
            print(json.dumps(
                {"metric": "serve_spec_throughput_speedup",
                 "value": round(b["throughput"] / a["throughput"], 3),
                 "unit": "x (spec/plain)"}))
        # the host-vs-device verdict: same drafts, same acceptance —
        # the ratio isolates what the per-step proposer round-trip
        # costs (on CPU-tiny it prices the MECHANISM; on-chip the
        # eliminated syncs are the latency frontier — see PERF.md)
        d = res["specdev"]
        if b.get("tpot_p50") and d.get("tpot_p50"):
            print(json.dumps({"metric": "serve_spec_mode_tpot_speedup",
                              "value": round(b["tpot_p50"]
                                             / d["tpot_p50"], 3),
                              "unit": "x (host/device)"}))
    if args.lora_ab:
        # the multi-tenant verdict: decode cadence with the
        # batched-adapter gather in the program vs without, on the
        # identical replayed load — the per-token price of serving K
        # fine-tunes from one engine
        a, b = res["base"], res["lora"]
        if a.get("tpot_p50") and b.get("tpot_p50"):
            print(json.dumps({"metric": "serve_lora_tpot_overhead",
                              "value": round(b["tpot_p50"]
                                             / a["tpot_p50"], 3),
                              "unit": "x (lora/base)"}))
        if a.get("throughput") and b.get("throughput"):
            print(json.dumps(
                {"metric": "serve_lora_throughput_ratio",
                 "value": round(b["throughput"] / a["throughput"], 3),
                 "unit": "x (lora/base)"}))
    if args.tp_ab:
        # the tensor-parallel verdict on identical replayed load:
        # decode cadence TP=1/TP=N (on CPU meshes this measures the
        # MECHANISM + partition overhead — psums are free-ish on ICI,
        # not on a host mesh), and the capacity headline: the weights+
        # pool bytes a TP=N engine holds are spread over N chips, so
        # at FIXED per-chip HBM the servable model is N x what one
        # chip loads — the record a 13B/65B memory-fit config cashes
        a, b = res["tp1"], res[f"tp{tp_n}"]
        print(json.dumps({"metric": "serve_tp_degree",
                          "value": tp_n, "unit": "devices"}))
        if a.get("tpot_p50") and b.get("tpot_p50"):
            print(json.dumps({"metric": "serve_tp_tpot_speedup",
                              "value": round(a["tpot_p50"]
                                             / b["tpot_p50"], 3),
                              "unit": "x (tp1/tpN)"}))
        if a.get("model_bytes"):
            # per-chip footprint of the TP=1 arm x N: the largest
            # (weights + KV pool) total a TP=N mesh can serve at the
            # unsharded arm's per-chip HBM budget
            print(json.dumps({"metric": "serve_tp_max_model_bytes",
                              "value": a["model_bytes"] * tp_n,
                              "unit": "bytes (at TP=1 per-chip HBM)"}))
        if b.get("model_bytes"):
            print(json.dumps(
                {"metric": "serve_tp_bytes_per_chip",
                 "value": b["model_bytes"] // tp_n,
                 "unit": "bytes/chip (weights+pool, TP arm)"}))
    if args.fleet:
        # the cross-process verdict on identical replayed load: what
        # the HTTP hop + router fan-out cost against ONE process
        # holding the same total silicon. TTFT carries the per-request
        # connection + admission-probe price; TPOT should track the
        # mono arm closely (streaming rides one long-lived response);
        # throughput says whether N schedulers beat one big batch at
        # this arrival rate. Equal-silicon is the FAIR baseline and
        # also the fleet's ceiling — its floor (the mono arm cannot
        # model it) is the per-chip memory wall that forces the fleet
        # shape in the first place
        a, b = res["mono"], res["fleet"]
        print(json.dumps({"metric": "serve_fleet_replicas",
                          "value": args.fleet, "unit": "processes"}))
        if a.get("ttft_p50") and b.get("ttft_p50"):
            print(json.dumps({"metric": "serve_fleet_ttft_overhead",
                              "value": round(b["ttft_p50"]
                                             / a["ttft_p50"], 3),
                              "unit": "x (fleet/mono)"}))
        if a.get("tpot_p50") and b.get("tpot_p50"):
            print(json.dumps({"metric": "serve_fleet_tpot_overhead",
                              "value": round(b["tpot_p50"]
                                             / a["tpot_p50"], 3),
                              "unit": "x (fleet/mono)"}))
        if a.get("throughput") and b.get("throughput"):
            print(json.dumps(
                {"metric": "serve_fleet_throughput_ratio",
                 "value": round(b["throughput"] / a["throughput"], 3),
                 "unit": "x (fleet/mono)"}))
    if args.kv_ab:
        # the quantization verdict on identical replayed load: decode
        # cadence bf16/int8 (HBM-bound hardware converts the halved
        # read bytes into TPOT; CPU-tiny measures the MECHANISM),
        # effective page capacity at equal HBM from the REAL per-page
        # byte costs (scale overhead included), and the bounded-
        # numerics probe — max next-token logit divergence + greedy
        # token flips on a fresh engine pair
        a, b = res["bf16"], res["int8"]
        if a.get("tpot_p50") and b.get("tpot_p50"):
            print(json.dumps({"metric": "serve_kv_quant_tpot_speedup",
                              "value": round(a["tpot_p50"]
                                             / b["tpot_p50"], 3),
                              "unit": "x (bf16/int8)"}))
        if b.get("kv_page_cost"):
            # effective page capacity at equal HBM vs the bf16
            # PRODUCTION baseline (the toy model's f32 cache dtype
            # must not inflate this): bf16-equivalent bytes over the
            # int8 arm's actual per-page cost, scale overhead included
            cost = b["kv_page_cost"]
            print(json.dumps(
                {"metric": "serve_kv_quant_capacity_ratio",
                 "value": round(cost["bf16_equiv_bytes_per_page"]
                                / cost["bytes_per_page"], 3),
                 "unit": "x pages at equal HBM (vs bf16)"}))
        div = _kv_quant_divergence(args, prompts)
        print(f"kv quant numerics: max logit div "
              f"{div['max_logit_div']:.4f} (mean "
              f"{div['mean_logit_div']:.4f}), {div['token_flips']} "
              f"greedy token flips over {div['tokens']} tokens")
        print(json.dumps({"metric": "serve_kv_quant_max_logit_div",
                          "value": round(div["max_logit_div"], 6),
                          "unit": "logit"}))
        print(json.dumps({"metric": "serve_kv_quant_token_flips",
                          "value": div["token_flips"],
                          "unit": "count"}))
    return 0


def _wire_chaos(args, prompts) -> int:
    """--wire-chaos: two arms over the REAL wire. Each arm builds a
    fresh seeded prefill/decode server pair behind ``serve_http`` and
    drives the identical pre-drawn load through a ``RemoteReplica``;
    the chaos arm replays it through an injected
    delay/drop/half-close/corrupt ``NetworkFaultPlan`` at both seams
    (generate + kv_import). The driver replays a request once on a
    terminal wire failure (the failover the router would run), so the
    verdict is exactly-once SURVIVAL: every request finishes and its
    tokens are bitwise-identical to the clean arm's — injected chaos
    shows up in the resume/retry/reship counters, never the output."""
    import argparse as _ap

    import numpy as np

    from paddle_tpu import tracing
    from paddle_tpu.inference.generation import GenerationConfig
    from paddle_tpu.serving import (DisaggregatedFront, RemoteReplica,
                                    RequestFailed, RequestRejected)
    from paddle_tpu.serving.http import serve_http
    from paddle_tpu.testing.faults import NetworkFaultPlan

    # the ship phase needs the paged prefix cache on both sides
    if args.cache_prefixes != "on":
        args = _ap.Namespace(**vars(args))
        args.cache_prefixes = "on"
    cfg = GenerationConfig(max_new_tokens=args.max_new,
                           do_sample=False)
    # the requests a ship cycle exports: longest prompts first — at
    # least one FULL page-size block resident from their prefill
    ship = sorted(range(len(prompts)),
                  key=lambda i: -len(prompts[i]))[:4]

    def _run(chaos: bool) -> dict:
        arm = "wirechaos" if chaos else "wireclean"
        tracing.clear()
        if chaos:
            tracing.enable()
        srv1 = srv2 = httpd1 = httpd2 = rep = rep2 = None
        try:
            srv1, vocab, _ = _build_toy_server(args, False)
            srv2, _, _ = _build_toy_server(args, False)
            assert vocab >= _TOY_VOCAB
            httpd1, httpd2 = serve_http(srv1), serve_http(srv2)
            rep = RemoteReplica(
                f"http://127.0.0.1:{httpd1.server_address[1]}")
            rep2 = RemoteReplica(
                f"http://127.0.0.1:{httpd2.server_address[1]}")
            assert rep.wait_ready(timeout=120)
            assert rep2.wait_ready(timeout=120)
            plan = None
            if chaos:
                plan = NetworkFaultPlan()
                # generate seam: one of each injection, spread over
                # the (sequential, so deterministic) call sequence.
                # Retries/resumes count as calls too — the plan fires
                # strictly by call order, same as a real flaky link.
                plan.delay_at("generate", nth=2, seconds=0.05)
                plan.drop_at("generate", nth=4)       # submit retry
                plan.half_close_at("generate", nth=6, after=1)
                plan.corrupt_at("generate", nth=9, mode="flip",
                                after=1)              # garbled line
                # three consecutive tears exhaust the resume budget
                # (default 2) and force the failover replay
                plan.half_close_at("generate", nth=11, after=1,
                                   times=3)
                # kv_import seam: both corruption modes + a delay
                plan.corrupt_at("kv_import", nth=1, mode="flip")
                plan.delay_at("kv_import", nth=2, seconds=0.02)
                plan.corrupt_at("kv_import", nth=3, mode="truncate")
                rep.fault_plan = plan
                rep2.fault_plan = plan
            tokens, failovers, failures = [], 0, 0
            for p in prompts:
                ids = np.asarray(p, np.int32)
                toks = None
                for attempt in (0, 1):
                    try:
                        h = rep.submit(ids, cfg)
                        toks = [int(t)
                                for t in h.result(timeout=120)]
                        break
                    except (RequestFailed, RequestRejected,
                            RuntimeError, TimeoutError):
                        if attempt:
                            failures += 1
                        else:
                            failovers += 1   # the replay the router
                            #                  would run elsewhere
                tokens.append(toks)
            # ship phase: prefill pages for the longest prompts are
            # resident on srv1 (their requests just ran there) — ship
            # them to the decode server through the faulted seam
            front = DisaggregatedFront(rep, rep2)
            ship_fail = 0
            for i in ship:
                try:
                    front.ship(prompts[i])
                except Exception:
                    ship_fail += 1
            out = {
                "tokens": tokens, "failovers": failovers,
                "failures": failures, "ship_failures": ship_fail,
                "resumes": rep.resumes,
                "submit_retries": rep.submit_retries,
                "reships": front.reships,
                "integrity_rejects": rep2.integrity_rejects,
                "injected": list(plan.injected) if plan else [],
            }
            if chaos and args.trace_out:
                tracing.export_chrome(args.trace_out)
                print(f"wrote wire trace to {args.trace_out} "
                      f"(tools/monitor_report.py --wire "
                      f"{args.trace_out})")
            return out
        finally:
            for r in (rep, rep2):
                if r is not None:
                    r.close()
            for hd in (httpd1, httpd2):
                if hd is not None:
                    hd.shutdown()
            for s in (srv1, srv2):
                if s is not None:
                    s.shutdown(drain=False)
            tracing.disable()
            tracing.clear()

    res = {"wireclean": _run(False), "wirechaos": _run(True)}
    a, b = res["wireclean"], res["wirechaos"]
    matched = sum(1 for x, y in zip(a["tokens"], b["tokens"])
                  if x is not None and x == y)
    survival = matched / max(1, len(prompts))
    for arm in ("wireclean", "wirechaos"):
        r = res[arm]
        print(f"{arm}: {sum(1 for t in r['tokens'] if t is not None)}"
              f"/{len(prompts)} finished, {r['resumes']} resumes, "
              f"{r['submit_retries']} submit retries, "
              f"{r['failovers']} failovers, {r['reships']} reships, "
              f"{r['integrity_rejects']} integrity rejects, "
              f"{len(r['injected'])} injections")
    for name, val in (("serve_wire_resumes", b["resumes"]),
                      ("serve_wire_failovers", b["failovers"]),
                      ("serve_wire_reships", b["reships"]),
                      ("serve_wire_integrity_rejects",
                       b["integrity_rejects"]),
                      ("serve_wire_submit_retries",
                       b["submit_retries"])):
        print(json.dumps({"metric": name, "value": int(val),
                          "unit": "count"}))
    print(json.dumps({"metric": "serve_wire_survival_rate",
                      "value": round(survival, 4),
                      "unit": "fraction (chaos tokens == clean)"}))
    ok = (survival == 1.0 and b["ship_failures"] == 0
          and len(b["injected"]) > 0
          and (b["resumes"] or b["submit_retries"])
          and b["integrity_rejects"])
    print(f"wire verdict: {'PASS' if ok else 'FAIL'} — survival "
          f"{survival:.3f} (bar 1.0) under {len(b['injected'])} "
          f"injections; {b['resumes']} mid-stream resumes, "
          f"{b['submit_retries']} idempotent submit retries, "
          f"{b['integrity_rejects']} corrupt ships rejected "
          f"({b['reships']} re-shipped clean)")
    return 0 if ok else 1


def _kv_quant_divergence(args, prompts, n_prompts: int = 3,
                         steps: int = 16):
    """Bounded-numerics probe for the --kv-ab verdict: one fresh
    bf16/int8 engine pair (identical seeded weights), the run's first
    few prompts, stepwise next-token logit comparison through the REAL
    store/read pipeline (quantization.kv.max_logit_divergence)."""
    import argparse as _ap

    from paddle_tpu.quantization.kv import max_logit_divergence

    pa = _ap.Namespace(**vars(args))
    pa.kv_dtype = "bf16"
    pb = _ap.Namespace(**vars(args))
    pb.kv_dtype = "int8"
    eng_a, _ = _toy_engine(pa)
    eng_b, _ = _toy_engine(pb)
    import numpy as np

    # prompt + probe steps must fit one sequence's max_len; with a
    # tiny --max-pages the step count shrinks rather than the cap
    # going negative and silently mis-slicing (or emptying) prompts
    max_len = args.max_pages * args.page_size
    steps = max(1, min(steps, max_len // 2))
    cap = max(1, max_len - steps - 1)
    use = [np.asarray(p[:cap], np.int32)
           for p in prompts[:n_prompts]]
    try:
        return max_logit_divergence(eng_a, eng_b, use, steps=steps)
    finally:
        eng_a.close()
        eng_b.close()


def _ttft_decomposition():
    """Split each finished request's TTFT into its trace-derived phase
    shares: queue wait (enqueue -> dequeue), admission prefill (the
    admit/chunk span durations), and the remainder — scheduler gap +
    the first decode segment's share. Returns (queue, prefill, gap)
    second-lists over the requests whose enqueue AND first token are
    still in the bounded ring."""
    from paddle_tpu import tracing

    per = {}
    for e in tracing.events():
        rid, ph = e.get("rid"), e["phase"]
        if rid is None:
            continue
        d = per.setdefault(rid, {})
        if ph == "queue.enqueue":
            d["enq"] = e["ts_ns"]
        elif ph == "queue.dequeue" and "deq" not in d:
            d["deq"] = e["ts_ns"]
        elif ph in ("admit", "admit.begin", "prefill_chunk"):
            # only spans BEFORE the first token count toward TTFT: a
            # preempted request's replay re-admission happens after it
            # and must not inflate the prefill share (ring insertion
            # order is end-time order, so the gate below is exact —
            # the first admission's span lands before first_token)
            if "first" not in d:
                d["admit"] = d.get("admit", 0) + e["dur_ns"]
        elif ph == "first_token" and "first" not in d:
            d["first"] = e["ts_ns"]
    qs, ps, gs = [], [], []
    for d in per.values():
        if "enq" not in d or "first" not in d:
            continue
        ttft = (d["first"] - d["enq"]) / 1e9
        q = max((d.get("deq", d["enq"]) - d["enq"]) / 1e9, 0.0)
        p = d.get("admit", 0) / 1e9
        qs.append(q)
        ps.append(p)
        gs.append(max(ttft - q - p, 0.0))
    return qs, ps, gs


def _load_bench_adapters(server, args) -> None:
    """Hot-load ``--adapters`` seeded synthetic LoRA adapters through
    the Server's admin path (the same inter-segment-gap marshalling a
    production load uses). Factors are small (0.05 std) so the toy
    model's outputs stay well-formed while the gather does real
    work."""
    import numpy as np

    reg = server.engine.adapters
    for j in range(args.adapters):
        g = np.random.default_rng(1000 + j)
        params = {
            t: (g.standard_normal((args.lora_rank, d_in))
                .astype(np.float32) * 0.05,
                g.standard_normal((d_out, args.lora_rank))
                .astype(np.float32) * 0.05)
            for t, (d_in, d_out) in reg.shapes.items()}
        server.load_adapter(f"ad{j}", params)


def _run_arm(args, arm: str, spec_on: bool, trace_on: bool, prompts,
             arrivals, assign=None, mon_on: bool = True,
             tenants=None) -> dict:
    """Build one server (in-process mode), drive the pre-drawn load
    through it, print the table + BENCH records (metric names suffixed
    ``_<arm>`` in A/B mode), shut down. ``assign`` is the pre-drawn
    per-request adapter name list (ignored when --adapters is 0 for
    this arm); ``tenants`` the pre-drawn per-request tenant list
    (--overload-ab — the hot/cold mix every arm replays).
    ``mon_on=False`` (the --slo-ab OFF arm) runs with
    FLAGS_enable_monitor disabled — the one-bool-branch path.
    Returns the numbers the A/B verdict needs."""
    sfx = f"_{arm}" if arm else ""
    if assign is None:
        assign = [None] * len(prompts)
    if tenants is None:
        tenants = [None] * len(prompts)
    server = None
    plan = None
    kill_fn = None
    if args.url is None:
        from paddle_tpu import monitor, tracing
        from paddle_tpu.monitor import ledger
        if mon_on:
            monitor.enable()
        else:
            monitor.disable()
        monitor.reset()    # per-arm program/compile counters
        ledger.reset()     # per-arm program records
        if getattr(args, "profile", False):
            ledger.enable()
        else:
            ledger.disable()
        tracing.clear()    # per-arm ring (the off arm must not export
        #                    the on arm's leftovers)
        if trace_on:
            tracing.enable()
        else:
            tracing.disable()
        if getattr(args, "fleet", 0):
            server, vocab, kill_fn = _build_fleet_router(args)
        elif args.router:
            server, vocab, kill_fn = _build_toy_router(args)
        else:
            server, vocab, plan = _build_toy_server(args, spec_on)
            if args.adapters:
                _load_bench_adapters(server, args)
        # prompts were drawn in [0, _TOY_VOCAB) before the server
        # existed; any preset with at least that many tokens serves
        # them (tiny == exactly; 13b/65b have 32000)
        assert vocab >= _TOY_VOCAB, \
            f"model vocab {vocab} < {_TOY_VOCAB} the prompts used"

    stats = _Stats()
    # KV pool occupancy sampler (in-process paged engine): the
    # utilization half of the reserved-vs-optimistic A/B — reserved
    # mode's occupancy counts RESERVED pages (worst case held against
    # the pool), optimistic mode's counts pages actually written
    occ_samples = []
    occ_stop = threading.Event()
    occ_th = None
    eng = getattr(server, "engine", None)   # a Router has replicas,
    #                                         not one engine
    alloc = getattr(eng, "alloc", None) if eng is not None else None
    # HBM cost per page under this arm's storage dtype (scales
    # included) + its bf16-equivalent baseline — the --kv-ab
    # capacity-ratio record divides these
    bpp_fn = getattr(eng, "kv_page_cost", None)
    kv_page_cost = bpp_fn() if callable(bpp_fn) else None
    # weights + KV pool bytes this engine holds on device (logical
    # totals; a TP mesh spreads them over tp_degree chips) — the
    # --tp-ab capacity record's numerator
    model_bytes = None
    if eng is not None and getattr(eng, "params", None) is not None:
        model_bytes = sum(int(v.nbytes) for v in eng.params.values())
        if kv_page_cost is not None:
            model_bytes += (kv_page_cost["bytes_per_page"]
                            * eng.num_pages)
    if alloc is not None:
        def _sample_occ():
            while not occ_stop.wait(0.005):
                occ_samples.append(alloc.occupancy)

        occ_th = threading.Thread(target=_sample_occ, daemon=True)
        occ_th.start()
    threads = []
    kill_timer = None
    t_start = time.monotonic()
    if kill_fn is not None:
        kill_timer = threading.Timer(args.kill_replica_at, kill_fn)
        kill_timer.daemon = True
        kill_timer.start()
    for i, (at, prompt) in enumerate(zip(arrivals, prompts)):
        delay = t_start + at - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        if args.url is None:
            from paddle_tpu.inference.generation import GenerationConfig
            import numpy as np

            cfg = GenerationConfig(
                max_new_tokens=args.max_new,
                adapter=(assign[i] if args.adapters else None))
            th = threading.Thread(
                target=_drive_inproc,
                args=(server, np.asarray(prompt, np.int32), cfg, stats,
                      tenants[i]))
        else:
            th = threading.Thread(
                target=_drive_http,
                args=(args.url, prompt,
                      {"max_new_tokens": args.max_new}, stats))
        th.start()
        threads.append(th)
    for th in threads:
        th.join()
    wall = time.monotonic() - t_start
    if kill_timer is not None:
        # a run that drained before T must not (a) leave the timer to
        # fire into a later A/B arm, or (b) silently report a
        # NO-FAULT run as the fault-plan arm
        kill_timer.cancel()
        if not kill_fn.fired["kill"]:
            print(f"warning: --kill-replica-at {args.kill_replica_at} "
                  "never fired (the run finished first) — the fleet "
                  "records below reflect an UNFAULTED run; lower the "
                  "kill time or raise --requests", file=sys.stderr)
    if occ_th is not None:
        occ_stop.set()
        occ_th.join(timeout=2.0)

    done = len(stats.e2e)
    print(f"\n[{arm or 'run'}] {done}/{args.requests} completed, "
          f"{stats.rejected} rejected, {stats.failed} failed, "
          f"{stats.tokens} tokens in {wall:.2f}s "
          f"({stats.tokens / wall:.1f} tok/s)\n")
    # provenance header: ties this arm's records to the machine/
    # backend/rev that produced them — tools/bench_diff.py reads it
    # and warns when two compared rounds disagree
    from paddle_tpu.monitor.provenance import env_stamp
    print(json.dumps({"metric": "bench_env",
                      **env_stamp(extra={"tp_degree": args.tp,
                                         "arm": arm or "run"})}))
    rows = [("ttft", stats.ttft, "s"), ("tpot", stats.tpot, "s"),
            ("e2e_latency", stats.e2e, "s")]
    print(f"{'METRIC':<14}{'p50':>10}{'p90':>10}{'p99':>10}")
    for name, xs, _u in rows:
        print(f"{name:<14}"
              f"{_percentile(xs, 50):>10.4f}"
              f"{_percentile(xs, 90):>10.4f}"
              f"{_percentile(xs, 99):>10.4f}")
    print()
    for name, xs, unit in rows:
        if not xs:
            continue   # NaN is not valid JSON; the table above shows it
        for q in (50, 90, 99):
            print(json.dumps({"metric": f"serve_{name}_p{q}{sfx}",
                              "value": round(_percentile(xs, q), 6),
                              "unit": unit}))
    print(json.dumps({"metric": f"serve_throughput{sfx}",
                      "value": round(stats.tokens / wall, 2),
                      "unit": "tokens/s"}))
    print(json.dumps({"metric": f"serve_rejected{sfx}",
                      "value": stats.rejected, "unit": "count"}))
    if server is not None and mon_on and not getattr(args, "fleet", 0):
        # the bucketing win in the methodology: how many prefill
        # programs this run compiled (and what that cost) — bounded by
        # len(buckets)+1 with bucketing on, O(#distinct lengths) off
        # (--fleet arm: the compiles happened in the CHILD processes;
        # the local registry would report a misleading zero)
        pre_n, pre_s, all_n, all_s = _prefill_program_stats()
        n_lens = len({len(p) for p in prompts})
        print(f"prefill programs compiled: {pre_n} "
              f"({pre_s:.2f}s) for {n_lens} distinct prompt lengths; "
              f"all jit programs: {all_n} ({all_s:.2f}s)")
        print(json.dumps({"metric": f"serve_prefill_programs{sfx}",
                          "value": pre_n, "unit": "count"}))
        print(json.dumps({"metric": f"serve_prefill_compile_seconds{sfx}",
                          "value": round(pre_s, 4), "unit": "s"}))
        print(json.dumps({"metric": f"serve_distinct_prompt_lens{sfx}",
                          "value": n_lens, "unit": "count"}))
    if alloc is not None:
        # memory-pressure accounting: the utilization/throughput A/B
        # (PERF.md) reads these four — occupancy tells how much of the
        # pool the policy actually used, preemptions + the latency
        # penalty tell what the optimistic win cost in tail latency
        occ50, occ99 = (_percentile(occ_samples, 50),
                        _percentile(occ_samples, 99))
        pre = alloc.preemptions
        n_pre = len(stats.e2e_preempted)
        print(f"kv pool [{args.admission_mode}]: occupancy "
              f"p50={occ50:.3f} p99={occ99:.3f}, {pre} preemptions, "
              f"{n_pre} requests preempted >= once")
        if occ_samples:
            print(json.dumps({"metric": f"serve_kv_occupancy_p50{sfx}",
                              "value": round(occ50, 4),
                              "unit": "ratio"}))
            print(json.dumps({"metric": f"serve_kv_occupancy_p99{sfx}",
                              "value": round(occ99, 4),
                              "unit": "ratio"}))
        print(json.dumps({"metric": f"serve_kv_preemptions{sfx}",
                          "value": pre, "unit": "count"}))
        print(json.dumps({"metric": f"serve_preempted_requests{sfx}",
                          "value": n_pre, "unit": "count"}))
        n_clean = len(stats.e2e) - n_pre
        if n_pre and n_clean:
            penalty = (sum(stats.e2e_preempted) / n_pre
                       - (sum(stats.e2e) - sum(stats.e2e_preempted))
                       / n_clean)
            print(json.dumps(
                {"metric": f"serve_preempted_latency_penalty{sfx}",
                 "value": round(penalty, 6), "unit": "s"}))
        if plan is None:
            # chaos runs emit these below from fault accounting
            print(json.dumps({"metric": f"serve_requests_survived{sfx}",
                              "value": done, "unit": "count"}))
            print(json.dumps({"metric": f"serve_requests_failed{sfx}",
                              "value": stats.failed, "unit": "count"}))
        if getattr(alloc, "kv_dtype", "bf16") == "int8":
            # quantized-KV accounting: bytes the int8 layout avoided
            # for the pages this run claimed (scale overhead already
            # netted out) — the capacity half of the quantization win
            print(f"kv quant [int8]: {alloc.quant_bytes_saved} HBM "
                  f"bytes saved across claimed pages")
            print(json.dumps(
                {"metric": f"serve_kv_quant_bytes_saved{sfx}",
                 "value": alloc.quant_bytes_saved, "unit": "bytes"}))
        if args.shared_prefix_len > 0 or getattr(alloc, "prefix_cache",
                                                 False):
            # prefix-cache A/B: hit rate over lookups (cache off: both
            # zero — the cold column), prefill tokens whose compute a
            # warm admission skipped, shared-page high-water via the
            # pressure surface. Read alongside ttft_p50/p99 and
            # kv_occupancy above — the win is TTFT down AND occupancy
            # down at matched load
            hits = getattr(alloc, "prefix_hits", 0)
            looks = getattr(alloc, "prefix_lookups", 0)
            saved = getattr(alloc, "prefix_tokens_saved", 0)
            rate = hits / looks if looks else 0.0
            print(f"prefix cache [{args.cache_prefixes}]: "
                  f"{hits}/{looks} warm admissions "
                  f"(hit rate {rate:.3f}), {saved} prefill tokens "
                  f"saved, {getattr(alloc, 'cow_copies', 0)} CoW "
                  f"copies, {getattr(alloc, 'cached_pages', 0)} pages "
                  f"parked at exit")
            print(json.dumps({"metric": f"serve_prefix_hit_rate{sfx}",
                              "value": round(rate, 4),
                              "unit": "ratio"}))
            print(json.dumps({"metric": f"serve_prefill_tokens_saved{sfx}",
                              "value": saved, "unit": "tokens"}))
            print(json.dumps({"metric": f"serve_prefix_cow_copies{sfx}",
                              "value": getattr(alloc, "cow_copies", 0),
                              "unit": "count"}))
    reg = (getattr(eng, "adapters", None) if eng is not None
           else None)
    if reg is not None and args.adapters:
        # multi-tenant accounting: how many fine-tunes ONE engine
        # served this run, and how concentrated the mix was (entropy
        # over the drawn assignment — log2(K) = perfectly uniform,
        # lower = a few hot tenants; zipf loads land in between)
        import math
        from collections import Counter

        info = reg.resident()
        used = [a for a in assign if a is not None]
        cnt = Counter(used)
        n_u = len(used)
        ent = (-sum((c / n_u) * math.log2(c / n_u)
                    for c in cnt.values()) if n_u else 0.0)
        print(f"lora [{args.adapters} adapters, {args.adapter_dist}]: "
              f"{info['resident']} resident, {len(cnt)} distinct in "
              f"the mix, entropy {ent:.3f} bits "
              f"(max {math.log2(args.adapters):.3f})")
        print(json.dumps({"metric": f"serve_lora_adapters_resident{sfx}",
                          "value": info["resident"], "unit": "count"}))
        print(json.dumps({"metric": f"serve_lora_mix_entropy{sfx}",
                          "value": round(ent, 4), "unit": "bits"}))
    spec_stats = (getattr(eng, "spec_stats", None)
                  if eng is not None else None)
    if spec_stats is not None and getattr(eng, "draft_k", 0):
        # speculative-decoding accounting (spec arm / --speculative
        # on): accepted-tokens-per-forward is the number that converts
        # into TPOT on HBM-bound hardware; acceptance rate says how
        # well the n-gram proposer fit this load. CPU-tiny runs
        # measure the MECHANISM (the host proposer round-trip
        # dominates there), not the speedup — see PERF.md.
        ss = spec_stats()
        print(f"speculative [draft_k={args.draft_k}]: "
              f"{ss['emitted']} tokens / {ss['slot_steps']} slot-"
              f"forwards ({ss['forwards']} verify steps) = "
              f"{ss['tokens_per_forward']:.2f} tok/fwd per slot, "
              f"acceptance {ss['accepted']}/{ss['proposed']} "
              f"= {ss['acceptance_rate']:.3f}")
        print(json.dumps({"metric": f"serve_spec_tokens_per_forward{sfx}",
                          "value": round(ss["tokens_per_forward"], 4),
                          "unit": "tokens/forward"}))
        print(json.dumps({"metric": f"serve_spec_acceptance_rate{sfx}",
                          "value": round(ss["acceptance_rate"], 4),
                          "unit": "ratio"}))
        print(json.dumps({"metric": f"serve_spec_draft_tokens{sfx}",
                          "value": ss["proposed"], "unit": "tokens"}))
        # the sync-elimination receipt: host mode blocks on one
        # proposer readback per verify forward, device mode reads back
        # once per SEGMENT — this must print 0.0 there
        print(json.dumps(
            {"metric": f"serve_spec_host_syncs_per_token{sfx}",
             "value": round(ss["host_syncs_per_token"], 4),
             "unit": "syncs/token"}))
    if server is not None and args.router:
        # fleet accounting (PERF.md fleet-survival methodology): the
        # survival rate over ACCEPTED requests is the headline — with
        # spare replicas it should stay 1.0 through a replica kill;
        # failover count/latency price the migrations, breaker opens
        # count how often routing walled off a sick replica
        snap = server.load()
        accepted = args.requests - stats.rejected
        survival = done / accepted if accepted else 0.0
        per_rep = ", ".join(
            f"r{e['replica']}:{e['status']}"
            f"(breaker={e['breaker']['state']},"
            f"restarts={e['restarts']})" for e in snap["replicas"])
        print(f"fleet [{len(snap['replicas'])} replicas]: survival "
              f"{done}/{accepted} = {survival:.3f}, "
              f"{snap['failovers']} failovers, "
              f"{snap['breaker_opens']} breaker opens; {per_rep}")
        print(json.dumps({"metric": f"serve_fleet_survival_rate{sfx}",
                          "value": round(survival, 4),
                          "unit": "ratio"}))
        print(json.dumps({"metric": f"serve_failover_count{sfx}",
                          "value": snap["failovers"],
                          "unit": "count"}))
        if stats.e2e_failover:
            print(json.dumps(
                {"metric": f"serve_failover_latency_p99{sfx}",
                 "value": round(
                     _percentile(stats.e2e_failover, 99), 6),
                 "unit": "s"}))
        print(json.dumps({"metric": f"serve_breaker_opens{sfx}",
                          "value": snap["breaker_opens"],
                          "unit": "count"}))
        print(json.dumps({"metric": f"serve_replica_restarts{sfx}",
                          "value": sum(e["restarts"]
                                       for e in snap["replicas"]),
                          "unit": "count"}))
        print(json.dumps({"metric": f"serve_requests_survived{sfx}",
                          "value": done, "unit": "count"}))
        print(json.dumps({"metric": f"serve_requests_failed{sfx}",
                          "value": stats.failed, "unit": "count"}))
    if plan is not None:
        # chaos accounting: what was injected, what survived, what the
        # supervisor did about it (fault_stats is host-side — readable
        # even with the monitor off)
        fs = server.fault_stats()
        rec = sorted(fs["recovery_s"])
        print(f"chaos: {len(plan.injected)} faults injected "
              f"({args.fault_kind} @ {args.fault_site}), "
              f"{done} requests survived, {stats.failed} failed, "
              f"{fs['restarts']} engine restarts")
        print(json.dumps({"metric": f"serve_faults_injected{sfx}",
                          "value": len(plan.injected),
                          "unit": "count"}))
        print(json.dumps({"metric": f"serve_requests_survived{sfx}",
                          "value": done, "unit": "count"}))
        print(json.dumps({"metric": f"serve_requests_failed{sfx}",
                          "value": stats.failed, "unit": "count"}))
        print(json.dumps({"metric": f"serve_restarts{sfx}",
                          "value": fs["restarts"], "unit": "count"}))
        for q in (50, 90, 99):
            if rec:
                print(json.dumps(
                    {"metric": f"serve_recovery_p{q}{sfx}",
                     "value": round(_percentile(rec, q), 6),
                     "unit": "s"}))

    if (server is not None and mon_on
            and (args.slo_ttft is not None
                 or args.slo_tpot is not None)):
        # SLO/goodput accounting (PERF.md SLO methodology): the
        # GET /stats rollup — digest-exact percentiles (a Router's
        # version MERGES replica digests, never averages) scored
        # against the armed policy. serve_goodput is the headline:
        # the fraction of service-terminal requests the fleet served
        # INSIDE the SLO — the quantity disaggregation papers
        # optimize, where raw throughput can lie
        st = server.stats()
        tens = st.get("tenants") or {}
        met = sum(v.get("met", 0) for v in tens.values())
        missed = sum(v.get("missed", 0) for v in tens.values())
        parts = []
        for t, v in sorted(tens.items()):
            gp = v.get("goodput")
            parts.append(f"{t}:{'-' if gp is None else format(gp, '.3f')}"
                         f"(burn_f={v.get('burn_fast')})")
        print(f"slo [ttft<={args.slo_ttft} tpot<={args.slo_tpot}]: "
              f"goodput {met}/{met + missed}, per-tenant "
              + ", ".join(parts))
        if met + missed:
            print(json.dumps({"metric": f"serve_goodput{sfx}",
                              "value": round(met / (met + missed), 4),
                              "unit": "ratio"}))
        for metric, rec in (("ttft", "serve_slo_ttft_p99"),
                            ("tpot", "serve_slo_tpot_p99")):
            agg = (st.get("metrics") or {}).get(metric, {}).get("*")
            if agg and agg.get("p99") is not None:
                print(json.dumps({"metric": f"{rec}{sfx}",
                                  "value": agg["p99"], "unit": "s"}))
    extra = {}
    if server is not None and getattr(args, "overload_ab", False):
        # overload accounting (PERF.md overload methodology): the
        # verdict needs goodput SPLIT by tenant class — the control
        # plane's whole job is spending the hot tenant's availability
        # (shedding it at the door) to keep the cold tenants inside
        # SLO. Shed rejects + the control snapshot say what the plane
        # actually did; the cap arm prints zeros for both.
        st = server.stats()
        tens = st.get("tenants") or {}
        hm = hx = cm = cx = 0
        for t, v in tens.items():
            if t == "hot":
                hm += v.get("met", 0)
                hx += v.get("missed", 0)
            else:
                cm += v.get("met", 0)
                cx += v.get("missed", 0)
        cold_gp = cm / (cm + cx) if cm + cx else None
        hot_gp = hm / (hm + hx) if hm + hx else None
        ctrl = (server.load() or {}).get("control") or {}
        shed_total = sum(sum(r.values()) for r in
                         (ctrl.get("sheds") or {}).values())
        def fmt(g):
            return "-" if g is None else format(g, ".3f")

        print(f"overload [{arm}]: cold goodput {fmt(cold_gp)} "
              f"({cm}/{cm + cx}), hot goodput {fmt(hot_gp)} "
              f"({hm}/{hm + hx}), {stats.shed} shed rejects, "
              f"rung {ctrl.get('rung', 0)} "
              f"({ctrl.get('rung_action', 'off')}) at drain")
        if cold_gp is not None:
            print(json.dumps({"metric": f"serve_goodput_cold{sfx}",
                              "value": round(cold_gp, 4),
                              "unit": "ratio"}))
        if hot_gp is not None:
            print(json.dumps({"metric": f"serve_goodput_hot{sfx}",
                              "value": round(hot_gp, 4),
                              "unit": "ratio"}))
        print(json.dumps({"metric": f"serve_shed_rejects{sfx}",
                          "value": stats.shed, "unit": "count"}))
        met = sum(v.get("met", 0) for v in tens.values())
        missed = sum(v.get("missed", 0) for v in tens.values())
        extra = {"cold_goodput": cold_gp, "hot_goodput": hot_gp,
                 "goodput": (met / (met + missed) if met + missed
                             else None),
                 "sheds": shed_total}
    if server is not None and trace_on:
        # trace-derived TTFT decomposition: WHICH phase ate the time.
        # queue = submit->dequeue, prefill = the admission span(s),
        # gap = the remainder (scheduler gap + first segment share) —
        # the three sum to the server-side TTFT per request
        qs, ps, gs = _ttft_decomposition()
        if qs:
            print(f"ttft decomposition (n={len(qs)}): queue p50 "
                  f"{_percentile(qs, 50):.4f}s, prefill p50 "
                  f"{_percentile(ps, 50):.4f}s, gap p50 "
                  f"{_percentile(gs, 50):.4f}s")
            for name, xs in (("queue", qs), ("prefill", ps),
                             ("gap", gs)):
                print(json.dumps(
                    {"metric": f"serve_ttft_{name}_p50{sfx}",
                     "value": round(_percentile(xs, 50), 6),
                     "unit": "s"}))
        if args.trace_out:
            from paddle_tpu import tracing
            tpath = args.trace_out + sfx
            tracing.export_chrome(tpath)
            print(f"wrote trace to {tpath} (open in chrome://tracing "
                  f"or ui.perfetto.dev; tools/monitor_report.py "
                  f"--trace {tpath} for the phase table)")
    if server is not None and getattr(args, "profile", False):
        # program-ledger report: read BEFORE shutdown — engine.close()
        # retires the ledger rows the engine owns. The per-program
        # table is the "which compiled program is eating the step"
        # answer; the dispatch total cross-checks the monitored_jit
        # counters (ISSUE 16 acceptance: the two must agree)
        from paddle_tpu.monitor import ledger
        prof_fn = getattr(server, "profile", None)
        prof = prof_fn() if prof_fn is not None else ledger.profile()
        progs = prof.get("programs") or {}
        if progs:
            from tools.monitor_report import render_profile
            print()
            print(render_profile(prof))
            print()
            print(json.dumps({"metric": f"serve_profile_programs{sfx}",
                              "value": len(progs), "unit": "count"}))
            print(json.dumps(
                {"metric": f"serve_profile_dispatch_seconds{sfx}",
                 "value": round(prof.get("total_seconds", 0.0), 6),
                 "unit": "s"}))
        if args.profile_out:
            ppath = args.profile_out + sfx
            with open(ppath, "w") as f:
                json.dump(prof, f, indent=1)
            print(f"wrote /profile snapshot to {ppath} "
                  f"(tools/monitor_report.py --profile {ppath})")
    if server is not None:
        if args.monitor_out:
            from paddle_tpu import monitor
            from paddle_tpu.monitor.provenance import env_stamp
            path = args.monitor_out + sfx
            n = monitor.write_jsonl(path,
                                    extra={"env": env_stamp()})
            print(f"wrote {n} monitor samples to {path}")
        server.shutdown(drain=False)
        if trace_on:
            from paddle_tpu import tracing
            tracing.disable()   # in-process callers (slow-tier tests)
            #                     must not inherit a live recorder
        if getattr(args, "profile", False):
            from paddle_tpu.monitor import ledger
            ledger.disable()    # same contract as tracing above
    return {
        "tpot_p50": (_percentile(stats.tpot, 50) if stats.tpot
                     else None),
        "ttft_p50": (_percentile(stats.ttft, 50) if stats.ttft
                     else None),
        "throughput": (stats.tokens / wall if wall > 0 else None),
        "kv_page_cost": kv_page_cost,
        "model_bytes": model_bytes,
        **extra,
    }


if __name__ == "__main__":
    # entry-point only (tests call main() in-process): runs of this tool
    # and the replica children it spawns share one persistent compile cache
    from paddle_tpu.device.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(main())
