"""Record the fixture of ``test_host_spans.py`` on the chip.

    python3 benchmark/selfcheck/record_host_spans.py OUT.json.gz N_LOOPS <run.py's arguments>

Runs ``benchmark/run.py`` in this process with the arguments given
(``--workload serve-mistral-7b-chat --trace 1 ...``) and, where the
harness has run the per-layer readers, cuts N consecutive iterations of
the scheduler's loop out of the middle of the traced window: the
device's ``XLA Modules`` and ``XLA Ops`` events that start inside them,
the ``pt:*`` events of the host plane that lie inside them, the ring's
events that belong to those, and what else a reader takes from ``ctx``.
N_LOOPS 0 keeps every whole iteration of the window. The five readers
are then run on the cut and their values written beside it as
``expect``: read off once, they pin the reduction.
"""
import gzip
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.lib import host_spans as hs  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

READERS = ("idle_admit_share.serve", "idle_unattributed_share.serve",
           "admit_host_ms_per_req", "paged_decode_roofline",
           "prefill_pad_share")
LINES = (tr.MODULES_LINE, tr.OPS_LINE)


def load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """The reader of one per-layer metric, by the metric's name."""
    return load(os.path.join(ROOT, "benchmark", "layers", name + ".py"),
                "reader_" + "".join(c if c.isalnum() else "_" for c in name))


def cut(ctx: dict, loops: int) -> dict:
    view = hs.view(ctx)
    line = hs.scheduler_line(view["spans"])
    steps = sorted((s for s in view["spans"].values()
                    if s["name"] == hs.ROOT and s["line"] == line),
                   key=lambda s: s["start"])
    loops = min(loops, len(steps)) or len(steps)
    first = (len(steps) - loops) // 2
    lo, hi = steps[first]["start"], steps[first + loops - 1]["end"]
    raw = tr.trim({"planes": [
        {"name": p["name"], "lines": [ln for ln in p["lines"]
                                      if ln["name"] in LINES]}
        for p in tr.device_planes(ctx["raw"])[:1]]}, lo, hi)
    keep = {sid for sid, s in view["spans"].items()
            if lo <= s["start"] and s["end"] <= hi}
    host = {"planes": [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [
            e for e in ln["events"]
            if e[0].startswith(hs.PREFIX) and e[3].get("id") in keep]}
        for ln in p["lines"]]} for p in ctx["host"]["planes"]]}
    spans = [ev for ev in ctx["spans"]
             if ev.get("span.id") in keep or ev.get("span.parent") in keep]
    peaks = ctx.get("peaks")
    if peaks is None:
        import jax

        from benchmark.lib.peaks import peaks as peaks_of

        peaks = peaks_of(jax.devices()[0].device_kind)
    return {"raw": raw, "host": host, "spans": spans,
            "config": ctx["config"], "mix": ctx["mix"], "run": ctx["run"],
            "peaks": peaks}


def expect(fixture: dict) -> dict:
    ctx = dict(fixture)
    out = {}
    for name in READERS:
        out[name] = reader(name).read(ctx)
    lo, hi = tr.window_ns(ctx["raw"])
    out["window_ns"] = hi - lo
    out["idle_ns"] = ctx["host_spans"]["idle_ns"]
    runs = hs.segment_runs(ctx, "jit_segment", "engine.segment")
    out["segment_runs_matched"] = len(runs)
    out["segment_runs"] = len(tr.module_runs(ctx["raw"], ("jit_segment",)))
    return out


def main() -> int:
    out, loops, argv = sys.argv[1], int(sys.argv[2]), sys.argv[3:]
    run = load(os.path.join(ROOT, "benchmark", "run.py"), "benchmark_run")
    per_layer = run.per_layer

    def recording(bench, cell, ctx):
        metrics = per_layer(bench, cell, ctx)
        fixture = cut(ctx, loops)
        fixture["expect"] = expect(fixture)
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with gzip.open(out, "wt") as f:
            json.dump(fixture, f)
        run.say(phase="fixture", path=out, bytes=os.path.getsize(out),
                loops=loops, expect=fixture["expect"])
        return metrics

    run.per_layer = recording
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
