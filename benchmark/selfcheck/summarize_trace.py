"""Look at one trace by hand: planes, lines, the programs and the
operations with most time, and a few whole events with their stats.

    python3 benchmark/selfcheck/summarize_trace.py <trace.json.gz> [--trim OUT N_MODULE_RUNS]

``--trim`` writes the events of the first N runs of the busiest program
as a fixture for the self-tests.
"""
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark.lib import trace_reduce as tr  # noqa: E402


def main() -> int:
    with gzip.open(sys.argv[1], "rt") as f:
        raw = json.load(f)
    for p in raw["planes"]:
        print("plane", p["name"])
        for ln in p["lines"]:
            print("  line", repr(ln["name"]), len(ln["events"]), "events")
    print("modules", json.dumps(tr.module_names(raw)))
    print("top ops", json.dumps(tr.top_ops(raw, 25)))
    print("idle gaps", json.dumps(tr.idle_gaps(raw, 5)))
    lo, hi = tr.window_ns(raw)
    print("window_s", (hi - lo) / 1e9, "busy_s", tr.busy_ns(raw) / 1e9)
    ops = tr.line_events(tr.device_planes(raw)[0], tr.OPS_LINE)
    seen = set()
    for ev in ops:
        if tr.op_name(ev) not in seen and len(seen) < 40:
            seen.add(tr.op_name(ev))
            print("event", json.dumps(ev)[:500])
    kernels = {}
    for ev in ops:
        if tr.is_pallas(ev):
            kernels[tr.op_name(ev)] = kernels.get(tr.op_name(ev), 0) + 1
    print("pallas kernels", json.dumps(kernels))
    if "--trim" in sys.argv:
        i = sys.argv.index("--trim")
        out, n = sys.argv[i + 1], int(sys.argv[i + 2])
        mods = tr.line_events(tr.device_planes(raw)[0], tr.MODULES_LINE)
        busiest = max(tr.module_names(raw).items(), key=lambda kv: kv[1][1])[0]
        runs = [e for e in mods if e[0].split("(", 1)[0] == busiest][:n]
        cut = tr.trim(raw, runs[0][1], runs[-1][1] + runs[-1][2])
        with gzip.open(out, "wt") as f:
            json.dump(cut, f)
        print("trimmed to", os.path.getsize(out), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
