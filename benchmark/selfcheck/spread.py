"""Several runs of one cell, one process each, and the spread of each
metric: how a bound is set and checked (a builder's tool, not the
driver's).

    python3 benchmark/selfcheck/spread.py --workload <cell> --seeds 1,2,3,4,5,6 [--seconds S] [--trace 0|1]

Each run is ``benchmark/run.py`` in a child (this process never touches
JAX, so the chip is the child's). Every line the runs print is kept in
``chiprun_out/<cell>.log``; the result lines are printed compactly, then
per metric the median and the spread: the distance between the first and
third quartile (``statistics.quantiles(values, n=4)``) as a share of the
median. A bound is about five times the widest spread, never under 1 %.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default=None)
    ap.add_argument("--trace", default="0")
    args = ap.parse_args()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = os.path.join(ROOT, "chiprun_out", args.workload + ".log")
    results = []
    for seed in args.seeds.split(","):
        cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
               "--workload", args.workload, "--seed", seed,
               "--trace", args.trace]
        if args.seconds:
            cmd += ["--seconds", args.seconds]
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
        with open(log, "a") as f:
            f.write(f"# seed {seed} rc {proc.returncode}\n")
            f.write("\n".join(lines) + "\n")
            if proc.returncode != 0:
                f.write(proc.stderr[-4000:] + "\n")
        if proc.returncode != 0 or not lines:
            print(f"seed {seed}: rc {proc.returncode}",
                  proc.stderr[-1500:], flush=True)
            continue
        for ln in lines[:-1]:
            rec = json.loads(ln)
            if rec.get("phase") in ("setup", "check"):
                print(" ", json.dumps(rec)[:900], flush=True)
        res = json.loads(lines[-1])
        results.append(res)
        print(f"seed {seed}: correct={res['correct']} "
              f"attempted={res['attempted']} failed={res['failed']} "
              f"peak={res['device'].get('memory_peak_bytes')} "
              + " ".join(f"{k}={v['value']:.6g}"
                         for k, v in res["metrics"].items()), flush=True)
    if len(results) >= 2:
        for name in results[0]["metrics"]:
            vals = [r["metrics"][name]["value"] for r in results
                    if name in r["metrics"]]
            # set-up: the first run compiles and is left out
            vals = vals[1:] if name == "setup_s" and len(vals) > 2 else vals
            if len(vals) >= 2:
                print(f"{name}: n={len(vals)} median="
                      f"{statistics.median(vals):.6g} spread="
                      f"{100 * spread(vals):.3f}% min={min(vals):.6g} "
                      f"max={max(vals):.6g}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
