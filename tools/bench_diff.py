"""bench_diff — metric-by-metric comparison of two BENCH records.

The repo accumulates ``BENCH_r*.json`` rounds (and ``serve_bench`` /
``bench.py`` JSONL logs), but until now comparing two rounds was a
human squinting at numbers — which is how a perf regression ships
silently. This tool makes the comparison mechanical and CI-able:

    python -m tools.bench_diff OLD.json NEW.json          # two files
    python -m tools.bench_diff --dir .                    # newest two
    python -m tools.bench_diff --dir . --baseline BASE.json
    python -m tools.bench_diff OLD.json NEW.json --threshold 0.05
    python -m tools.bench_diff NEW.json --write-baseline BASE.json

Exit status: 0 when nothing regressed (identical records compare
clean by construction), 1 on any regression past threshold, 2 on
usage/load errors — so a script or CI can gate on it directly.

**Direction-aware**: a +20% on ``tokens_per_sec`` is an improvement;
a +20% on ``tpot_p50`` is a regression. Direction is classified from
the metric name (latency/seconds/overhead → lower-better;
throughput/goodput/mfu/hit-rate → higher-better) with the record's
``unit`` as a fallback; unclassifiable metrics are reported
informationally and never fail the gate.

**Format-tolerant** — accepts every shape the repo produces:
- the root ``BENCH_r*.json`` wrapper ``{"n", "cmd", "rc", "tail",
  "parsed"}`` (records are parsed out of the embedded stdout tail);
- raw JSONL from ``bench.py`` / ``tools/serve_bench.py`` (one
  ``{"metric", "value", "unit", ...}`` object per line, non-JSON
  lines skipped);
- a JSON array of such records;
- a ``--write-baseline`` file this tool wrote earlier.

**Provenance-aware**: when both sides carry an ``env`` header
(``bench_env`` record or wrapper field — PR 16 provenance stamping),
mismatched backend / device_kind / device_count prints a WARNING —
cross-machine comparisons are unsound and should be read as such.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

# metric-name substrings → direction. First list wins on conflict
# ("tokens_per_sec_overhead" would be odd, but overhead is the gate).
_LOWER_BETTER = (
    "ttft", "tpot", "latency", "seconds", "compile", "overhead",
    "occupancy", "recovery", "p50", "p90", "p99", "stall", "loss",
    "bytes", "cost", "miss", "preempt", "evict", "syncs",
)
_HIGHER_BETTER = (
    "tokens_per_sec", "throughput", "goodput", "survival", "capacity",
    "speedup", "hit_rate", "tokens_saved", "mfu", "accept", "tok_s",
    "per_chip", "bandwidth", "flops",
)
_LOWER_UNITS = ("s", "ms", "us", "seconds", "x (on/off)", "bytes")
_HIGHER_UNITS = ("tokens/s", "tokens/s/chip", "req/s", "1 (ratio)")


def classify(metric: str, unit: str = "") -> Optional[str]:
    """'lower' | 'higher' | None (unknown — informational only)."""
    low = metric.lower()
    for sub in _HIGHER_BETTER:
        if sub in low:
            return "higher"
    for sub in _LOWER_BETTER:
        if sub in low:
            return "lower"
    u = (unit or "").lower()
    if u in _HIGHER_UNITS:
        return "higher"
    if u in _LOWER_UNITS:
        return "lower"
    return None


def _records_from_text(text: str) -> List[Dict[str, Any]]:
    """Pull ``{"metric": ...}`` records out of mixed stdout (JSONL
    interleaved with XLA warnings — the wrapper's ``tail``)."""
    out = []
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "metric" in rec:
            out.append(rec)
    return out


def load_records(path: str) -> Tuple[List[Dict[str, Any]],
                                     Optional[Dict[str, Any]]]:
    """(records, env_header) from any supported file shape."""
    with open(path) as f:
        text = f.read()
    recs: List[Dict[str, Any]] = []
    env: Optional[Dict[str, Any]] = None
    doc = None
    try:
        doc = json.loads(text)
    except ValueError:
        pass
    if isinstance(doc, dict) and "tail" in doc:        # BENCH_r wrapper
        recs = _records_from_text(str(doc.get("tail", "")))
        parsed = doc.get("parsed")
        if isinstance(parsed, dict) and "metric" in parsed \
                and not any(r.get("metric") == parsed.get("metric")
                            for r in recs):
            recs.append(parsed)
        env = doc.get("env")
    elif isinstance(doc, dict) and "records" in doc:   # our baseline
        recs = list(doc["records"])
        env = doc.get("env")
    elif isinstance(doc, list):                        # JSON array
        recs = [r for r in doc if isinstance(r, dict) and "metric" in r]
    elif isinstance(doc, dict) and "metric" in doc:    # single record
        recs = [doc]
    else:                                              # JSONL / mixed
        recs = _records_from_text(text)
    for r in recs:                       # env header travels as a record
        if r.get("metric") == "bench_env" and env is None:
            env = r
    recs = [r for r in recs if r.get("metric") != "bench_env"
            and isinstance(r.get("value"), (int, float))]
    return recs, env


def index(recs: List[Dict[str, Any]]) -> Dict[str, Dict[str, Any]]:
    """Latest record per metric name (later lines win — the JSONL
    convention everywhere else in the repo)."""
    out: Dict[str, Dict[str, Any]] = {}
    for r in recs:
        out[str(r["metric"])] = r
    return out


def diff(old: Dict[str, Dict[str, Any]],
         new: Dict[str, Dict[str, Any]],
         threshold: float) -> Tuple[List[dict], List[dict], List[dict]]:
    """(regressions, improvements, infos) over the shared metric set."""
    regressions, improvements, infos = [], [], []
    for metric in sorted(set(old) & set(new)):
        ov = float(old[metric]["value"])
        nv = float(new[metric]["value"])
        unit = new[metric].get("unit", old[metric].get("unit", ""))
        if ov == 0:
            ratio = None
            delta = None
        else:
            ratio = nv / ov
            delta = ratio - 1.0
        direction = classify(metric, unit)
        row = {"metric": metric, "old": ov, "new": nv, "unit": unit,
               "delta": delta, "direction": direction}
        if delta is None or direction is None:
            infos.append(row)
            continue
        bad = delta > threshold if direction == "lower" \
            else delta < -threshold
        good = delta < -threshold if direction == "lower" \
            else delta > threshold
        if bad:
            regressions.append(row)
        elif good:
            improvements.append(row)
        else:
            infos.append(row)
    return regressions, improvements, infos


def _fmt(row: dict) -> str:
    d = row["delta"]
    pct = f"{d * 100:+.1f}%" if d is not None else "n/a"
    arrow = {"lower": "↓ better", "higher": "↑ better",
             None: "?"}[row["direction"]]
    return (f"  {row['metric']:<48} {row['old']:>12.6g} -> "
            f"{row['new']:>12.6g} {pct:>8}  [{arrow}]"
            + (f" {row['unit']}" if row["unit"] else ""))


def _env_mismatch(env_a: Optional[dict], env_b: Optional[dict]
                  ) -> List[str]:
    if not env_a or not env_b:
        return []
    out = []
    for k in ("backend", "device_kind", "device_count", "jax"):
        va, vb = env_a.get(k), env_b.get(k)
        if va is not None and vb is not None and va != vb:
            out.append(f"{k}: {va!r} vs {vb!r}")
    return out


def _newest_two(dirpath: str) -> Tuple[str, str]:
    cands = sorted(glob.glob(os.path.join(dirpath, "BENCH_r*.json")))
    if len(cands) < 2:
        raise SystemExit(
            f"--dir {dirpath}: need >= 2 BENCH_r*.json files, "
            f"found {len(cands)}")
    return cands[-2], cands[-1]


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="direction-aware diff of two BENCH record files; "
                    "exit 1 on regression")
    ap.add_argument("files", nargs="*",
                    help="OLD NEW (two files), or one NEW with "
                         "--baseline/--write-baseline")
    ap.add_argument("--dir", help="compare the newest two "
                    "BENCH_r*.json in this directory")
    ap.add_argument("--baseline",
                    help="compare FILES[0] (or --dir newest) against "
                         "this baseline instead of the prior round")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="relative change that counts as a "
                         "regression/improvement (default 0.10)")
    ap.add_argument("--write-baseline", metavar="OUT",
                    help="write FILES[0]'s records (+env) as a "
                         "baseline file and exit 0")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable verdict on stdout")
    args = ap.parse_args(argv)

    try:
        if args.write_baseline:
            if len(args.files) != 1:
                ap.error("--write-baseline takes exactly one input file")
            recs, env = load_records(args.files[0])
            with open(args.write_baseline, "w") as f:
                json.dump({"records": sorted(
                    (index(recs)).values(),
                    key=lambda r: r["metric"]), "env": env,
                    "source": os.path.basename(args.files[0])},
                    f, indent=1)
            print(f"baseline: {len(index(recs))} metrics -> "
                  f"{args.write_baseline}")
            return 0
        if args.dir:
            old_path, new_path = _newest_two(args.dir)
            if args.files:
                new_path = args.files[0]
        elif len(args.files) == 2:
            old_path, new_path = args.files
        elif len(args.files) == 1 and args.baseline:
            old_path, new_path = args.baseline, args.files[0]
        else:
            ap.error("give OLD NEW, or --dir DIR, or NEW --baseline B")
        if args.baseline:
            old_path = args.baseline
        old_recs, old_env = load_records(old_path)
        new_recs, new_env = load_records(new_path)
    except OSError as e:
        print(f"bench_diff: {e}", file=sys.stderr)
        return 2

    old_idx, new_idx = index(old_recs), index(new_recs)
    if not old_idx or not new_idx:
        print(f"bench_diff: no metric records in "
              f"{old_path if not old_idx else new_path}",
              file=sys.stderr)
        return 2
    regs, imps, infos = diff(old_idx, new_idx, args.threshold)
    warns = _env_mismatch(old_env, new_env)

    if args.json:
        print(json.dumps({
            "old": old_path, "new": new_path,
            "threshold": args.threshold,
            "regressions": regs, "improvements": imps,
            "unchanged_or_unclassified": len(infos),
            "env_mismatch": warns,
            "verdict": "regressed" if regs else "clean"}))
    else:
        print(f"bench_diff: {os.path.basename(old_path)} -> "
              f"{os.path.basename(new_path)}  "
              f"({len(set(old_idx) & set(new_idx))} shared metrics, "
              f"threshold {args.threshold:.0%})")
        for w in warns:
            print(f"  WARNING env mismatch — {w} (comparison may be "
                  f"unsound)")
        if regs:
            print(f"REGRESSIONS ({len(regs)}):")
            for r in regs:
                print(_fmt(r))
        if imps:
            print(f"improvements ({len(imps)}):")
            for r in imps:
                print(_fmt(r))
        if not regs and not imps:
            print("  no change past threshold")
        only_old = sorted(set(old_idx) - set(new_idx))
        only_new = sorted(set(new_idx) - set(old_idx))
        if only_old:
            print(f"  dropped metrics: {', '.join(only_old[:8])}"
                  + (" ..." if len(only_old) > 8 else ""))
        if only_new:
            print(f"  new metrics: {', '.join(only_new[:8])}"
                  + (" ..." if len(only_new) > 8 else ""))
        print(f"verdict: {'REGRESSED' if regs else 'clean'}")
    return 1 if regs else 0


if __name__ == "__main__":
    sys.exit(main())
