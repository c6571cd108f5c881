"""How often the scheduler hands the handles what they are owed AFTER a
dispatch, the next segment's or an admission's (ISSUE 37), read from one
run of a benchmark cell.

Runs ``benchmark/run.py``'s own ``main`` in this process with the arguments
given (a traced run: ``--trace 1`` turns the program's ring on), then sums
the ring's ``push`` spans and prints one more line:

    {"phase": "push_share", "spans": n, "handles": h,
     "after_dispatch_handles": a, "share": a / h}

``handles`` counts each span's handles, so a handle flushed in two cycles
counts twice. A program from before the ``push`` span prints zeros and a
share of None.

    python3 experiments/exp_push_share.py --workload <cell> --seed <n> \\
        --trace 1
"""
import importlib.util
import json
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def main(argv) -> int:
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    rc = run.main(argv)
    from paddle_tpu import tracing

    pushes = [e for e in tracing.events() if e["phase"] == "push"]
    n = sum(e["handles"] for e in pushes)
    after = sum(e["handles"] for e in pushes if e["after_dispatch"])
    print(json.dumps({"phase": "push_share", "spans": len(pushes),
                      "handles": n, "after_dispatch_handles": after,
                      "share": after / n if n else None}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
