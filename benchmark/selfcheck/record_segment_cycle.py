"""Record the fixture of ``test_segment_cycle.py`` on the chip.

    python3 benchmark/selfcheck/record_segment_cycle.py OUT.json.gz N_LOOPS <run.py's arguments>

As ``record_host_spans.py``, whose ``cut`` it uses: runs
``benchmark/run.py`` in this process (``--workload serve-mistral-7b-chat
--trace 1 ...``) and, where the harness has run the per-layer readers,
cuts N consecutive iterations of the scheduler's loop out of the middle
of the traced window (0: every whole one). The six readers of the
segment's cycle are then run on the cut and their values, with the
``segment_cycle`` line's counts, written beside it as ``expect``. Of the
device's operations the cut keeps the outermost (a ``while`` and not what
runs inside it): the union of their intervals, which is all these readers
and ``host_spans`` take from them, is that of all, at a tenth of the size.
"""
import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.lib import segment_cycle as sc  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402
from benchmark.selfcheck.record_host_spans import (cut, load,  # noqa: E402
                                                   reader)

READERS = ("segment_cycle_host_ms", "segment_wake_ms", "segment_launch_ms",
           "segment_collect_ms", "table_upload_ms",
           "table_upload_changed_share")


def outermost(events: list) -> list:
    """The events of one line that lie in no other."""
    out, hi = [], -1
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        if ev[1] >= hi:
            out.append(ev)
            hi = ev[1] + ev[2]
    return out


def thin(fixture: dict) -> dict:
    for plane in fixture["raw"]["planes"]:
        for line in plane["lines"]:
            if line["name"] == tr.OPS_LINE:
                line["events"] = outermost(line["events"])
    return fixture


def expect(fixture: dict) -> dict:
    ctx = dict(fixture)
    out = {name: reader(name).read(ctx) for name in READERS}
    view = sc.view(ctx)
    out["segments"] = len(view["segments"])
    out["segments_whole"] = sum(s["run"] is not None
                                for s in view["segments"])
    out["cycles"] = len(view["cycles"])
    return out


def main() -> int:
    out, loops, argv = sys.argv[1], int(sys.argv[2]), sys.argv[3:]
    run = load(os.path.join(ROOT, "benchmark", "run.py"), "benchmark_run")
    per_layer = run.per_layer

    def recording(bench, cell, ctx):
        metrics = per_layer(bench, cell, ctx)
        fixture = thin(cut(ctx, loops))
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
