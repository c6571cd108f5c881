"""The host's cycle between two decode segments, piece by piece.

Between the end of one ``jit_segment`` run and the start of the next the
chip waits for the host. Since PR 36 the program names what the host
does there with spans of its own (``paddle_tpu.tracing``, each also a
``pt:<name>`` event on the device's clock, ``lib/host_spans.py``):

    engine.segment > engine.dispatch   arguments built, the jitted call
                   > engine.wait       the one readback: the host waits,
                                       then is woken
                   > engine.collect    tokens to their requests, retirement
    collect                            the scheduler: finish and stream
    gap, gap.pressure, control         the scheduler, before a segment
    segment > engine.tables            the page-table upload

A *cycle* is a pair of consecutive ``engine.segment`` spans on the
scheduler's thread with no admission (``admit`` / ``admit.begin`` /
``prefill_chunk``) between them; each span's ``jit_segment`` run is found
as ``host_spans.segment_runs`` finds it, and a segment whose run the
traced window's edge cuts has none and makes no cycle.

``view(ctx)`` gives, once per run (kept in ``ctx``), the per-segment and
per-cycle times in ns on the device's clock, and prints one diagnostic
line, ``{"phase": "segment_cycle", ...}``: the median cycle, the median
of each piece in the order the host runs them, the residue no span
names, and what sizes ROADMAP S12's leads (``args``, ``pushed`` a cycle,
the launch and the upload of the segments that FOLLOW an admission, when
the threads the last collection woke have long run, and the rank
correlations of the wake-up with the rows and of the launch with the
handles pushed just before it; a wake-up comes a whole run after the last
push, when those threads are long done, so no such number is given for it).

A program from before these spans (no event of any of the new names in
the ring: the parent under this PR's benchmark files) has nothing to
read: ``view`` and ``ring_events`` return None. A program that HAS them
and ran an ``engine.segment`` without its children is a fault and raises
``ValueError``.
"""
from __future__ import annotations

import json
import statistics

from benchmark.lib import host_spans as hs

MODULE = "jit_segment"
SEGMENT = "engine.segment"
DISPATCH, WAIT, COLLECT = "engine.dispatch", "engine.wait", "engine.collect"
TABLES = "engine.tables"
CHILDREN = (DISPATCH, WAIT, COLLECT)
NEW = CHILDREN + (TABLES,)
ADMISSION = ("admit", "admit.begin", "prefill_chunk")
# the scheduler's own spans a cycle crosses, in the order it runs them
BETWEEN = ("collect", "gap", "gap.pressure", "control")
PIECES = ("wake", COLLECT) + BETWEEN + (
    "segment_before_tables", TABLES, "counter_sums", "launch")


def has_new_spans(ctx: dict) -> bool:
    return any(ev["phase"] in NEW for ev in ctx["spans"])


def ring_events(ctx: dict, phase: str):
    """The ring's events of one of the new spans; None for a program from
    before them; ``ValueError`` when the program has the new spans, ran
    segments, and recorded none of this one."""
    if not has_new_spans(ctx):
        return None
    out = [ev for ev in ctx["spans"] if ev["phase"] == phase]
    if not out and any(ev["phase"] == SEGMENT for ev in ctx["spans"]):
        raise ValueError(f"the ring holds {SEGMENT} spans and no {phase}")
    return out or None


def median_ms(values):
    return statistics.median(values) / 1e6 if values else None


def rank_correlation(xs, ys):
    """Spearman's: Pearson's on the ranks, ties at their mean rank; None
    under three points or where one side does not vary."""
    def ranks(v):
        order = sorted(range(len(v)), key=v.__getitem__)
        out, i = [0.0] * len(v), 0
        while i < len(order):
            j = i
            while j + 1 < len(order) and v[order[j + 1]] == v[order[i]]:
                j += 1
            for k in range(i, j + 1):
                out[order[k]] = (i + j) / 2.0
            i = j + 1
        return out

    if len(xs) < 3:
        return None
    rx, ry = ranks(xs), ranks(ys)
    mx, my = sum(rx) / len(rx), sum(ry) / len(ry)
    sxx = sum((a - mx) ** 2 for a in rx)
    syy = sum((b - my) ** 2 for b in ry)
    if not sxx or not syy:
        return None
    return sum((a - mx) * (b - my) for a, b in zip(rx, ry)) / (sxx * syy) ** .5


def segments_of(ctx: dict, v: dict) -> list:
    """One dict per traced ``engine.segment`` span of the scheduler's
    thread, by start: its children, its run with the wake-up after it
    and the launch before it (None: cut by the window), and the
    ``segment`` span and upload beside it."""
    spans = v["spans"]
    line = hs.scheduler_line(spans)
    kids = {}
    for s in spans.values():
        kids.setdefault(s["parent"], {})[s["name"]] = s
    runs = {a.get("span.id"): (lo, hi)
            for lo, hi, a in hs.segment_runs(ctx, MODULE, SEGMENT)}
    out = []
    for s in sorted((s for s in spans.values()
                     if s["name"] == SEGMENT and s["line"] == line),
                    key=lambda s: s["start"]):
        mine = kids.get(s["id"], {})
        missing = [c for c in CHILDREN if c not in mine]
        if missing:
            raise ValueError(f"{SEGMENT} span {s['id']} has no {missing} "
                             f"child in the trace")
        outer, run = spans.get(s["parent"]), runs.get(s["id"])
        out.append({"span": s, "run": run, "outer": outer,
                    "tables": kids.get(s["parent"], {}).get(TABLES)
                    if outer else None,
                    "wake": run and mine[WAIT]["end"] - run[1],
                    "launch": run and run[0] - mine[DISPATCH]["start"],
                    **{c: mine[c] for c in CHILDREN}})
    return out


def cycle_of(a: dict, b: dict, between: list) -> dict:
    """The pieces (ns) of the cycle from segment ``a``'s run to segment
    ``b``'s; ``between``: the scheduler's spans that lie between the two
    ``engine.segment`` spans."""
    p = dict.fromkeys(PIECES, 0)
    p["wake"] = a["wake"]
    p[COLLECT] = a[COLLECT]["end"] - a[COLLECT]["start"]
    for s in between:
        if s["name"] in BETWEEN or s["name"] == TABLES:
            p[s["name"]] += s["end"] - s["start"]
    before = b["span"]["start"]
    if b["tables"] is not None:
        p["segment_before_tables"] = (b["tables"]["start"]
                                      - b["outer"]["start"])
        before = b["tables"]["end"]
    p["counter_sums"] = b[DISPATCH]["start"] - before
    p["launch"] = b["launch"]
    p["cycle"] = b["run"][0] - a["run"][1]
    p["residue"] = p["cycle"] - sum(p[k] for k in PIECES)
    return p


def view(ctx: dict):
    """Segments and cycles of the traced window; None for a program
    without the new spans. Built once, kept in ``ctx``; the first build
    prints the ``segment_cycle`` line."""
    if "segment_cycle" in ctx:
        return ctx["segment_cycle"]
    v = hs.view(ctx) if has_new_spans(ctx) else None
    if v is None:
        ctx["segment_cycle"] = None
        return None
    segs = segments_of(ctx, v)
    line = hs.scheduler_line(v["spans"])
    others = sorted((s for s in v["spans"].values() if s["line"] == line
                     and s["name"] in ADMISSION + BETWEEN + (TABLES,)),
                    key=lambda s: s["start"])
    cycles, pushed, admitted = [], [], []
    for a, b in zip(segs, segs[1:]):
        if a["run"] is None or b["run"] is None:
            continue
        between = [s for s in others if a["span"]["end"] <= s["start"]
                   and s["end"] <= b["span"]["start"]]
        if any(s["name"] in ADMISSION for s in between):
            admitted.append(b)
            continue
        cycles.append(cycle_of(a, b, between))
        pushed.append(sum(s["attrs"].get("pushed", 0) for s in between
                          if s["name"] == "collect"))
        b["pushed_before"] = pushed[-1]
    whole = [s for s in segs if s["run"] is not None]
    after = [s for s in whole if "pushed_before" in s]
    out = {"segments": segs, "cycles": cycles,
           "wake_ns": [s["wake"] for s in whole],
           "launch_ns": [s["launch"] for s in whole]}
    args = sorted({s[DISPATCH]["attrs"].get("args") for s in segs} - {None})
    print(json.dumps({
        "phase": "segment_cycle", "segments": len(segs),
        "segments_whole": len(whole), "cycles": len(cycles),
        "cycle_ms": median_ms([c["cycle"] for c in cycles]),
        "pieces_ms": {k: median_ms([c[k] for c in cycles])
                      for k in PIECES},
        "residue_ms": median_ms([c["residue"] for c in cycles]),
        "args": args[0] if len(args) == 1 else args,
        "pushed_per_cycle": sum(pushed) / len(pushed) if pushed else None,
        # a segment that follows an admission: the threads the last
        # collection woke have had the admission's wait to run in
        "after_admission": {
            "segments": len(admitted),
            "launch_ms": median_ms([s["launch"] for s in admitted]),
            "tables_ms": median_ms([s["tables"]["end"] - s["tables"]["start"]
                                    for s in admitted if s["tables"]])},
        "wake_vs_rows": rank_correlation(
            [s["span"]["attrs"].get("rows", 0) for s in whole],
            out["wake_ns"]),
        "launch_vs_pushed": rank_correlation(
            [s["pushed_before"] for s in after],
            [s["launch"] for s in after])}), flush=True)
    ctx["segment_cycle"] = out
    return out
