"""The program's spans on the device's clock, and the chip's idle time
split by what the host was doing.

``paddle_tpu.tracing`` enters a ``jax.profiler.TraceAnnotation`` named
``pt:<phase>`` around every span, carrying the span's ``id``, its
``parent`` and ``rid`` (a root also its ``pid``). Inside a profiler
session these land in the ``/host:CPU`` plane of the ``.xplane.pb``, one
line per thread, on the clock of the device planes. The ring
(``ctx["spans"]``) holds the same spans with their attributes; the id
joins the two.

``view(ctx)`` gives, once per run (kept in ``ctx``):

``spans``    every ``pt:*`` event as a dict (``id``, ``parent``, ``name``
             without the prefix, ``start``, ``end`` in trace ns, ``line``,
             and the ring's attributes under ``attrs``), by id: a tree.
``idle``     the first chip's idle intervals (the gaps in the union of
             its ``XLA Ops``, as ``trace_reduce.idle_share`` counts them)
             split by the innermost span open on the scheduler's thread
             at each instant: ns per span id, 0 for "no span open".
``tree``     ``{id: (name, parent)}`` of the ring and the host plane
             together. A span that was open when the profiler session
             began or ended is in the ring only (the profiler writes a
             span it saw begin and end): its children in the trace
             still find their ancestors here.

The harness does not hand a reader the trace's path, so the host plane
is found: the newest ``*.xplane.pb`` under
``<tmp>/bench_*/trace/`` whose ``pt:step`` events carry this process's
pid. A caller that has the host plane already (the self-test) puts it
in ``ctx["host"]``.

A program from before the spans had ids (``span.id`` missing from the
ring) has nothing to read: ``view`` returns None. A ring WITH ids and a
trace without ``pt:*`` events is a fault and raises.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import tempfile

from benchmark.lib import trace_reduce as tr

HOST_PLANE = re.compile(r"^/host:CPU$")
PREFIX = "pt:"
ROOT = "step"                       # the scheduler's loop iteration
NO_SPAN = "(no span)"


def pt_events(host: dict) -> dict:
    """{id: span} of the ``pt:*`` events of the host plane."""
    out = {}
    for plane in host["planes"]:
        for n, line in enumerate(plane["lines"]):
            for name, start, dur, stats in line["events"]:
                if name.startswith(PREFIX) and "id" in stats:
                    out[stats["id"]] = {
                        "id": stats["id"], "parent": stats.get("parent", 0),
                        "name": name[len(PREFIX):], "start": start,
                        "end": start + dur, "line": n,
                        "rid": stats.get("rid"), "pid": stats.get("pid"),
                        "attrs": {}}
    return out


def find_host_plane(pid: int) -> dict:
    """The host plane of this process's newest profiler session under
    the harness's temporary directories."""
    found = sorted(glob.glob(os.path.join(
        tempfile.gettempdir(), "bench_*", "trace", "plugins", "profile",
        "*", "*.xplane.pb")), key=os.path.getmtime, reverse=True)
    for path in found:
        host = tr.load_xplane(path, planes=HOST_PLANE)
        if any(s["name"] == ROOT and s["pid"] == pid
               for s in pt_events(host).values()):
            return host
    raise ValueError(
        f"no profiler trace with a pt:{ROOT} span of process {pid} among "
        f"{len(found)} under {tempfile.gettempdir()}/bench_*/trace")


def scheduler_line(spans: dict) -> int:
    """The host-plane line (thread) that holds the ``step`` spans."""
    count = {}
    for s in spans.values():
        if s["name"] == ROOT:
            count[s["line"]] = count.get(s["line"], 0) + 1
    if not count:
        raise ValueError(f"the host plane holds no pt:{ROOT} span")
    return max(count, key=count.get)


def innermost(spans) -> list:
    """Spans of ONE thread (properly nested) -> disjoint, sorted
    ``[lo, hi, id]`` pieces, each labelled with the innermost span open
    in it. Time no span covers is left out."""
    out, stack, t = [], [], 0

    def close(until):
        nonlocal t
        while stack and stack[-1]["end"] <= until:
            top = stack.pop()
            if top["end"] > t:
                out.append([t, top["end"], top["id"]])
                t = top["end"]

    for s in sorted(spans, key=lambda s: (s["start"], -s["end"])):
        close(s["start"])
        if stack and s["start"] > t:
            out.append([t, s["start"], stack[-1]["id"]])
        t = max(t, s["start"])
        stack.append(s)
    close(float("inf"))
    return out


def idle_intervals(raw: dict) -> list:
    """[lo, hi) gaps in the union of the first chip's operations."""
    ops = tr.line_events(tr.device_planes(raw)[0], tr.OPS_LINE)
    out, hi = [], None
    for ev in ops:
        if hi is not None and ev[1] > hi:
            out.append((hi, ev[1]))
        hi = ev[1] + ev[2] if hi is None else max(hi, ev[1] + ev[2])
    return out


def split_idle(gaps: list, pieces: list) -> dict:
    """ns of ``gaps`` per span id of ``pieces`` (0: no span open)."""
    by = {}
    starts = [p[0] for p in pieces]
    for lo, hi in gaps:
        covered = 0
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        while i < len(pieces) and pieces[i][0] < hi:
            a, b = max(lo, pieces[i][0]), min(hi, pieces[i][1])
            if b > a:
                by[pieces[i][2]] = by.get(pieces[i][2], 0) + b - a
                covered += b - a
            i += 1
        if hi - lo > covered:
            by[0] = by.get(0, 0) + hi - lo - covered
    return by


def under(sid: int, tree: dict, names) -> bool:
    """Whether span ``sid`` or one of its ancestors in ``tree``
    (``{id: (name, parent)}``) is named in ``names``."""
    while sid in tree:
        name, sid = tree[sid]
        if name in names:
            return True
    return False


def view(ctx: dict):
    """The spans as a tree and the idle split; None for a program whose
    spans have no ids. Built once, kept in ``ctx``; the first build
    prints the ``idle_by_span`` line."""
    if "host_spans" in ctx:
        return ctx["host_spans"]
    ring = {ev["span.id"]: ev for ev in ctx["spans"] if ev.get("span.id")}
    if not ring:
        ctx["host_spans"] = None
        return None
    if "host" not in ctx:
        ctx["host"] = find_host_plane(os.getpid())
    spans = pt_events(ctx["host"])
    if not spans:
        raise ValueError("the ring's spans have ids but the profiler's "
                         "host plane holds no pt:* event")
    for sid, s in spans.items():
        s["attrs"] = ring.get(sid, {})
    line = scheduler_line(spans)
    lo, hi = tr.window_ns(ctx["raw"])
    gaps = idle_intervals(ctx["raw"])
    idle = split_idle(gaps, innermost(
        [s for s in spans.values() if s["line"] == line]))
    by_name = {}
    for sid, ns in idle.items():
        name = spans[sid]["name"] if sid else NO_SPAN
        by_name[name] = by_name.get(name, 0) + ns
    tree = {sid: (ev["phase"], ev["span.parent"]) for sid, ev in ring.items()}
    tree.update((sid, (s["name"], s["parent"])) for sid, s in spans.items())
    out = {"spans": spans, "tree": tree, "idle": idle,
           "window_ns": hi - lo, "idle_ns": sum(idle.values())}
    print(json.dumps({
        "phase": "idle_by_span", "window_s": (hi - lo) / 1e9,
        "idle_s": out["idle_ns"] / 1e9, "gaps": len(gaps),
        "spans": len(spans),
        "by_span_s": {k: v / 1e9 for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])}}), flush=True)
    ctx["host_spans"] = out
    return out


def idle_share(ctx: dict, keep):
    """100 x (idle ns of the span ids for which ``keep(id, tree)``
    holds; id 0: no span open) / window; None without a view."""
    v = view(ctx)
    if v is None:
        return None
    ns = sum(n for sid, n in v["idle"].items() if keep(sid, v["tree"]))
    return 100.0 * ns / v["window_ns"]


def segment_runs(ctx: dict, module: str, span_name: str) -> list:
    """[(module start, module end, ring attributes)] for every run of the
    device program ``module`` (first chip) that lies whole inside the
    ``span_name`` span that dispatched it; [] without a view."""
    v = view(ctx)
    if v is None:
        return []
    segs = sorted((s for s in v["spans"].values()
                   if s["name"] == span_name), key=lambda s: s["start"])
    starts = [s["start"] for s in segs]
    out = []
    for ev in tr.line_events(tr.device_planes(ctx["raw"])[0],
                             tr.MODULES_LINE):
        if ev[0].split("(", 1)[0] != module:
            continue
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] + ev[2] <= segs[i]["end"]:
            out.append((ev[1], ev[1] + ev[2], segs[i]["attrs"]))
    return out
