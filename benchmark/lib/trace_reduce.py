"""From a profiler trace to numbers: the reduction every PR shares.

Two stages. ``load_xplane`` turns the profiler's ``.xplane.pb`` into a
plain dict (``jax.profiler.ProfileData``, nothing else):

    {"planes": [{"name": str, "lines": [{"name": str, "events":
        [[name, start_ns, dur_ns, {stat: value}], ...]}]}]}

Everything after that is arithmetic on the dict, checked on the CPU
against a trimmed recorded trace (``benchmark/selfcheck``).

What a TPU trace holds (looked at by hand, v5e, JAX 0.9.0, PR 24): one
plane per chip named ``/device:TPU:<n>``; on it the line ``XLA Modules``
has one event per run of a compiled program, named ``jit_<python
function>(<id>)``, and the line ``XLA Ops`` one event per HLO operation,
nested where an operation (a ``while``) contains others. An operation's
event is named by its whole HLO text, ``%fusion.436.remat = bf16[4,2048,
2048]{...} fusion(...)``; its stats hold only device offsets. A Pallas
kernel is a ``custom-call`` with ``custom_call_target="tpu_custom_call"``,
named after the ``pallas_call``'s ``name`` (``%paged_decode.12``,
``%rms_norm.3``, ``%fused_rope.55``) or, where the kernel has none, after
the JAX scope around it (the flash kernels show as ``%closed_call``,
``%rematted_computation``, ``%checkpoint``, ``%prefill_one``): so a kernel
is recognised by its call target, and told from another by name only
where it has one. Busy time is the union of the ``XLA Ops`` intervals.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, planes=DEVICE_PLANE) -> dict:
    """The planes whose name matches ``planes``, as the plain dict."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not planes.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                stats = {}
                for key, val in ev.stats:
                    if isinstance(val, (str, int, float)):
                        stats[key] = val
                events.append([ev.name, int(ev.start_ns),
                               int(ev.duration_ns), stats])
            lines.append({"name": line.name, "events": events})
        out.append({"name": plane.name, "lines": lines})
    return {"planes": out}


def device_planes(raw: dict) -> list:
    return [p for p in raw["planes"] if DEVICE_PLANE.match(p["name"])]


def line_events(plane: dict, line_name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == line_name:
            return sorted(line["events"], key=lambda e: (e[1], -e[2]))
    return []


def union_ns(intervals) -> int:
    """Total length covered by [start, end) intervals, overlaps once."""
    total, cur_lo, cur_hi = 0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def self_times(events) -> list:
    """Per event of one line (sorted by start, longest first), its
    duration less the part its nested events cover: a ``while`` that
    holds a layer's operations keeps only what is its own."""
    selfs = [e[2] for e in events]
    stack = []          # indices of the events open at this point
    for i, (_, start, dur, _) in enumerate(events):
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= dur
        stack.append(i)
    return [max(s, 0) for s in selfs]


PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
_SUFFIX = re.compile(r"(\.\d+|\.remat\d*|\.clone)+$")
_RESULT = re.compile(r"[a-z]+\d*\[[\d,]*\]")


def op_name(event) -> str:
    """``%fusion.436.remat = bf16[...] fusion(...)`` -> ``fusion``: the
    instruction's name without its counters, so that the same operation
    of every layer and step adds up."""
    return _SUFFIX.sub("", event[0].split(" = ", 1)[0].lstrip("%"))


def op_label(event) -> str:
    """The name with the first array type of the result, which tells one
    ``fusion`` from another: ``fusion bf16[4,2048,8192]``."""
    head = event[0].split(" = ", 1)
    m = _RESULT.search(head[1]) if len(head) == 2 else None
    return op_name(event) + (" " + m.group(0) if m else "")


def is_pallas(event) -> bool:
    return PALLAS_TARGET in event[0]


def window_ns(raw: dict) -> tuple:
    """[first start, last end) of the device operations of all chips."""
    los, his = [], []
    for plane in device_planes(raw):
        evs = line_events(plane, OPS_LINE)
        if evs:
            los.append(evs[0][1])
            his.append(max(e[1] + e[2] for e in evs))
    if not los:
        raise ValueError("the trace holds no device operation")
    return min(los), max(his)


def busy_ns(raw: dict) -> float:
    """Device busy time: union of the operation intervals, averaged over
    the chips in the trace."""
    per_chip = [union_ns((e[1], e[1] + e[2])
                         for e in line_events(p, OPS_LINE))
                for p in device_planes(raw)]
    if not per_chip:
        raise ValueError("the trace holds no device plane")
    return sum(per_chip) / len(per_chip)


def module_runs(raw: dict, names) -> list:
    """Durations (ns) of the runs of the compiled programs whose module
    name starts with one of ``names`` (``jit_segment`` matches
    ``jit_segment(42)``), over all chips' first plane."""
    plane = device_planes(raw)[0]
    out = []
    for ev in line_events(plane, MODULES_LINE):
        base = ev[0].split("(", 1)[0]
        if base in names:
            out.append(ev[2])
    return out


def mean_run_ns(raw: dict, names) -> float:
    """Mean device time of one run of the programs ``names``; an error
    that lists the programs seen when none of them ran."""
    runs = module_runs(raw, names)
    if not runs:
        raise ValueError(f"no run of {names} in the trace; programs seen: "
                         f"{sorted(module_names(raw))}")
    return sum(runs) / len(runs)


def module_names(raw: dict) -> dict:
    plane = device_planes(raw)[0]
    out = {}
    for ev in line_events(plane, MODULES_LINE):
        base = ev[0].split("(", 1)[0]
        n, ns = out.get(base, (0, 0))
        out[base] = (n + 1, ns + ev[2])
    return out


def op_self_ns(raw: dict, keep=None) -> int:
    """Sum of the self times of the device operations (first chip) for
    which ``keep(event)`` holds; of all operations when it is None."""
    evs = line_events(device_planes(raw)[0], OPS_LINE)
    return sum(s for ev, s in zip(evs, self_times(evs))
               if keep is None or keep(ev))


def idle_share(raw: dict) -> float:
    """1 - busy time / traced window (first operation's start to the last
    one's end)."""
    lo, hi = window_ns(raw)
    return 1.0 - busy_ns(raw) / (hi - lo)


def pallas_share(raw: dict) -> float:
    """Self time of the Pallas kernels over the device's busy time; an
    error when the trace holds no kernel at all."""
    kernel = op_self_ns(raw, is_pallas)
    if not kernel:
        raise ValueError(f"no operation calls {PALLAS_TARGET}")
    return kernel / busy_ns(raw)


def top_ops(raw: dict, n: int = 10) -> list:
    """[[label, seconds], ...]: the operations with most self time, the
    same operation of every layer and step added up."""
    evs = line_events(device_planes(raw)[0], OPS_LINE)
    by = {}
    for ev, s in zip(evs, self_times(evs)):
        by[op_label(ev)] = by.get(op_label(ev), 0) + s
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(raw: dict, n: int = 10) -> list:
    """[[label, seconds], ...]: the idle time of the first chip, added up
    by the programs that ran before and after each gap (``jit_segment ->
    jit_prefill_one x12``: twelve such gaps), most time first. What the
    HOST did in a gap needs the program's spans on the device's clock,
    which they are not yet."""
    plane = device_planes(raw)[0]
    ops = line_events(plane, OPS_LINE)
    mods = line_events(plane, MODULES_LINE)
    starts = [ev[1] for ev in mods]

    def module_at(t):
        i = bisect.bisect_right(starts, t)
        return mods[i - 1][0].split("(", 1)[0] if i else "?"

    by, hi = {}, None
    for ev in ops:
        if hi is not None and ev[1] > hi:
            key = f"{module_at(hi - 1)} -> {module_at(ev[1])}"
            count, ns = by.get(key, (0, 0))
            by[key] = (count + 1, ns + ev[1] - hi)
        hi = ev[1] + ev[2] if hi is None else max(hi, ev[1] + ev[2])
    top = sorted(by.items(), key=lambda kv: -kv[1][1])[:n]
    return [[f"{key} x{count}", ns / 1e9] for key, (count, ns) in top]


def trim(raw: dict, lo_ns: int, hi_ns: int) -> dict:
    """The events that start in [lo, hi): for the recorded fixture."""
    planes = []
    for p in raw["planes"]:
        lines = [{"name": ln["name"],
                  "events": [e for e in ln["events"]
                             if lo_ns <= e[1] < hi_ns]}
                 for ln in p["lines"]]
        planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}
