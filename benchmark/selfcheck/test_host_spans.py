"""CPU self-tests of ``lib/host_spans.py`` and the five readers on it.

    python -m pytest benchmark/selfcheck -q

Synthetic spans with the split worked out by hand, then a few loops of
``serve-mistral-7b-chat`` recorded on the chip
(``record_host_spans.py``): the device's operations, the program's
``pt:*`` events from the profiler's host plane, the ring's events.
"""
import copy
import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import host_spans as hs  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402
from benchmark.selfcheck.record_host_spans import READERS, reader  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


# -- synthetic ----------------------------------------------------------------
def span(sid, parent, name, start, end, line=0):
    return {"id": sid, "parent": parent, "name": name, "start": start,
            "end": end, "line": line, "rid": None, "pid": None, "attrs": {}}


def synthetic_spans():
    # step [100, 400) > gap [110, 250) > admit [120, 200) > install [150, 180)
    #                 > segment [260, 380); a second step [500, 600)
    return [span(1, 0, "step", 100, 400), span(2, 1, "gap", 110, 250),
            span(3, 2, "admit", 120, 200),
            span(4, 3, "engine.install", 150, 180),
            span(5, 1, "segment", 260, 380), span(6, 0, "step", 500, 600)]


def test_innermost_pieces_cover_each_span_once():
    pieces = hs.innermost(synthetic_spans())
    assert pieces == [
        [100, 110, 1], [110, 120, 2], [120, 150, 3], [150, 180, 4],
        [180, 200, 3], [200, 250, 2], [250, 260, 1], [260, 380, 5],
        [380, 400, 1], [500, 600, 6]]


def test_split_idle_by_hand():
    pieces = hs.innermost(synthetic_spans())
    # a gap before any span, one across admit > install > admit, one
    # across the end of a step into the uncovered time, one in no span
    gaps = [(50, 105), (140, 190), (390, 450), (450, 480)]
    by = hs.split_idle(gaps, pieces)
    assert by == {0: 50 + 50 + 30, 1: 5 + 10, 3: 10 + 10, 4: 30}
    assert sum(by.values()) == sum(hi - lo for lo, hi in gaps)


def test_under_walks_the_parents():
    tree = {s["id"]: (s["name"], s["parent"]) for s in synthetic_spans()}
    assert hs.under(4, tree, ("admit",))
    assert hs.under(3, tree, ("admit",))
    assert not hs.under(5, tree, ("admit",))
    assert not hs.under(2, tree, ("admit", "prefill_chunk"))
    assert not hs.under(0, tree, ("step",))
    # a parent the tree lacks ends the walk
    assert not hs.under(4, {4: ("engine.install", 3)}, ("admit",))


def test_idle_intervals_are_the_gaps_of_the_union():
    ops = [["%a = f32[] add()", 100, 50, {}], ["%b = f32[] add()", 120, 10, {}],
           ["%c = f32[] add()", 170, 30, {}], ["%d = f32[] add()", 260, 40, {}]]
    raw = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULES_LINE, "events": []},
        {"name": tr.OPS_LINE, "events": ops}]}]}
    assert hs.idle_intervals(raw) == [(150, 170), (200, 260)]
    lo, hi = tr.window_ns(raw)
    assert sum(b - a for a, b in hs.idle_intervals(raw)) == pytest.approx(
        tr.idle_share(raw) * (hi - lo))


def test_program_span_readers_from_the_ring_alone():
    spans = [
        {"phase": "admit", "rid": "s:1", "ts_ns": 0, "dur_ns": 80_000_000},
        {"phase": "admit.begin", "rid": "s:2", "ts_ns": 0,
         "dur_ns": 10_000_000},
        {"phase": "prefill_chunk", "rid": "s:2", "ts_ns": 0,
         "dur_ns": 30_000_000},
        {"phase": "prefill_chunk", "rid": "s:2", "ts_ns": 0,
         "dur_ns": 40_000_000},
        {"phase": "gap", "rid": None, "ts_ns": 0, "dur_ns": 500_000_000},
        {"phase": "engine.prefill", "rid": None, "ts_ns": 0, "dur_ns": 1,
         "plen": 700, "bucket": 1024, "cached": 0},
        {"phase": "engine.prefill", "rid": None, "ts_ns": 0, "dur_ns": 1,
         "plen": 1500, "bucket": 1024, "cached": 600},
        # the parent's shape: an instant with no ``cached``; a warm one
        {"phase": "engine.prefill", "rid": None, "ts_ns": 0, "dur_ns": 0,
         "plen": 100, "bucket": 128},
        {"phase": "engine.prefill", "rid": None, "ts_ns": 0, "dur_ns": 0,
         "plen": 100, "bucket": "warm", "cached": 64}]
    ctx = {"spans": spans}
    assert reader("admit_host_ms_per_req").read(ctx) == pytest.approx(80.0)
    assert reader("prefill_pad_share").read(ctx) == pytest.approx(
        100 * (1 - (700 + 900 + 100) / (1024 + 1024 + 128)))
    for name in ("admit_host_ms_per_req", "prefill_pad_share"):
        assert reader(name).read({"spans": []}) is None


# -- the recorded loops -----------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "host_spans_chat_v5e.json.gz"),
                   "rt") as f:
        return json.load(f)


def ctx_of(recorded):
    return {k: copy.deepcopy(v) for k, v in recorded.items()
            if k != "expect"}


def test_split_sums_to_the_idle_time(recorded, capsys):
    ctx = ctx_of(recorded)
    view = hs.view(ctx)
    lo, hi = tr.window_ns(ctx["raw"])
    idle_ns = tr.idle_share(ctx["raw"]) * (hi - lo)
    assert view["idle_ns"] == pytest.approx(idle_ns, rel=0.01)
    assert view["idle_ns"] == recorded["expect"]["idle_ns"]
    assert hi - lo == recorded["expect"]["window_ns"]
    # the diagnostic line: once, with the whole table
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "idle_by_span"
    assert sum(line["by_span_s"].values()) == pytest.approx(
        view["idle_ns"] / 1e9)
    assert hs.view(ctx) is view and capsys.readouterr().out == ""
    # the tree: every parent named in the cut is in it, on one thread
    spans = view["spans"]
    line_of = hs.scheduler_line(spans)
    for s in spans.values():
        if s["parent"] in spans:
            assert spans[s["parent"]]["start"] <= s["start"]
            assert s["end"] <= spans[s["parent"]]["end"]
            assert s["line"] == line_of


@pytest.mark.parametrize("name", READERS)
def test_readers_give_the_recorded_numbers(recorded, name):
    want = recorded["expect"][name]
    got = reader(name).read(ctx_of(recorded))
    assert got == pytest.approx(want, rel=1e-9)
    if name.endswith("roofline") or "share" in name:
        assert 0.0 <= got < 100.0


def test_shares_by_hand(recorded):
    """The two idle shares from the view's own table, and the roofline
    from its three counters, worked here as PERF.md works them."""
    ctx = ctx_of(recorded)
    view = hs.view(ctx)
    spans, idle = view["spans"], view["idle"]
    admit = sum(ns for sid, ns in idle.items() if sid and any(
        a["name"] in ("admit", "admit.begin", "prefill_chunk")
        for a in chain(spans[sid], spans)))
    assert admit > 0
    assert reader("idle_admit_share.serve").read(ctx) == pytest.approx(
        100.0 * admit / view["window_ns"])
    assert reader("idle_unattributed_share.serve").read(ctx) == \
        pytest.approx(100.0 * idle.get(0, 0) / view["window_ns"])
    runs = hs.segment_runs(ctx, "jit_segment", "engine.segment")
    assert runs and all(a["steps"] == ctx["run"]["segment_steps"]
                        for _, _, a in runs)
    tokens = sum(8 * a["ctx_tokens"] + a["rows"] * 28 for _, _, a in runs)
    kv = 2 * 8 * 128 * 2 * 20                 # Mistral-7B, 20 layers, bf16
    ops = tr.line_events(tr.device_planes(ctx["raw"])[0], tr.OPS_LINE)
    kernel_ns = sum(s for ev, s in zip(ops, tr.self_times(ops))
                    if "%paged_decode" in ev[0].split(" = ")[0]
                    and any(a <= ev[1] < b for a, b, _ in runs))
    assert reader("paged_decode_roofline").read(ctx) == pytest.approx(
        100.0 * (tokens * kv / 819e9) / (kernel_ns / 1e9))


def chain(span, spans):
    while span is not None:
        yield span
        span = spans.get(span["parent"])


@pytest.mark.parametrize("name", READERS[:2] + READERS[3:4])
def test_no_pt_events_is_an_error_not_a_zero(recorded, name):
    ctx = ctx_of(recorded)
    for plane in ctx["host"]["planes"]:
        for line in plane["lines"]:
            line["events"] = [e for e in line["events"]
                              if not e[0].startswith(hs.PREFIX)]
    with pytest.raises(ValueError):
        reader(name).read(ctx)


@pytest.mark.parametrize("name", READERS[:2] + READERS[3:4])
def test_a_ring_without_ids_has_nothing_to_read(recorded, name):
    """The parent's program: spans with no ids. Nothing is read, and
    nothing raises."""
    ctx = ctx_of(recorded)
    for ev in ctx["spans"]:
        ev.pop("span.id", None)
        ev.pop("span.parent", None)
    assert reader(name).read(ctx) is None


def test_a_span_cut_by_the_session_keeps_its_children(recorded):
    """An ``admit`` that was open when the profiler stopped is in the
    ring and not in the trace: the idle time inside its children still
    counts as admission."""
    ctx = ctx_of(recorded)
    whole = reader("idle_admit_share.serve").read(ctx)
    cut = ctx_of(recorded)
    admits = {ev["span.id"] for ev in cut["spans"] if ev["phase"] == "admit"}
    for plane in cut["host"]["planes"]:
        for line in plane["lines"]:
            line["events"] = [e for e in line["events"]
                              if e[3].get("id") not in admits]
    # what was directly inside ``admit`` is now inside ``gap``; the
    # children's share stays
    got = reader("idle_admit_share.serve").read(cut)
    assert 0.95 * whole < got <= whole
