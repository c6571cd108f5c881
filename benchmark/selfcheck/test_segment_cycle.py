"""CPU self-tests of ``lib/segment_cycle.py`` and the six readers on it.

    python -m pytest benchmark/selfcheck -q

Hand-built loops with every piece of a cycle worked out by hand, then
two dozen loops of ``serve-mistral-7b-chat`` recorded on the chip
(``record_segment_cycle.py``): the device's programs and operations, the
program's ``pt:*`` events from the profiler's host plane, the ring's
events, and the readers' values read off once.
"""
import copy
import gzip
import json
import os
import statistics
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import host_spans as hs  # noqa: E402
from benchmark.lib import segment_cycle as sc  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402
from benchmark.selfcheck.record_host_spans import reader  # noqa: E402
from benchmark.selfcheck.record_segment_cycle import READERS  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1000                       # the hand-built loops are laid out in us


# -- hand-built ---------------------------------------------------------------
PERIOD = 1020
# (name, parent, start, end) inside one iteration that starts at 0;
# the device runs jit_segment over [350, 700)
LOOP = (("step", None, 0, 1000), ("gap", "step", 10, 60),
        ("segment", "step", 100, 900),
        ("engine.tables", "segment", 110, 150),
        ("engine.segment", "segment", 200, 890),
        ("engine.dispatch", "engine.segment", 210, 300),
        ("engine.wait", "engine.segment", 300, 800),
        ("engine.collect", "engine.segment", 810, 880),
        ("collect", "step", 910, 990))
RUN = (350, 700)
ATTRS = {"engine.segment": {"rows": 3, "steps": 8},
         "engine.dispatch": {"args": 207},
         "engine.tables": {"changed": 0}, "collect": {"pushed": 3}}
# what one cycle of these loops reads, in us
BY_HAND = {"cycle": PERIOD - 350, "wake": 100, "engine.collect": 70,
           "collect": 80, "gap": 50, "gap.pressure": 0, "control": 0,
           "segment_before_tables": 10, "engine.tables": 40,
           "counter_sums": 60, "launch": 140, "residue": 120}


def loops(n, admit_in=(), no_run=(), drop=()):
    """``ctx`` of ``n`` iterations: the ring, the host plane, the device's
    programs. ``admit_in``: iterations whose gap holds an admission;
    ``no_run``: whose program the window's edge cut; ``drop``: span names
    left out of ring and trace."""
    ring, host, mods, ops, sid = [], [], [], [], 0
    for i in range(n):
        t, ids = i * PERIOD, {}
        spans = list(LOOP)
        if i in admit_in:
            spans.insert(2, ("admit", "gap", 20, 50))
        for name, parent, lo, hi in spans:
            if name in drop:
                continue
            sid += 1
            ids[name] = sid
            attrs = dict(ATTRS.get(name, {}))
            if name == "engine.tables":
                attrs["changed"] = int(i in admit_in or i == 0)
            ring.append({"phase": name, "rid": None, "ts_ns": (t + lo) * US,
                         "dur_ns": (hi - lo) * US, "span.id": sid,
                         "span.parent": ids.get(parent, 0), **attrs})
            stats = {"id": sid, "parent": ids.get(parent, 0)}
            if parent is None:
                stats["pid"] = 1
            host.append(["pt:" + name, (t + lo) * US, (hi - lo) * US, stats])
        if i not in no_run:
            lo, hi = (t + RUN[0]) * US, (t + RUN[1]) * US
            mods.append([f"jit_segment({i})", lo, hi - lo, {}])
            ops.append(["%fusion.1 = f32[8]{0} fusion()", lo, hi - lo, {}])
    return {"spans": ring,
            "host": {"planes": [{"name": "/host:CPU", "lines": [
                {"name": "scheduler", "events": host}]}]},
            "raw": {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": tr.MODULES_LINE, "events": mods},
                {"name": tr.OPS_LINE, "events": ops}]}]}}


def line_of(capsys):
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    return [x for x in lines if x["phase"] == "segment_cycle"]


def test_every_piece_of_a_cycle_by_hand(capsys):
    ctx = loops(4)
    view = sc.view(ctx)
    assert len(view["segments"]) == 4 and len(view["cycles"]) == 3
    for cycle in view["cycles"]:
        assert cycle == {k: v * US for k, v in BY_HAND.items()}
        assert cycle["cycle"] == cycle["residue"] + sum(
            cycle[k] for k in sc.PIECES)
    (line,) = line_of(capsys)
    assert line["cycle_ms"] == pytest.approx(BY_HAND["cycle"] / 1e3)
    assert line["pieces_ms"] == {
        k: pytest.approx(BY_HAND[k] / 1e3) for k in sc.PIECES}
    assert list(line["pieces_ms"]) == list(sc.PIECES)
    assert line["residue_ms"] == pytest.approx(0.120)
    assert (line["args"], line["pushed_per_cycle"]) == (207, 3.0)
    assert (line["segments"], line["segments_whole"], line["cycles"]) == (
        4, 4, 3)
    # nothing varies here: no rank to correlate
    assert line["wake_vs_rows"] is None and "wake_vs_pushed" not in line
    # once a run
    assert sc.view(ctx) is view and line_of(capsys) == []
    want = {"segment_cycle_host_ms": 0.670, "segment_wake_ms": 0.100,
            "segment_launch_ms": 0.140, "segment_collect_ms": 0.070,
            "table_upload_ms": 0.040, "table_upload_changed_share": 25.0}
    assert set(want) == set(READERS)
    for name, value in want.items():
        assert reader(name).read(ctx) == pytest.approx(value)


def test_a_cycle_with_an_admission_between_is_skipped(capsys):
    ctx = loops(4, admit_in=(2,))
    view = sc.view(ctx)
    assert len(view["segments"]) == 4
    # the segment that follows the admission is told apart on the line
    assert line_of(capsys)[0]["after_admission"] == {
        "segments": 1, "launch_ms": pytest.approx(0.140),
        "tables_ms": pytest.approx(0.040)}
    # 0 -> 1 and 2 -> 3 stay; 1 -> 2 holds the admission
    assert [c["cycle"] for c in view["cycles"]] == [670 * US] * 2
    assert len(view["wake_ns"]) == 4
    assert reader("table_upload_changed_share").read(ctx) == 50.0


def test_a_segment_whose_run_the_window_cuts_is_skipped():
    ctx = loops(4, no_run=(3,))
    view = sc.view(ctx)
    assert [s["run"] is not None for s in view["segments"]] == [
        True, True, True, False]
    assert len(view["cycles"]) == 2 and len(view["launch_ns"]) == 3
    # a run that outlasts its span (the session ended inside it) too
    late = loops(3)
    late["raw"]["planes"][0]["lines"][0]["events"][-1][2] = 2000 * US
    assert [s["run"] is not None
            for s in sc.view(late)["segments"]] == [True, True, False]


def test_segments_without_their_children_are_a_fault():
    """The program has the new spans (the upload's is there) and its
    segments lack their children: raised, on every reader that needs
    them."""
    for name in ("segment_cycle_host_ms", "segment_wake_ms",
                 "segment_launch_ms", "segment_collect_ms"):
        with pytest.raises(ValueError):
            reader(name).read(loops(3, drop=sc.CHILDREN))
    with pytest.raises(ValueError):
        reader("table_upload_ms").read(
            loops(3, drop=("engine.tables", "engine.wait")))


@pytest.mark.parametrize("name", READERS)
def test_a_program_from_before_the_spans_has_nothing_to_read(name, capsys):
    """The parent under this PR's benchmark files: ``engine.segment`` and
    none of the new names. Nothing is read, nothing raises, no line."""
    ctx = loops(3, drop=sc.NEW)
    assert reader(name).read(ctx) is None
    assert line_of(capsys) == []
    assert reader(name).read({"spans": []}) is None


def test_rank_correlation():
    assert sc.rank_correlation([1, 2, 3, 4], [10, 20, 30, 45]) == 1.0
    assert sc.rank_correlation([1, 2, 3, 4], [9, 7, 5, 1]) == -1.0
    # ties take their mean rank: ranks (0, 1.5, 1.5, 3) against (0, 1, 2, 3)
    assert sc.rank_correlation([1, 2, 2, 3], [1, 2, 3, 4]) == pytest.approx(
        4.5 / (4.5 * 5.0) ** 0.5)
    assert sc.rank_correlation([1, 1, 1], [1, 2, 3]) is None
    assert sc.rank_correlation([1, 2], [1, 2]) is None


# -- the recorded loops -------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "segment_cycle_chat_v5e.json.gz"),
                   "rt") as f:
        return json.load(f)


def ctx_of(recorded):
    return {k: copy.deepcopy(v) for k, v in recorded.items()
            if k != "expect"}


@pytest.mark.parametrize("name", READERS)
def test_readers_give_the_recorded_numbers(recorded, name):
    got = reader(name).read(ctx_of(recorded))
    assert got == pytest.approx(recorded["expect"][name], rel=1e-9)
    assert got > 0
    if name.endswith("share"):
        assert got <= 100.0


def test_recorded_cycles_add_up(recorded, capsys):
    ctx = ctx_of(recorded)
    view = sc.view(ctx)
    want = recorded["expect"]
    assert (len(view["segments"]), len(view["cycles"])) == (
        want["segments"], want["cycles"])
    assert want["cycles"] >= 3
    for c in view["cycles"]:
        assert c["cycle"] == c["residue"] + sum(c[k] for k in sc.PIECES)
        assert all(c[k] >= 0 for k in sc.PIECES)
        # what no span names stays small
        assert 0 <= c["residue"] < 0.1 * c["cycle"]
    assert all(w > 0 for w in view["wake_ns"])
    assert all(x > 0 for x in view["launch_ns"])
    (line,) = line_of(capsys)
    assert line["cycle_ms"] == pytest.approx(
        want["segment_cycle_host_ms"], rel=1e-9)
    assert isinstance(line["args"], int) and line["args"] > 100


def test_cycle_is_the_wait_between_two_runs_read_by_hand(recorded):
    """The number PERF.md took off a trace by hand: the waits between two
    consecutive ``jit_segment`` runs with no other program between them,
    from the device's line alone."""
    ctx = ctx_of(recorded)
    mods = tr.line_events(tr.device_planes(ctx["raw"])[0], tr.MODULES_LINE)
    waits = [b[1] - (a[1] + a[2]) for a, b in zip(mods, mods[1:])
             if a[0].startswith("jit_segment(")
             and b[0].startswith("jit_segment(")]
    assert len(waits) >= len(sc.view(ctx)["cycles"])
    assert reader("segment_cycle_host_ms").read(ctx) == pytest.approx(
        statistics.median(waits) / 1e6, rel=0.1)


def test_children_lie_inside_their_segment_on_the_devices_clock(recorded):
    ctx = ctx_of(recorded)
    for s in sc.view(ctx)["segments"]:
        d, w, c = (s[k] for k in sc.CHILDREN)
        assert s["span"]["start"] <= d["start"] <= d["end"] <= w["start"]
        assert w["end"] <= c["start"] <= c["end"] <= s["span"]["end"]
        if s["run"] is not None:
            # the program starts inside the dispatch or the wait and ends
            # before the wait does
            assert d["start"] < s["run"][0] < s["run"][1] < w["end"]
        assert s["tables"]["end"] <= s["span"]["start"]
    spans = hs.view(ctx)["spans"]
    assert all(spans[s["span"]["parent"]]["name"] == "segment"
               for s in sc.view(ctx)["segments"])
