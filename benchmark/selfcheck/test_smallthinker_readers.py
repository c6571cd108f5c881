"""CPU self-tests of the three readers of the cell
``serve-smallthinker-21b-a3b-longanswer`` on a hand-made context, the
numbers worked out beside them.

    python -m pytest benchmark/selfcheck -q
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import host_spans as hs  # noqa: E402
from benchmark.run import load_module  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

PALLAS = ', custom_call_target="tpu_custom_call"'


def reader(name):
    return load_module(os.path.join(ROOT, "benchmark", "layers", name + ".py"))


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21b-a3b.json")) as f:
        return json.load(f)


# two segments of 8 steps: (start, end, the engine.segment span's counters)
RUNS = [(1000, 2000, {"steps": 8, "rows": 20, "ctx_tokens": 60000,
                      "ctx_tokens_window": 50000, "experts_hit": 5000,
                      "expert_rows_max": 3000}),
        (3000, 4000, {"steps": 8, "rows": 24, "ctx_tokens": 80000,
                      "ctx_tokens_window": 70000, "experts_hit": 5400,
                      "expert_rows_max": 4000})]


def op(name, start, dur, pallas=True):
    return [f"%{name}.1 = bf16[24,28,128]{{2,1,0}} custom-call(%a)"
            + (PALLAS if pallas else ""), start, dur, {}]


@pytest.fixture
def ctx(monkeypatch):
    ops = [op("gmm", 1100, 300), op("gmm", 1500, 200),
           op("paged_decode", 1800, 100), op("gmm", 3100, 400),
           op("paged_decode", 3600, 150),
           op("gmm", 2500, 999),       # between the runs: an admission's
           op("fusion", 3800, 50, pallas=False)]
    raw = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": ops}]}]}
    monkeypatch.setattr(hs, "segment_runs", lambda c, module, span: (
        list(RUNS) if (module, span) == ("jit_segment", "engine.segment")
        else []))
    spans = [dict(a, phase="engine.segment") for _, _, a in RUNS]
    return {"raw": raw, "spans": spans, "config": config(),
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_reglu_experts_roofline_by_hand(ctx):
    # 3 x 2560 x 768 x 2 B an expert; gmm inside the runs 300 + 200 + 400 ns
    least_s = (5000 + 5400) * 3 * 2560 * 768 * 2 / 819e9
    got = reader("reglu_experts_decode_roofline").read(ctx)
    assert got == pytest.approx(100.0 * least_s / 900e-9)


def test_paged_decode_gqa7_roofline_by_hand(ctx):
    # layers 0, 4, 8 full; the other 9 window layers
    full = sum(8 * a["ctx_tokens"] + a["rows"] * 8 * 7 // 2 for *_, a in RUNS)
    window = sum(8 * a["ctx_tokens_window"] for *_, a in RUNS)
    least_s = (3 * full + 9 * window) * 2 * 4 * 128 * 2 / 819e9
    got = reader("paged_decode_gqa7_roofline").read(ctx)
    assert got == pytest.approx(100.0 * least_s / 250e-9)


def test_primary_expert_load_by_hand(ctx):
    even = (20 + 24) * 8 * 12 * 6 / 64
    got = reader("primary_expert_load_max_over_mean").read(ctx)
    assert got == pytest.approx((3000 + 4000) / even)


@pytest.mark.parametrize("name", ["reglu_experts_decode_roofline",
                                  "paged_decode_gqa7_roofline",
                                  "primary_expert_load_max_over_mean"])
def test_a_program_without_the_counters_has_nothing_to_read(ctx, monkeypatch,
                                                            name):
    """The parent of this configuration: no span carries the counters.
    Nothing is read, and nothing raises."""
    monkeypatch.setattr(hs, "segment_runs", lambda *a: [])
    assert reader(name).read(dict(ctx, spans=[])) is None
