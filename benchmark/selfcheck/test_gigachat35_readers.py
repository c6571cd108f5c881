"""CPU self-tests of the four readers of the cell
``serve-gigachat3.5-longdocs`` on a hand-made context, the numbers worked
out beside them; one admission is cut by the traced window's edge.

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
from benchmark.lib import latent_hybrid as lh  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402
from benchmark.run import load_module  # noqa: E402

PALLAS = ', custom_call_target="tpu_custom_call"'
PEAKS = {"hbm_bytes_per_s": 819e9, "flops_bf16": 197e12}


def reader(name):
    return load_module(os.path.join(ROOT, "benchmark", "layers", name + ".py"))


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


# two segments of 8 steps: (start, end, the engine.segment span's counters)
RUNS = [(1000, 2000, {"steps": 8, "rows": 20, "latent_rows_attended": 100000,
                      "state_rows": 160, "experts_hit": 400}),
        (3000, 4000, {"steps": 8, "rows": 24, "latent_rows_attended": 120000,
                      "state_rows": 192, "experts_hit": 420})]


def op(text, start, dur, pallas=True):
    return [text + (PALLAS if pallas else ""), start, dur, {}]


def latent(start, dur):
    return op("%paged_latent_decode.3 = f32[32,64,512]{2,1,0} custom-call("
              "s32[32,2176]{1,0} %t, s32[32]{0} %l, bf16[32,64,640]{2,1,0} "
              "%q, bf16[69632,16,640]{2,1,0} %p)", start, dur)


def causal(start, dur, heads=16, s=16384):
    return op(f"%mla_selected_prefill.7 = bf16[{heads},{s},128]{{2,1,0}} "
              f"custom-call(s32[1]{{0}} %a, s32[136]{{0}} %b, "
              f"s32[136]{{0}} %c, bf16[{heads},{s},192]{{2,1,0}} %q, "
              f"bf16[{heads},{s},192]{{2,1,0}} %k, "
              f"bf16[{heads},{s},128]{{2,1,0}} %v)", start, dur)


@pytest.fixture
def ctx(monkeypatch):
    ops = [latent(1100, 300), op("%gdn_decode_step.2 = f32[32,8,8,128]"
                                 "{3,2,1,0} custom-call(s32[32]{0} %r)",
                                 1400, 200),
           op("%copy.5 = f32[32,64,128,128]{3,2,1,0} copy(f32[32,64,128,"
              "128]{3,2,1,0} %s)", 1600, 50, pallas=False),
           op("%fusion.9 = bf16[32,16384]{1,0} fusion(bf16[32,3,16384]"
              "{2,1,0} %u, bf16[16384,4]{1,0} %w)", 1650, 40, pallas=False),
           op("%gmm.1 = bf16[256,2048]{1,0} custom-call(bf16[256,7168] %x)",
              1700, 210),
           # q and k laid out for the update kernel's blocks, both ways
           op("%copy.7 = f32[32,8,4,128]{3,2,1,0} copy(f32[32,8,4,128]"
              "{2,3,1,0} %q)", 1910, 30, pallas=False),
           op("%multiply_bitcast_fusion.2 = f32[32,8,128,4]{3,2,1,0} fusion("
              "f32[32,32,128]{2,1,0} %k)", 1940, 20, pallas=False),
           latent(3100, 400),
           op("%gdn_decode_step.2 = f32[32,8,8,128]{3,2,1,0} custom-call("
              "s32[32]{0} %r)", 3500, 300),
           op("%fusion.1 = bf16[32,7168]{1,0} fusion(bf16[32,7168] %x)",
              3800, 100, pallas=False),
           # q normed, before any layout for the kernel: not the update's
           op("%fusion.4 = f32[32,32,128]{2,1,0} fusion(bf16[32,16384] %c)",
              3900, 10, pallas=False),
           # admissions: one call before the first span (left out), the
           # whole first admission (4 groups of 16 heads), and the second
           # cut by the window's edge after 2 of its 4 calls
           causal(500, 999),
           causal(5100, 1000), causal(6100, 1000), causal(7100, 1000),
           causal(8100, 1000),
           causal(20100, 500, s=8192), causal(20600, 500, s=8192)]
    raw = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": ops}]}]}
    monkeypatch.setattr(hs, "segment_runs", lambda c, module, span: (
        list(RUNS) if (module, span) == ("jit_segment", "engine.segment")
        else []))
    prefill = {1: {"name": "engine.prefill", "start": 5000, "end": 9500,
                   "attrs": {"plen": 12000}},
               2: {"name": "engine.prefill", "start": 20000, "end": 21200,
                   "attrs": {"plen": 7000}}}
    monkeypatch.setattr(hs, "view", lambda c: {"spans": prefill})
    spans = [dict(a, phase="engine.segment") for _, _, a in RUNS]
    return {"raw": raw, "spans": spans,
            "config": load("configs", "gigachat3.5-432b-a28b.json"),
            "mix": load("traffic", "longdocs-steady.json"), "peaks": PEAKS}


def test_latent_dense_decode_roofline_by_hand(ctx):
    # 1 full layer x 220,000 rows x 576 x 2 B; the FLOPs (2 x 64 x 1,088 a
    # row) take half as long at 197 TFLOP/s; the kernel 300 + 400 ns
    rows = 100000 + 120000
    assert rows * 2 * 64 * 1088 / 197e12 < rows * 576 * 2 / 819e9
    got = reader("latent_dense_decode_roofline").read(ctx)
    assert got == pytest.approx(100.0 * rows * 576 * 2 / 819e9 / 700e-9)


def test_gdn_grouped_decode_roofline_by_hand(ctx):
    # 352 (row, step) pairs x 4 linear layers x 2 x 64 x 128 x 128 x 4 B;
    # the kernel 200 + 300 ns, a state-shaped copy 50 ns and q and k laid
    # out for the kernel 30 + 20 ns
    least_s = 352 * 4 * 2 * 64 * 128 * 128 * 4 / 819e9
    got = reader("gdn_grouped_decode_roofline").read(ctx)
    assert got == pytest.approx(100.0 * least_s / 600e-9)


def test_latent_causal_prefill_roofline_by_hand(ctx):
    # the first admission's 4 calls of 16 heads and the cut second's 2:
    # 2 x 16 x 320 FLOPs a pair, plen (plen + 1) / 2 pairs; 5,000 ns
    flops = (4 * 2 * 16 * 320 * 12000 * 12001 / 2
             + 2 * 2 * 16 * 320 * 7000 * 7001 / 2)
    got = reader("latent_causal_prefill_roofline").read(ctx)
    assert got == pytest.approx(100.0 * flops / 197e12 / 5000e-9)


def test_hybrid_mixers_time_share_by_hand(ctx):
    # inside the runs: latent 700, update 600, conv 40 of 1,660 ns
    got = reader("hybrid_mixers_time_share.serve").read(ctx)
    assert got == pytest.approx(100.0 * (700 + 600 + 40) / 1660)


def test_the_operations_are_told_apart(ctx):
    geo = lh.geometry(ctx)
    assert (geo["full_layers"], geo["linear_layers"], geo["conv_dim"],
            geo["rows"]) == (1, 4, 16384, 32)
    kinds = [lh.kind(ev, geo) for ev in
             ctx["raw"]["planes"][0]["lines"][0]["events"]]
    assert kinds[:11] == ["latent", "update", "update", "conv", "",
                          "update", "update", "latent", "update", "", ""]
    assert set(kinds[11:]) == {"causal"}


@pytest.mark.parametrize("name", ["latent_dense_decode_roofline",
                                  "gdn_grouped_decode_roofline",
                                  "latent_causal_prefill_roofline",
                                  "hybrid_mixers_time_share.serve"])
def test_a_program_without_the_counters_has_nothing_to_read(ctx, monkeypatch,
                                                            name):
    """The parent of this configuration: no span carries the counters and
    no admission's attention is traced. Nothing is read, nothing raises."""
    monkeypatch.setattr(hs, "segment_runs", lambda *a: [])
    monkeypatch.setattr(hs, "view", lambda c: None)
    assert reader(name).read(dict(ctx, spans=[])) is None
