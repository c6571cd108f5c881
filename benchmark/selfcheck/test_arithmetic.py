"""CPU self-tests of the yardstick's own arithmetic.

    python -m pytest benchmark/selfcheck -q

Nothing here measures anything: synthetic timings and a trimmed recorded
trace go in, and the numbers the reduction must give are worked out by
hand beside them.
"""
import gzip
import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import shapes, stats, traffic  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


# -- trace reduction on a synthetic trace ---------------------------------------
def synthetic():
    kernel = ('%paged_decode.7 = bf16[32,32,128]{2,1,0} custom-call(s32[32] %a), '
              'custom_call_target="tpu_custom_call"')
    ops = [["%while.1 = (s32[], bf16[8]) while(%t)", 100, 80, {}],   # holds two
           ["%fusion.1 = bf16[4,8]{1,0} fusion(%p)", 110, 30, {}],
           [kernel, 150, 20, {}],
           ["%fusion.2.remat = bf16[4,8]{1,0} fusion(%p)", 200, 50, {}],  # gap 20
           ["%fusion.2 = bf16[4,8]{1,0} fusion(%p)", 300, 10, {}]]       # gap 50
    mods = [["jit_f(3)", 100, 150, {}], ["jit_g(4)", 300, 10, {}]]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULES_LINE, "events": mods},
        {"name": tr.OPS_LINE, "events": ops}]},
        {"name": "/host:CPU", "lines": []}]}


def test_union_counts_overlap_once():
    assert tr.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)]) == 30
    assert tr.union_ns([]) == 0


def test_busy_window_and_idle_share():
    raw = synthetic()
    assert tr.window_ns(raw) == (100, 310)
    assert tr.busy_ns(raw) == 80 + 50 + 10
    idle = 1 - tr.busy_ns(raw) / (310 - 100)
    assert idle == pytest.approx(70 / 210)


def test_self_time_takes_nested_operations_out():
    evs = tr.line_events(tr.device_planes(synthetic())[0], tr.OPS_LINE)
    assert [tr.op_name(e) for e in evs] == ["while", "fusion", "paged_decode",
                                            "fusion", "fusion"]
    assert tr.self_times(evs) == [30, 30, 20, 50, 10]


def test_sums_by_module_and_by_kernel_name():
    raw = synthetic()
    assert tr.module_runs(raw, ("jit_f",)) == [150]
    assert tr.module_runs(raw, ("jit_missing",)) == []
    assert tr.module_names(raw) == {"jit_f": (1, 150), "jit_g": (1, 10)}
    assert tr.op_self_ns(raw, tr.is_pallas) == 20     # by its call target
    assert tr.op_self_ns(raw, lambda e: tr.op_name(e) == "missing") == 0
    assert tr.op_self_ns(raw) == 140
    # the same operation of every layer adds up; the result type tells
    # one fusion from another
    assert tr.top_ops(raw, 2) == [["fusion bf16[4,8]", 90 / 1e9],
                                  ["while s32[]", 30 / 1e9]]


def test_idle_gaps_add_up_by_the_programs_around_them():
    raw = synthetic()
    assert tr.idle_gaps(raw, 5) == [["jit_f -> jit_g x1", 50 / 1e9],
                                    ["jit_f -> jit_f x1", 20 / 1e9]]
    lo, hi = tr.window_ns(raw)
    assert sum(s for _, s in tr.idle_gaps(raw, 5)) * 1e9 == pytest.approx(
        (hi - lo) - tr.busy_ns(raw))


# -- the recorded trace -----------------------------------------------------------
RECORDED = os.path.join(HERE, "trace_train_v5e.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this checkout")
def test_recorded_trace_reduces_to_its_known_numbers():
    with gzip.open(RECORDED, "rt") as f:
        raw = json.load(f)
    want = load("benchmark", "selfcheck", "trace_train_v5e.expect.json")
    runs = tr.module_runs(raw, ("jit_bench_train_step",))
    assert len(runs) == want["train_step_runs"]
    assert sum(runs) == want["train_step_ns"]
    assert tr.busy_ns(raw) == want["busy_ns"]
    lo, hi = tr.window_ns(raw)
    assert hi - lo == want["window_ns"]
    ops = tr.line_events(tr.device_planes(raw)[0], tr.OPS_LINE)
    # 18 layers x 2 steps x (3 rope + flash fwd, re-fwd, bwd dq, bwd dkv)
    assert sum(map(tr.is_pallas, ops)) == want["pallas_calls"] == 18 * 2 * 10
    assert tr.op_self_ns(raw, tr.is_pallas) == want["pallas_ns"]
    assert tr.op_self_ns(
        raw, lambda e: tr.is_pallas(e)
        and tr.op_name(e) != "fused_rope") == want["flash_ns"]
    # self times never count an interval twice
    assert tr.op_self_ns(raw) <= tr.busy_ns(raw)
    assert tr.top_ops(raw, 1)[0][0] == "fusion bf16[4,2048,8192]"


# -- percentiles and timing from the due instant -------------------------------------
def test_percentile_and_ten_beyond():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 50) == 51
    assert stats.percentile([7.0], 90) == 7.0
    assert stats.beyond(100, 90) == 10       # p90 of 100 requests: just enough
    assert stats.beyond(99, 90) == 9
    assert stats.beyond(200, 95) == 10


def rec(due, first, last, n, end, ok=True, lag=0.001):
    return {"due_s": due, "t_first_s": first, "t_last_s": last, "n": n,
            "t_end_s": end, "ok": ok, "lag_s": lag}


def test_latency_is_taken_from_the_due_instant_not_the_send():
    # sent 0.4 s late: the wait still counts
    r = rec(due=1.0, first=1.9, last=2.9, n=11, end=2.9, lag=0.4)
    assert stats.ttft_ms([r]) == [pytest.approx(900.0)]
    assert stats.tpot_ms([r]) == [pytest.approx(100.0)]
    assert stats.lateness_ms([r]) == {"p50": pytest.approx(400.0),
                                      "max": pytest.approx(400.0)}


def test_failed_request_counts_as_infinite_and_gives_no_tokens():
    good = rec(0.0, 0.5, 1.5, 11, 2.0)
    bad = rec(0.1, None, None, 0, 0.2, ok=False)
    assert stats.ttft_ms([good, bad])[1] == math.inf
    assert stats.tokens_per_s([good, bad]) == pytest.approx(11 / 2.0)
    assert stats.tpot_ms([good, bad]) == [pytest.approx(100.0)]
    # one failure in two: the 90th percentile is the failure
    assert stats.serve_metrics([good, bad])["serve_ttft_p90_ms"] == math.inf


# -- shapes: FLOPs against a hand count ------------------------------------------
def test_internlm2_flops_by_hand():
    cfg = load("benchmark", "configs", "internlm2-1.8b.json")
    # per layer: q 2048x2048, k and v 2048x1024, o 2048x2048, 3 x 2048x8192
    layer = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 8192
    assert layer == 62_914_560
    assert shapes.layer_matmul_params(cfg) == layer
    assert shapes.matmul_params(cfg) == 18 * layer + 2048 * 92544
    assert shapes.matmul_params(cfg) == 1_321_992_192
    assert shapes.total_params(cfg) == 1_511_598_080    # ISSUE 24's count
    per_token = shapes.train_flops_per_step(cfg, 4, 2048) / (4 * 2048)
    attn = 18 * 6 * 2 * 16 * 128 * 2049 / 2              # per token
    assert per_token == pytest.approx(6 * 1_321_992_192 + attn)
    assert 8.37e9 < per_token < 8.40e9
    unit = 2 * 4 * 16 * 128 * 2048 * 2049 / 2
    assert shapes.flash_train_flops_per_step(cfg, 4, 2048) == 18 * 9 * unit


def test_mistral_flops_by_hand():
    cfg = load("benchmark", "configs", "mistral-7b-v0.3.json")
    layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert shapes.layer_matmul_params(cfg) == layer
    assert shapes.matmul_params(cfg) == 20 * layer + 4096 * 32768
    assert shapes.decode_step_bytes(cfg) == 2 * (20 * layer + 4096 * 32768)
    # 8.99 GB of matmul weights a step; with the embedding, 9.26 GB resident
    assert 2 * shapes.total_params(cfg) == pytest.approx(9.26e9, rel=2e-3)
    p = shapes.prefill_flops(cfg, 1024)
    assert p == pytest.approx(2 * 20 * layer * 1024
                              + 20 * 2 * (2 * 32 * 128 * 1024 * 1025 / 2)
                              + 2 * 4096 * 32768)


# -- traffic: the same schedule for every seed -----------------------------------
@pytest.mark.parametrize("mix_name", ["chat-steady", "longprompt-steady"])
def test_every_seed_replays_the_same_schedule_with_other_tokens(mix_name):
    mix = load("benchmark", "traffic", mix_name + ".json")
    a = traffic.serve_schedule(mix, 1, 30.0, 1000)
    b = traffic.serve_schedule(mix, 2 ** 31 + 77, 30.0, 1000)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 30.0)

    def shape(s):
        return [(r["due_s"], len(r["prompt"]), r["max_new_tokens"])
                for r in s]

    assert shape(a) == shape(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert a == traffic.serve_schedule(mix, 1, 30.0, 1000)   # same seed, same
    assert a[0]["due_s"] == 0.0 and all(0 <= r["due_s"] < 30.0 for r in a)
    due = [r["due_s"] for r in a]
    assert due == sorted(due)
    positions = mix["engine"]["page_size"] * mix["engine"]["max_pages"]
    lo, hi = mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]
    for r in a:
        assert lo <= len(r["prompt"]) <= hi
        assert len(r["prompt"]) + r["max_new_tokens"] <= positions
    # a sweep's other rate keeps the sizes and squeezes the gaps
    fast = traffic.serve_schedule(mix, 1, 30.0, 1000, rate=2 * mix["rate_per_s"])
    assert len(fast) == 2 * len(a)
    assert [len(r["prompt"]) for r in fast[:len(a)]] == \
        [len(r["prompt"]) for r in a]


def test_train_batches_differ_by_step_and_repeat_by_seed():
    a0 = traffic.train_batch(5, 0, 2, 16, 100)
    a1 = traffic.train_batch(5, 1, 2, 16, 100)
    assert (a0[0] != a1[0]).any() and a0[0].shape == (2, 16)
    assert (traffic.train_batch(5, 0, 2, 16, 100)[0] == a0[0]).all()
    big = traffic.train_batch(2 ** 31 + 9, 3, 2, 16, 100)
    assert big[0].min() >= 0 and big[0].max() < 100


# -- BENCHMARK.json names what exists ------------------------------------------------
def test_every_name_in_benchmark_json_has_its_file():
    bench = load("BENCHMARK.json")
    for c in bench["configs"]:
        cfg = load(c["file"])
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layers", m["name"] + ".py")), m["name"]
        assert m["moves"] in e2e
