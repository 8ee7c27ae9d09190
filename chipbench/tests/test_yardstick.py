"""The yardstick's own arithmetic: trace reduction, ops and bytes, the draw."""

import json
from pathlib import Path

import pytest

from chipbench import opsbytes, traffic, xplane
from chipbench.yardstick import load_peaks, percentile

DATA = Path(__file__).parent / "data"
ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def tiny_trace():
    # four bursts of four 2048x2048 bf16 matmul programs on one v5e chip,
    # with a 50 ms host sleep after each burst (recorded on the chip, PR 24)
    return xplane.load(str(DATA / "tiny_v5e.xplane.pb"))


def test_trace_busy_union_and_modules(tiny_trace):
    assert len(tiny_trace.devices) == 1
    runs = tiny_trace.module_runs(r"^jit__lambda")
    assert len(runs) == 16
    # ops of one program do not overlap, so the union equals the plain sum
    plain = sum(e - s for _, s, e in tiny_trace.devices[0].ops)
    assert tiny_trace.busy_s() == pytest.approx(plain, rel=1e-9)
    assert 1.0e-3 < tiny_trace.busy_s() < 2.5e-3
    assert tiny_trace.busy_s() <= sum(e - s for s, e in runs)
    assert 0.15 < tiny_trace.device_span_s() < 0.17


def test_trace_idle_gaps_are_labelled_by_the_host_call(tiny_trace):
    gaps = dict(tiny_trace.idle_gaps())
    # three of the four sleeps lie between device work: ~150 ms under time.sleep
    assert 0.14 < gaps["time_sleep"] < 0.17
    assert sum(gaps.values()) == pytest.approx(
        tiny_trace.device_span_s() - tiny_trace.busy_s(), rel=1e-6
    )


def test_trace_kernel_sums(tiny_trace):
    totals = dict(tiny_trace.op_totals())
    assert set(totals) == {"fusion", "copy-done", "copy-start"}
    fusions = tiny_trace.ops_matching(r"^%fusion")
    assert len(fusions) == 16
    assert totals["fusion"] == pytest.approx(sum(e - s for s, e in fusions))
    # a 2048^3 bf16 matmul is 17.2 GFLOP: the traced time cannot beat the peak
    peak = load_peaks("TPU v5 lite")["bf16_flops_per_s"]
    assert 2 * 2048 ** 3 / (totals["fusion"] / 16) < peak


def test_interval_arithmetic():
    assert xplane.union_intervals([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert xplane.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert xplane.op_short_name("%multiply_reduce_fusion.12 = f32[8]{0} fusion(...)") == "multiply_reduce_fusion"
    assert xplane.op_short_name("%all-reduce-start.3 = ...") == "all-reduce-start"


def test_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        load_peaks("TPU v9 imaginary")
    assert load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_mixtral_layer_hand_count():
    cfg = json.loads((ROOT / "configs" / "mixtral-8x7b-int8.json").read_text())
    # attention: q and o 4096x4096, k and v 4096x1024
    assert opsbytes.decoder_attn_params(cfg) == 2 * 4096 * 4096 + 2 * 4096 * 1024 == 41_943_040
    # one expert: three 4096x14336 matrices
    assert opsbytes.decoder_mlp_params(cfg) == 3 * 4096 * 14336 == 176_160_768
    assert opsbytes.decoder_layer_params(cfg) == 41_943_040 + 8 * 176_160_768 + 4096 * 8
    one = dict(cfg, num_hidden_layers=1)
    # one decode step, 64 live sequences (all 8 experts touched), empty cache:
    # int8 weights once, 64 new KV rows of 2*8*128 bf16, 64 fp32 embedding rows
    flops, moved = opsbytes.decode_step_cost(one, 64, 0)
    touched = 8 * (1 - 0.75 ** 64)
    want_bytes = (41_943_040 + 176_160_768 * touched + 4096 * 8 * 4) + 4096 * 32000 + 64 * 4096 + 64 * 4096 * 4
    assert moved == pytest.approx(want_bytes, rel=1e-12)
    # two FLOPs per weight per token: attention, two experts, the router, the head
    want_flops = 2 * 64 * (41_943_040 + 2 * 176_160_768 + 4096 * 8 + 4096 * 32000)
    assert flops == pytest.approx(want_flops, rel=1e-12)
    # a step never moves less than the experts a real batch touches
    assert opsbytes.experts_touched(8, 2, 1) == pytest.approx(2.0)
    # a 1000-token cache adds scores and values: 4 * heads * head_dim per position
    assert opsbytes.decode_step_cost(one, 64, 1000)[0] - flops == pytest.approx(4 * 32 * 128 * 1000)


def test_vit_block_hand_count():
    cfg = json.loads((ROOT / "configs" / "vit-b16-224.json").read_text())
    assert opsbytes.vit_tokens(cfg) == 197
    assert opsbytes.vit_block_params(cfg) == 4 * 768 * 768 + 2 * 768 * 3072 == 7_077_888
    one = dict(cfg, num_hidden_layers=1)
    fwd_block = 2 * 197 * 7_077_888 + 4 * 197 * 197 * 768
    fwd_rest = 2 * 196 * (16 * 16 * 3) * 768 + 2 * 768 * 1000
    assert opsbytes.vit_train_flops_per_sample(one) == pytest.approx(3 * (fwd_block + fwd_rest))
    # the whole model: about 105 GFLOP forward + backward per image
    assert 100e9 < opsbytes.vit_train_flops_per_sample(cfg) < 110e9


def test_traffic_draw_same_multiset_other_order():
    mix = json.loads((ROOT / "traffic" / "chat_lognormal_poisson.json").read_text())
    a = traffic.draw_requests(mix, 1, 40.0, 32000)
    b = traffic.draw_requests(mix, 2 ** 31 + 12345, 40.0, 32000)
    assert len(a) == len(b) == round(mix["rate_per_s"] * mix["ramp_s"]) + round(mix["rate_per_s"] * 40.0)

    def lens(reqs, key):
        return sorted(key(r) for r in reqs if r["in_window"])

    assert lens(a, lambda r: len(r["prompt"])) == lens(b, lambda r: len(r["prompt"]))
    assert lens(a, lambda r: r["max_new_tokens"]) == lens(b, lambda r: r["max_new_tokens"])
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert a == traffic.draw_requests(mix, 1, 40.0, 32000)
    p = mix["prompt_tokens"]
    assert all(p["min"] <= len(r["prompt"]) <= p["max"] for r in a)
    assert all(r["due_s"] < 0 for r in a if not r["in_window"])
    assert all(0 <= r["due_s"] <= 40.0 for r in a if r["in_window"])
    mid = traffic.lognormal_midpoints(1001, 192, 0.8, 16, 1024)
    assert mid[500] == 192 and mid == sorted(mid)


def test_percentile_interpolates():
    assert percentile([1, 2, 3, 4], 50) == 2.5
    assert percentile([5], 95) == 5
    assert percentile(range(101), 95) == 95
