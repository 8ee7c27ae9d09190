"""The hybrid configuration's rehearsal on the CPU at a tiny size: the runner
end to end, the int4 control and a broken state update coming out not
``correct``, the operations and bytes against hand counts, and the
benchmark's copy of the reference against the repo's. No device number."""

import json
from pathlib import Path

import pytest

DATA = Path(__file__).parent / "data"
ROOT = Path(__file__).resolve().parents[2]
CELL, TINY = "olmo_hybrid_longgen_decode", "tiny_longgen"


@pytest.fixture(scope="module")
def bench():
    """The real BENCHMARK.json's metrics over the tiny hybrid cell."""
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    tiny = json.loads((DATA / "tiny_hybrid_bench.json").read_text())
    out = dict(real, configs=tiny["configs"], workloads=tiny["workloads"])
    for m in out["end_to_end"] + out["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TINY for w in m["workloads"] if w == CELL]
    return out


def _run(bench, trace=False, seconds=3.0, seed=2 ** 31 + 7):
    from chipbench import run

    return run.run_cell(bench, TINY, seed, seconds, trace, require_chip=False, files_root=DATA)


def _tiny_cfg():
    return json.loads((DATA / "configs" / "tiny-olmo-hybrid.json").read_text())


def test_the_real_cell_reports_what_the_issue_names():
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    e2e = {m["name"] for m in real["end_to_end"] if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"tpot_ms_p50", "setup_s"}
    layer = {m["name"] for m in real["per_layer"] if CELL in m.get("workloads", [])}
    assert {"gdn_state_ms_per_step", "gdn_state_roofline", "hybrid_decode_step_roofline",
            "hybrid_prefill_roofline", "pool_parked_admission_pct", "paged_attn_ms_per_step"} <= layer
    # their operations and bytes count attention in every layer
    assert not {"decode_step_roofline", "prefill_roofline"} & layer


def test_serve_runner_rehearsal(bench):
    line = _run(bench)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 12
    assert set(line["metrics"]) == {"tpot_ms_p50", "setup_s"}
    assert line["device"]["platform"] == "cpu" and line["compiles_in_window"] == 0


def test_serve_runner_rehearsal_traced(bench):
    line = _run(bench, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    # the counters read on a CPU; what needs a device trace is left out
    assert {"pool_parked_admission_pct", "slot_occupancy_pct", "ttft_ms_p50"} <= set(line["metrics"])
    assert not {"gdn_state_ms_per_step", "gdn_state_roofline", "hybrid_decode_step_roofline"} & set(line["metrics"])


def test_a_state_update_without_its_decay_is_not_correct(bench, monkeypatch):
    from unionml_tpu.models import olmo_hybrid

    step = olmo_hybrid.gated_delta_step
    monkeypatch.setattr(
        olmo_hybrid, "gated_delta_step",
        lambda q, k, v, g, beta, state, live=None, **kw: step(q, k, v, 0.0 * g, beta, state, live, **kw),
    )
    line = _run(bench)
    assert line["correct"] is False and line["failed"] == 0


def _sound_and_control(seed):
    import jax.numpy as jnp
    import numpy as np

    from chipbench import judge, weights
    from chipbench.adapters import olmo_hybrid as adapter
    from chipbench.reference import olmo_hybrid as reference

    cfg = _tiny_cfg()
    params = weights.make_tree(adapter.build(cfg)["abstract_serve_params"](), seed)
    prompt = np.random.default_rng(seed).integers(1, 256, 40).tolist()
    # greedy tokens of the reference itself stand for a sound served stream
    toks = list(prompt)
    for _ in range(16):
        logits = reference.forward_layerwise(params, jnp.asarray([toks]), cfg)
        toks.append(int(np.asarray(logits)[0, -1].argmax()))
    sample = [{"prompt": prompt, "tokens": toks[len(prompt):]}]
    return cfg, params, judge.served_logit_gaps(
        lambda seq: reference.forward_layerwise(params, jnp.asarray(seq), cfg), sample, 64,
        control_forward=lambda seq: reference.forward_layerwise(params, jnp.asarray(seq), cfg, "int4"),
    )


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_control_in_int4_fails_where_the_program_passes(seed):
    cfg, _, gaps = _sound_and_control(seed)
    assert gaps["served"]["mean"] <= cfg["correct"]["served_logit_gap_mean"] < gaps["control"]["mean"]
    assert gaps["served"]["max"] <= cfg["correct"]["served_logit_gap_max"]


def test_the_benchmarks_reference_is_the_repos():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import weights
    from chipbench.adapters import olmo_hybrid as adapter
    from chipbench.reference import olmo_hybrid as copy
    from unionml_tpu.models import olmo_hybrid_reference as original

    cfg = _tiny_cfg()
    params = weights.make_tree(adapter.build(cfg)["abstract_serve_params"](), 5)
    tokens = jnp.asarray(np.random.default_rng(5).integers(1, 256, (1, 70)))
    with jax.default_matmul_precision("highest"):
        ours = np.asarray(copy.forward_layerwise(params, tokens, cfg))
        theirs = np.asarray(original.forward(params, tokens, cfg))
    assert ours.shape == theirs.shape == (1, 70, 256)
    # the same equations, jitted a layer at a time: summation order only.
    # (Not so under the int4 control: int8 values on an int4 step's half
    # round up or down with the compiler's reciprocal.)
    assert np.abs(ours - theirs).max() < 5e-4


def test_hybrid_ops_and_bytes_against_hand_counts():
    from chipbench import opsbytes_hybrid as ob

    cfg = json.loads((ROOT / "chipbench" / "configs" / "olmo-hybrid-7b-int8.json").read_text())
    d, ff, vocab = 3840, 11008, 100352
    assert ob.conv_channels(cfg) == 30 * (96 + 96 + 192) == 11520
    assert ob.mlp_params(cfg) == 3 * d * ff
    # q, k, v (3840 -> 11520), the output gate and o (3840 <-> 5760)
    assert ob.linear_mixer_params(cfg) == d * 11520 + 2 * d * 5760
    assert ob.linear_mixer_small_params(cfg) == 2 * d * 30 + 4 * 11520
    assert ob.full_mixer_params(cfg) == 4 * d * d
    wide = 24 * (d * 11520 + 2 * d * 5760 + 3 * d * ff) + 8 * (4 * d * d + 3 * d * ff) + d * vocab
    assert ob.matmul_params(cfg) == wide + 24 * 2 * d * 30
    assert ob.weight_bytes(cfg) == wide + 24 * (2 * d * 30 + 4 * 11520) * 4.0
    assert ob.state_bytes(cfg) == 30 * 96 * 192 * 4 == 2211840
    # one live sequence: 24 states read and written, and the step's small operands
    flops, moved = ob.state_step_cost(cfg, 1.0)
    assert moved == 24 * (2 * 2211840 + 30 * (2 * 96 + 2 * 192 + 2) * 4)
    assert flops == 7.0 * 24 * 30 * 96 * 192
    # a step over 10 sequences holding 5000 positions: weights, states, the
    # convolution's tail, 8 layers of 30-head keys and values, embedding rows
    flops, moved = ob.decode_step_cost(cfg, 10.0, 5000.0)
    want = ob.weight_bytes(cfg) + 10 * 24 * (2 * 2211840 + 30 * 578 * 4) + 10 * 24 * 2 * 3 * 11520 * 2.0
    want += 8 * 2 * 30 * 128 * 2.0 * (5000 + 10) + 10 * d * 4
    assert moved == pytest.approx(want)
    want_flops = 2.0 * 10 * ob.matmul_params(cfg) + 7.0 * 10 * 24 * 30 * 96 * 192
    want_flops += 4.0 * 8 * 30 * 128 * 5000 + 2.0 * 10 * 24 * 4 * 11520
    assert flops == pytest.approx(want_flops)
    # a 100-token prompt: two chunks of the scan a head and linear layer
    per_chunk = 4 * 64 * 64 * 96 + 64 * 64 * (96 + 192) + 2 * 64 * 64 * 192 + 8 * 64 * 96 * 192
    assert ob.chunk_scan_flops(cfg, 100) == 24 * 30 * 2 * per_chunk
    flops, moved = ob.prefill_cost(cfg, 100)
    assert moved == ob.weight_bytes(cfg) + 8 * 2 * 30 * 128 * 2.0 * 100 + 24 * 2211840 + 100 * d * 4
    want_flops = 2.0 * 100 * (ob.matmul_params(cfg) - d * vocab) + 2.0 * d * vocab + 2.0 * 8 * 30 * 128 * 100 * 100
    assert flops == pytest.approx(want_flops + ob.chunk_scan_flops(cfg, 100) + 2.0 * 100 * 24 * 4 * 11520)


def test_traced_load_counts_what_the_client_saw():
    from chipbench import opsbytes_hybrid as ob

    class Run:
        traffic = {"trace_from_s": 10, "trace_seconds": 4}
        record = {
            "trace_dir": "x", "t_zero": 100.0,
            "records": [
                # live through the whole traced interval: 1 sequence, prompt + the tokens seen by its middle
                {"error": None, "n_prompt": 50, "t_tokens": [105.0 + 0.5 * i for i in range(40)]},
                # live for its first half only
                {"error": None, "n_prompt": 10, "t_tokens": [108.0, 109.0, 110.0, 111.0, 112.0]},
                {"error": "HTTP 500", "n_prompt": 10, "t_tokens": [110.0, 111.0]},
                {"error": None, "n_prompt": 10, "t_tokens": [90.0, 95.0]},
            ],
        }

    live, cached = ob.traced_load(Run())
    assert live == pytest.approx(1.5)
    # the first: tokens at or before 112.0 are 15; the second: overlap 110-112, middle 111: 4 tokens
    assert cached == pytest.approx(1.0 * (50 + 15) + 0.5 * (10 + 4))
    Run.record["trace_dir"] = None
    assert ob.traced_load(Run()) is None


def test_kernel_time_counts_whole_decode_chunks_only():
    import re

    from chipbench import opsbytes_hybrid as ob

    class Trace:
        ops = {
            "%gated_delta_step.3 = (f32[..])": [(0.10, 0.11), (0.12, 0.13), (0.31, 0.32), (0.33, 0.35), (0.52, 0.53)],
            "%paged_attention.1 = bf16[..]": [(0.14, 0.20)],
        }
        runs = {"jit_decode_chunk(123)": [(0.1, 0.2), (0.3, 0.4), (0.5, 0.6)], "jit_prefill(9)": [(0.21, 0.29)]}

        def ops_matching(self, pattern):
            return [iv for name, ivs in self.ops.items() if re.search(pattern, name) for iv in ivs]

        def module_runs(self, pattern):
            return [iv for name, ivs in self.runs.items() if re.search(pattern, name) for iv in ivs]

    class Run:
        trace = Trace()
        record = {"chunk_steps": 2}

    # two whole chunks of two calls each (0.02 s and 0.03 s); the third was cut by the trace's end
    assert ob.kernel_ms_per_step(Run(), ob.GDN_STEP_KERNEL) == pytest.approx(1e3 * 0.05 / (2 * 2))
    assert ob.kernel_ms_per_step(Run(), r"^%?no_such_kernel") is None
    Run.trace = None
    assert ob.kernel_ms_per_step(Run(), ob.GDN_STEP_KERNEL) is None
