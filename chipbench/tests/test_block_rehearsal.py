"""The block-diffusion configuration's rehearsal on the CPU at a tiny size:
the new runner, adapter, reference, comparison and every new reader end to
end; the int4 control and the two controls of the mechanism (the reference
under a plainly causal mask, a program that keeps a block's last denoising
forward's rows) coming out not ``correct`` where the program passes; the
operations and bytes against hand counts; the benchmark's copy of the
reference against the repo's. No device number.

The tiny limits (``tests/data/configs/tiny-sdar-moe.json``): over six seeds
(3 s windows, 90-108 served tokens checked) the program's mean gap reads
0.004-0.028 and its widest 0.16-1.66; the int4 control's mean 0.19-0.48, the
causal mask's 0.24-0.58, the stale commit's 0.24-0.52: the mean's limit 0.075
is the geometric middle of 0.028 and 0.19; the widest's 5.0 is held against a
grossly wrong token only (the controls' widest read 1.9-3.9).
"""

import json
import re
from pathlib import Path

import numpy as np
import pytest

DATA = Path(__file__).parent / "data"
ROOT = Path(__file__).resolve().parents[2]
CELL, TINY = "sdar_chat_fixed_length_decode", "tiny_chat_fixed"
CONFIG = "sdar-30b-a3b-chat-int8"
NEW = {"tokens_per_forward", "block_attn_roofline", "block_decode_step_roofline", "block_prefill_roofline"}


def _real():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def bench():
    """The real BENCHMARK.json's metrics over the tiny cell."""
    tiny = json.loads((DATA / "tiny_block_bench.json").read_text())
    out = dict(_real(), configs=tiny["configs"], workloads=tiny["workloads"])
    for m in out["end_to_end"] + out["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TINY for w in m["workloads"] if w == CELL]
    return out


def _run(bench, trace=False, seconds=3.0, seed=2 ** 31 + 11):
    from chipbench import run

    return run.run_cell(bench, TINY, seed, seconds, trace, require_chip=False, files_root=DATA)


def _tiny_cfg():
    return json.loads((DATA / "configs" / "tiny-sdar-moe.json").read_text())


def _real_cfg():
    return json.loads((ROOT / "chipbench" / "configs" / f"{CONFIG}.json").read_text())


def test_the_real_cell_is_as_the_issue_names_it():
    real = _real()
    (cell,) = [w for w in real["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config=CONFIG, traffic="chat_fixed_output_poisson", chips=1)
    (config,) = [c for c in real["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["num_hidden_layers"]
    e2e = {m["name"] for m in real["end_to_end"] if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"tpot_ms_p50", "setup_s"}
    layer = {m["name"] for m in real["per_layer"] if CELL in m.get("workloads", [])}
    assert NEW | {"slo_met_pct", "harvest_lag_ms_p50", "decode_step_device_ms", "prefill_device_ms_p50",
                  "device_idle_pct.serve", "pool_parked_admission_pct", "decode_step_mixer_ms",
                  "decode_step_head_ms", "prefill_ffn_ms", "paged_attn_ms_per_step"} <= layer
    # the other families' operations and bytes count other programs
    assert not {"decode_step_roofline", "prefill_roofline", "sparse_attn_roofline", "latent_attn_roofline"} & layer
    assert all("workloads" in m for m in real["per_layer"])
    assert all(m["workloads"] == [CELL] for m in real["per_layer"] if m["name"] in NEW)
    assert all((ROOT / "chipbench" / "layer_metrics" / f"{m['name']}.py").is_file() for m in real["per_layer"])
    # new entries stand at the end of their lists
    assert real["workloads"][-1]["name"] == CELL and real["configs"][-1] is config
    assert {m["name"] for m in real["per_layer"][-len(NEW):]} == NEW
    assert all(m["workloads"][-1] == CELL for m in real["per_layer"] if CELL in m["workloads"])


def test_the_configuration_keeps_every_published_number():
    cfg = _real_cfg()
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        (entry,) = [e for e in map(json.loads, catalog.read_text().splitlines()) if e["name"] == "SDAR-30B-A3B-Chat"]
        assert cfg["source"] == entry["source_url"]
        differs = {k for k, v in entry["config"].items() if cfg.get(k, "missing") != v}
        assert differs == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] in (16, 12) and cfg["published"]["num_hidden_layers"] == 48
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]) == (
        2048, 32, 4, 128)
    assert (cfg["num_experts"], cfg["num_experts_per_tok"], cfg["moe_intermediate_size"]) == (128, 8, 768)
    assert cfg["vocab_size"] == 151936
    gen = cfg["generation"]
    assert (gen["block_length"], gen["denoising_steps"], gen["remasking_strategy"], gen["mask_token_id"]) == (
        4, 4, "low_confidence_static", 151669)
    serving = cfg["serving"]
    assert (serving["slots"], serving["max_new_tokens"], serving["kv_block_size"]) == (32, 256, 16)
    assert serving["prompt_buckets"] == [256, 512, 1024, 2048]
    assumed = " ".join(cfg["assumed"])
    for said in ("block_length 4", "denoising_steps 4", "remasking_strategy", "mask_token_id 151669",
                 "no shift by one", "per-head RMSNorm", "int8 weight-only"):
        assert said in assumed
    mix = json.loads((ROOT / "chipbench" / "traffic" / "chat_fixed_output_poisson.json").read_text())
    assert mix["kind"] == "serve_open_loop_blocks" and isinstance(mix["rate_per_s"], (int, float))
    assert mix["prompt_tokens"] == {"median": 256, "sigma": 0.8, "min": 32, "max": 2048}
    assert mix["output_tokens"] == {"median": 256, "sigma": 0, "min": 256, "max": 256}
    assert mix["slo"] == {"ttft_ms": 1000, "gap_ms": 50}
    assert (mix["ramp_s"], mix["check_requests"], mix["trace_from_s"], mix["trace_seconds"]) == (10.0, 6, 10, 4)
    assert mix["prompt_tokens"]["max"] <= serving["prompt_buckets"][-1]
    assert mix["output_tokens"]["max"] <= serving["max_new_tokens"]


def test_a_fixed_output_length_is_drawn_for_every_request():
    from chipbench import traffic

    mix = json.loads((ROOT / "chipbench" / "traffic" / "chat_fixed_output_poisson.json").read_text())
    requests = traffic.draw_requests(mix, 2 ** 31 + 5, 20.0, 151936)
    assert requests and {r["max_new_tokens"] for r in requests} == {256}
    assert min(len(r["prompt"]) for r in requests) >= 32 and max(len(r["prompt"]) for r in requests) <= 2048


def test_serve_runner_rehearsal(bench):
    line = _run(bench)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 12
    assert set(line["metrics"]) == {"tpot_ms_p50", "setup_s"}
    assert line["device"]["platform"] == "cpu" and line["compiles_in_window"] == 0


def test_serve_runner_rehearsal_traced(bench):
    line = _run(bench, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    # the counters read on a CPU; what needs a device trace is left out, and no reader raises
    assert {"pool_parked_admission_pct", "slot_occupancy_pct", "ttft_ms_p50", "tokens_per_forward"} <= set(
        line["metrics"])
    assert not (NEW - {"tokens_per_forward"}) & set(line["metrics"])
    # four entries a block of four denoising forwards and a commit; a request's last block takes none
    assert 0.8 <= line["metrics"]["tokens_per_forward"]["value"] < 0.9


def test_a_program_that_keeps_the_last_denoising_forwards_rows_is_not_correct(bench, monkeypatch):
    """The second control of the mechanism: a block's rows left over from
    the forward that decided its last entry (the mask token's keys and
    values at that entry) in the committed ones' place."""
    from unionml_tpu.serving.engine import DecodeEngine

    monkeypatch.setattr(DecodeEngine, "_block_stale_commit", True)
    line = _run(bench)
    assert line["correct"] is False and line["failed"] == 0


def _sound_and_controls(seed):
    """The repo's own generation loop stands for a sound served stream; the
    comparison's gaps of it, of the int4 control and of the causal mask."""
    import jax

    from chipbench import weights
    from chipbench.adapters import sdar_moe as adapter
    from chipbench.reference import sdar_moe as reference
    from chipbench.runners.serve_open_loop_blocks import served_block_logit_gaps
    from unionml_tpu.models import sdar_moe_reference as original

    cfg = _tiny_cfg()
    params = weights.make_tree(adapter.build(cfg)["abstract_serve_params"](), seed)
    prompt = np.random.default_rng(seed).integers(1, 250, 13).tolist()
    with jax.default_matmul_precision("highest"):
        tokens, decided_at = original.generate(params, prompt, cfg, 18)
        sample = [{"prompt": prompt, "tokens": tokens, "decided_at": decided_at}]

        def states(**kw):
            return lambda clean, copies, start: reference.forward_states(params, clean, copies, start, cfg, **kw)

        out = {
            name: served_block_logit_gaps(states(), sample, cfg["generation"], 44, 24, control_forward=states(**kw))
            for name, kw in (("int4", {"control": "int4"}), ("causal", {"mask": "causal"}))
        }
    return cfg, out


@pytest.mark.parametrize("seed", [1, 3, 4])
def test_the_controls_fail_where_a_sound_stream_passes(seed):
    """The reference's own greedy stream lies 0 below its best at every
    token, in the state it was decided from: the comparison rebuilds the
    states the loop went through. The tokens the int4 reference and the
    causal-mask reference would put first lie outside the limit."""
    cfg, gaps = _sound_and_controls(seed)
    limit = cfg["correct"]["served_logit_gap_mean"]
    for name in ("int4", "causal"):
        assert gaps[name]["served"]["max"] < 1e-3 and gaps[name]["tokens"] == 18
        assert gaps[name]["control"]["mean"] > limit


def test_the_states_are_the_loops():
    """``block_states`` against the state before each forward as the loop
    itself records it."""
    from chipbench.runners.serve_open_loop_blocks import block_states

    gen = {"block_length": 4, "denoising_steps": 4, "mask_token_id": 99}
    sample = {"prompt": [1, 2, 3, 4, 5, 6], "tokens": [10, 11, 12, 13, 14, 15, 16], "decided_at": [1, 0, 3, 2, 0, 1, 0]}
    clean, copies, start = block_states(sample, gen, 12)
    assert start == 4 and clean == [1, 2, 3, 4, 5, 6, 10, 11, 12, 13, 14, 15, 16, 99, 99, 99]
    m = 99
    assert copies.tolist() == [
        [5, 6, m, m, m, m, m, m, m, m, m, m],
        [5, 6, m, 11, m, m, 14, m, 16, m, m, m],
        [5, 6, 10, 11, m, m, 14, 15, 16, m, m, m],
        [5, 6, 10, 11, m, 13, 14, 15, 16, m, m, m],
    ]


def test_the_benchmarks_reference_is_the_repos(monkeypatch):
    import jax
    import jax.numpy as jnp

    from chipbench import weights
    from chipbench.adapters import sdar_moe as adapter
    from chipbench.reference import sdar_moe as copy
    from unionml_tpu.models import sdar_moe_reference as original

    cfg = _tiny_cfg()
    params = weights.make_tree(adapter.build(cfg)["abstract_serve_params"](), 5)
    assert "kernel_q" in params["block_1"]["attn"]["q"] and "w_gate_q" in params["block_1"]["moe"]
    tokens = np.random.default_rng(5).integers(1, 250, 40)
    monkeypatch.setattr(copy, "_Q_BLOCK", 8)   # five blocks of queries
    monkeypatch.setattr(copy, "_ROW_BLOCK", 16)
    with jax.default_matmul_precision("highest"):
        ours = copy.forward(params, tokens, cfg)
        theirs = np.asarray(original.forward(params, jnp.asarray([tokens]), cfg))[0]
        causal = copy.forward(params, tokens, cfg, mask="causal")
        # a state's logits are those of the repo's forward over the clean
        # blocks before it and the state itself
        state = tokens.copy()
        state[[33, 35]] = cfg["generation"]["mask_token_id"]
        want = np.asarray(original.forward(params, jnp.asarray([state[:36]]), cfg))[0, 32:36]
        copies = np.stack([state[24:40], tokens[24:40]])
        got = copy.forward_states(params, tokens, copies, 24, cfg, pad_to=80)
    assert ours.shape == theirs.shape == (40, 256)
    assert np.abs(ours - theirs).max() < 5e-4 and np.abs(causal - ours).max() > 0.1
    assert got.shape == (2, 16, 256) and np.abs(got[0, 8:12] - want).max() < 5e-4
    assert np.abs(got[1] - theirs[24:40]).max() < 5e-4
    # and it imports nothing of the program
    assert not re.search(r"^\s*(from|import) unionml_tpu", Path(copy.__file__).read_text(), re.M)


def test_operations_and_bytes_against_hand_counts():
    from chipbench import opsbytes_block as ob

    cfg = _real_cfg()
    layers = cfg["num_hidden_layers"]
    assert ob.attention_params(cfg) == 2048 * 128 * (32 + 4 + 4 + 32) == 18_874_368
    assert ob.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    assert ob.kv_row_bytes(cfg) == 2048
    assert ob.layer_params_a_row(cfg) == 18_874_368 + 2048 * 128 + 8 * 4_718_592
    # 128 rows (32 sequences' blocks) meet every expert, 4 rows a quarter of them
    assert 127.9 < ob.experts_touched(cfg, 128) <= 128 and 28 < ob.experts_touched(cfg, 4) < 30
    # one forward of 20 live sequences that see 12,000 positions in all
    flops, moved = ob.forward_cost(cfg, 20, 12_000)
    rows = 80
    head = 2048 * 151936
    attn_flops = 2.0 * layers * 4 * 32 * 2 * 128 * 12_000
    assert flops == 2.0 * rows * (layers * ob.layer_params_a_row(cfg) + head) + attn_flops
    weights_read = layers * (18_874_368 + ob.experts_touched(cfg, rows) * 4_718_592) + head + layers * 2048 * 128 * 4
    assert moved == pytest.approx(
        weights_read + layers * 2048 * 12_000 + rows * layers * 2048 + rows * 2048 * 4, rel=1e-12)
    # the visible rows are read once a forward, not once a token
    assert ob.block_attention_cost(cfg, 12_000)[1] == layers * 2048 * 12_000
    # block-causal pairs: whole blocks see themselves whole, a partial block its own entries
    assert ob.block_causal_pairs(8, 4) == 4 * 4 + 4 * 8 and ob.block_causal_pairs(6, 4) == 16 + 2 * 6
    flops, moved = ob.prefill_cost(cfg, 256)
    assert flops == 2.0 * 256 * layers * ob.layer_params_a_row(cfg) + 2.0 * layers * 32 * 2 * 128 * ob.block_causal_pairs(256, 4)
    assert moved == pytest.approx(
        ob.weight_bytes(cfg, 256, head=False) + 256 * layers * 2048 + 256 * 2048 * 4, rel=1e-12)
    # a forward is bound by the weight read at this size: 10 GB at the HBM rate
    peaks = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    from chipbench.yardstick import roofline_s

    least, bound = roofline_s(*ob.forward_cost(cfg, 20, 12_000), next(iter(peaks.values())))
    assert bound == "memory" and (0.010 < least < 0.016 if layers == 16 else 0.007 < least < 0.013)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """On the parent's program (no counter) and on another family's cell."""
    import importlib.util

    class View:
        record = {"occupancy": {"visible_positions": 10}, "trace_dir": None, "records": []}
        config = {"sa_config": {}}
        traffic = {"trace_from_s": 1, "trace_seconds": 1}
        trace = None

        def decode_step_s(self):
            return None

    for name in NEW:
        path = ROOT / "chipbench" / "layer_metrics" / f"{name}.py"
        spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        assert module.read(View()) is None
        assert module.MOVES == "tpot_ms_p50"
