"""The latent-attention mixture configuration's rehearsal on the CPU at a
tiny size: runner, adapter, reference, judge and every new reader end to
end; the int4 control and a broken timed path (the absorbed score without
its rotary term) coming out not ``correct``; the operations and bytes
against hand counts; the benchmark's copy of the reference against the
repo's. No device number."""

import json
import re
from pathlib import Path

import pytest

DATA = Path(__file__).parent / "data"
ROOT = Path(__file__).resolve().parents[2]
CELL, TINY = "glm_flash_code_context_decode", "tiny_code_context"
NEW = {"latent_attn_ms_per_step", "latent_attn_roofline", "latent_moe_decode_step_roofline",
       "latent_moe_prefill_roofline"}


def _real():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def bench():
    """The real BENCHMARK.json's metrics over the tiny latent cell."""
    tiny = json.loads((DATA / "tiny_latent_bench.json").read_text())
    out = dict(_real(), configs=tiny["configs"], workloads=tiny["workloads"])
    for m in out["end_to_end"] + out["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TINY for w in m["workloads"] if w == CELL]
    return out


def _run(bench, trace=False, seconds=3.0, seed=2 ** 31 + 11):
    from chipbench import run

    return run.run_cell(bench, TINY, seed, seconds, trace, require_chip=False, files_root=DATA)


def _tiny_cfg():
    return json.loads((DATA / "configs" / "tiny-glm-moe-lite.json").read_text())


def _real_cfg():
    return json.loads((ROOT / "chipbench" / "configs" / "glm-4.7-flash-int8.json").read_text())


def test_the_real_cell_is_as_the_issue_names_it():
    real = _real()
    (cell,) = [w for w in real["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config="glm-4.7-flash-int8", traffic="code_context_lognormal_poisson", chips=1)
    e2e = {m["name"] for m in real["end_to_end"] if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"tpot_ms_p50", "setup_s"}
    layer = {m["name"] for m in real["per_layer"] if CELL in m.get("workloads", [])}
    assert NEW | {"slo_met_pct", "harvest_lag_ms_p50", "decode_step_device_ms", "prefill_device_ms_p50",
                  "device_idle_pct.serve", "pool_parked_admission_pct"} <= layer
    # their operations and bytes count grouped-query attention on K and V pools
    assert not {"decode_step_roofline", "prefill_roofline", "paged_attn_ms_per_step"} & layer
    # every per-layer entry lists its cells (one that does not is read in every cell,
    # the parent's too), and the new ones list this cell alone
    assert all("workloads" in m for m in real["per_layer"])
    assert all(m["workloads"] == [CELL] for m in real["per_layer"] if m["name"] in NEW)
    assert all((ROOT / "chipbench" / "layer_metrics" / f"{m['name']}.py").is_file() for m in real["per_layer"])


def test_the_configuration_keeps_every_published_number():
    cfg = _real_cfg()
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        (entry,) = [e for e in map(json.loads, catalog.read_text().splitlines()) if e["name"] == "GLM-4.7-Flash"]
        assert cfg["source"] == entry["source_url"]
        differs = {k for k, v in entry["config"].items() if cfg.get(k, "missing") != v}
        assert differs == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 13 and cfg["published"]["num_hidden_layers"] == 47
    mix = json.loads((ROOT / "chipbench" / "traffic" / "code_context_lognormal_poisson.json").read_text())
    assert mix["prompt_tokens"] == {"median": 2048, "sigma": 0.7, "min": 256, "max": 4096}
    assert mix["output_tokens"] == {"median": 256, "sigma": 0.7, "min": 32, "max": 512}
    assert mix["prompt_tokens"]["max"] <= cfg["serving"]["prompt_buckets"][-1]
    assert mix["output_tokens"]["max"] <= cfg["serving"]["max_new_tokens"]


def test_serve_runner_rehearsal(bench):
    line = _run(bench)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 12
    assert set(line["metrics"]) == {"tpot_ms_p50", "setup_s"}
    assert line["device"]["platform"] == "cpu" and line["compiles_in_window"] == 0


def test_serve_runner_rehearsal_traced(bench):
    line = _run(bench, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    # the counters read on a CPU; what needs a device trace is left out, and no reader raises
    assert {"pool_parked_admission_pct", "slot_occupancy_pct", "ttft_ms_p50"} <= set(line["metrics"])
    assert not NEW & set(line["metrics"])


def test_an_absorbed_score_without_its_rotary_term_is_not_correct(bench, monkeypatch):
    from unionml_tpu.models import glm_moe_lite

    real = glm_moe_lite.paged_latent_attention
    rank = _tiny_cfg()["kv_lora_rank"]
    monkeypatch.setattr(
        glm_moe_lite, "paged_latent_attention",
        lambda q, pool, table, lengths, **kw: real(q.at[..., rank:].set(0), pool, table, lengths, **kw),
    )
    line = _run(bench)
    assert line["correct"] is False and line["failed"] == 0


def _sound_and_control(seed):
    import jax.numpy as jnp
    import numpy as np

    from chipbench import judge, weights
    from chipbench.adapters import glm_moe_lite as adapter
    from chipbench.reference import glm_moe_lite as reference

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


@pytest.mark.parametrize("seed", [1, 3, 4])
def test_serving_control_in_int4_fails_where_the_program_passes(seed):
    cfg, _, gaps = _sound_and_control(seed)
    assert gaps["served"]["mean"] <= cfg["correct"]["served_logit_gap_mean"] < gaps["control"]["mean"]
    assert gaps["served"]["max"] <= cfg["correct"]["served_logit_gap_max"]


def test_the_benchmarks_reference_is_the_repos():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import weights
    from chipbench.adapters import glm_moe_lite as adapter
    from chipbench.reference import glm_moe_lite as copy
    from unionml_tpu.models import glm_moe_lite_reference as original

    cfg = _tiny_cfg()
    params = weights.make_tree(adapter.build(cfg)["abstract_serve_params"](), 5)
    # the selection bias is filled small and not zero: the selection-only path runs
    bias = np.asarray(params["block_1"]["moe"]["e_score_correction_bias"])
    assert bias.shape == (8, 1) and 0.05 < np.abs(bias).mean() < 1.0
    tokens = jnp.asarray(np.random.default_rng(5).integers(1, 256, (1, 70)))
    with jax.default_matmul_precision("highest"):
        ours = copy.forward_layerwise(params, tokens, cfg)
        theirs = np.asarray(original.forward(params, tokens, cfg))
    assert isinstance(ours, np.ndarray) and ours.shape == theirs.shape == (1, 70, 256)
    assert np.abs(ours - theirs).max() < 5e-4
    # and it imports nothing of the program
    assert not re.search(r"^\s*(from|import) unionml_tpu", Path(copy.__file__).read_text(), re.M)


def test_the_reference_takes_the_head_in_row_blocks(monkeypatch):
    """More rows than a block of the head and of the softmax: the same logits."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench import weights
    from chipbench.adapters import glm_moe_lite as adapter
    from chipbench.reference import glm_moe_lite as reference

    cfg = _tiny_cfg()
    params = weights.make_tree(adapter.build(cfg)["abstract_serve_params"](), 6)
    tokens = jnp.asarray(np.random.default_rng(6).integers(1, 256, (1, 50)))
    whole = reference.forward_layerwise(params, tokens, cfg)
    monkeypatch.setattr(reference, "_ROW_BLOCK", 16)
    monkeypatch.setattr(reference, "_Q_BLOCK", 16)
    blocked = reference.forward_layerwise(params, tokens, cfg)
    assert blocked.shape == whole.shape and np.abs(blocked - whole).max() < 1e-4


def test_latent_ops_and_bytes_against_hand_counts():
    from chipbench import opsbytes_latent as ob

    cfg = _real_cfg()
    d, vocab = 2048, 154880
    attn = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
    assert ob.attention_params(cfg) == attn == 21_757_952
    assert ob.expert_params(cfg) == 3 * 2048 * 1536 and ob.dense_mlp_params(cfg) == 3 * 2048 * 10240
    # a token passes its four experts and the shared one, and the router
    token = 13 * attn + 3 * d * 10240 + 12 * (5 * 3 * d * 1536 + d * 64) + d * vocab
    assert ob.matmul_params_a_token(cfg) == token
    # every expert touched: the issue's 8.05 GB; one row touches four
    all_of_them = 13 * attn + 3 * d * 10240 + 12 * 65 * 3 * d * 1536 + d * vocab + 12 * 2049 * 64 * 4.0
    assert ob.weight_bytes(cfg, 1e6) == pytest.approx(all_of_them) and 8.0e9 < all_of_them < 8.1e9
    assert ob.experts_touched(cfg, 1) == pytest.approx(4.0)
    assert ob.experts_touched(cfg, 32) == pytest.approx(64 * (1 - (60 / 64) ** 32))
    assert ob.weight_bytes(cfg, 1) == pytest.approx(all_of_them - 12 * 60 * 3 * d * 1536)
    assert ob.latent_row_bytes(cfg) == 1152
    # 1,000 cached positions: a row a layer read once, 20 heads x (576 + 512) x 2 operations
    flops, moved = ob.latent_attention_cost(cfg, 1000.0)
    assert moved == 13 * 1152 * 1000 and flops == 2.0 * 13 * 20 * 1088 * 1000
    # a step over 10 sequences holding 5,000 positions
    flops, moved = ob.decode_step_cost(cfg, 10.0, 5000.0)
    assert moved == pytest.approx(ob.weight_bytes(cfg, 10.0) + 13 * 1152 * (5000 + 10) + 10 * d * 4)
    assert flops == pytest.approx(2.0 * 10 * token + 2.0 * 13 * 20 * 1088 * 5000)
    # a 100-token prompt: the head once, the causal half square at 256 + 256
    flops, moved = ob.prefill_cost(cfg, 100)
    assert flops == pytest.approx(2.0 * 100 * (token - d * vocab) + 2.0 * d * vocab + 13 * 20 * 512 * 100.0 * 100)
    assert moved == pytest.approx(ob.weight_bytes(cfg, 100) + 100 * 13 * 1152 + 100 * d * 4)


class _Trace:
    ops = {
        "%paged_latent_attention.3 = bf16[..]": [(0.10, 0.11), (0.12, 0.13), (0.31, 0.32), (0.33, 0.35), (0.52, 0.53)],
        "%paged_attention.1 = bf16[..]": [(0.14, 0.20)],
    }
    runs = {"jit_decode_chunk(123)": [(0.1, 0.2), (0.3, 0.4), (0.5, 0.6)], "jit_prefill(9)": [(0.21, 0.29)]}
    devices = [object()]

    def ops_matching(self, pattern):
        return [iv for name, ivs in self.ops.items() if re.search(pattern, name) for iv in ivs]

    def module_runs(self, pattern):
        return [iv for name, ivs in self.runs.items() if re.search(pattern, name) for iv in ivs]


def _reader(name):
    from chipbench.run import _load_reader

    return _load_reader(name)


def test_the_new_readers_read_a_trace_and_return_none_without_one():
    from chipbench import opsbytes_latent as ob

    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    class Run:
        trace = _Trace()
        config = _real_cfg()
        traffic = {"trace_from_s": 10, "trace_seconds": 4}
        record = {
            "chunk_steps": 2, "trace_dir": "x", "t_zero": 100.0, "window_s": 51.0, "timelines": [],
            "records": [{"error": None, "rid": "a", "n_prompt": 2000, "tokens": [1] * 40,
                         "t_tokens": [105.0 + 0.5 * i for i in range(40)]}],
        }

        def decode_step_s(self):
            return 0.1 / 2

        def prompt_lengths_prefilled_while_traced(self):
            return [2000]

    Run.peaks = peaks
    run = Run()
    # two whole chunks of two kernel calls each (0.02 s and 0.03 s); the third was cut by the trace's end
    ms = _reader("latent_attn_ms_per_step").read(run)
    assert ms == pytest.approx(1e3 * 0.05 / (2 * 2))
    # one sequence live through the traced seconds, 2,000 + 15 positions
    least = 13 * 1152 * 2015 / 819e9
    assert _reader("latent_attn_roofline").read(run) == pytest.approx(100 * least * 1e3 / ms)
    _, moved = ob.decode_step_cost(run.config, 1.0, 2015.0)
    assert _reader("latent_moe_decode_step_roofline").read(run) == pytest.approx(100 * (moved / 819e9) / 0.05)
    flops, _ = ob.prefill_cost(run.config, 2000)
    assert _reader("latent_moe_prefill_roofline").read(run) == pytest.approx(100 * (flops / 197e12) / 0.08)
    # another configuration's run, a run without a trace, a program without the kernel: nothing, and no raise
    for broken in ("config", "trace", "kernel"):
        other = Run()
        if broken == "config":
            other.config = {"layer_types": []}
        elif broken == "trace":
            other.trace, other.record = None, dict(Run.record, trace_dir=None)
            other.decode_step_s = lambda: None
        else:
            other.trace = type("T", (_Trace,), {"ops": {}, "runs": {}})()
            other.decode_step_s = lambda: None
        for name in sorted(NEW):
            if broken == "config" and name == "latent_attn_ms_per_step":
                continue  # a time, read wherever the kernel's name shows
            assert _reader(name).read(other) is None, (broken, name)
