"""The sparse-attention mixture configuration's rehearsal on the CPU at a
tiny size: runner, adapter, reference, judge and every new reader end to
end; the int4 control and a timed path with the selection removed coming out
not ``correct``; the operations and bytes against hand counts; the
benchmark's copy of the reference against the repo's. No device number.

The tiny limits (``tests/data/configs/tiny-keye-vl-moe.json``): over six
seeds the program's mean gap reads 0.12-0.23 and its widest 0.7-2.5 (16
picks of 24-96 positions at an indexer 16 wide: a bfloat16 score at the
boundary picks another position than the float32 reference for a good share
of tokens, and one of 16 picks weighs far more than one of 2,048), the int4
control's mean 0.54-0.84, a path with the selection removed 1.5-2.0 and
4.2-5.7: the mean's limit 0.35 lies between, the widest's 3.5 is held
against the removed selection only.
"""

import json
import re
from pathlib import Path

import pytest

DATA = Path(__file__).parent / "data"
ROOT = Path(__file__).resolve().parents[2]
CELL, TINY = "keye_vl2_long_context_decode", "tiny_long_context"
NEW = {"index_select_ms_per_step", "sparse_attn_ms_per_step", "index_scores_roofline", "sparse_attn_roofline",
       "selected_rows_pct", "sparse_moe_decode_step_roofline", "sparse_moe_prefill_roofline"}


def _real():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def bench():
    """The real BENCHMARK.json's metrics over the tiny sparse cell."""
    tiny = json.loads((DATA / "tiny_sparse_bench.json").read_text())
    out = dict(_real(), configs=tiny["configs"], workloads=tiny["workloads"])
    for m in out["end_to_end"] + out["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TINY for w in m["workloads"] if w == CELL]
    return out


def _run(bench, trace=False, seconds=3.0, seed=2 ** 31 + 11):
    from chipbench import run

    return run.run_cell(bench, TINY, seed, seconds, trace, require_chip=False, files_root=DATA)


def _tiny_cfg():
    return json.loads((DATA / "configs" / "tiny-keye-vl-moe.json").read_text())


def _real_cfg():
    return json.loads((ROOT / "chipbench" / "configs" / "keye-vl-2.0-30b-a3b-int8.json").read_text())


def test_the_real_cell_is_as_the_issue_names_it():
    real = _real()
    (cell,) = [w for w in real["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config="keye-vl-2.0-30b-a3b-int8", traffic="long_context_lognormal_poisson", chips=1)
    (config,) = [c for c in real["configs"] if c["name"] == "keye-vl-2.0-30b-a3b-int8"]
    assert config["reduced"] == ["num_hidden_layers"]
    e2e = {m["name"] for m in real["end_to_end"] if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"tpot_ms_p50", "setup_s"}
    layer = {m["name"] for m in real["per_layer"] if CELL in m.get("workloads", [])}
    assert NEW | {"slo_met_pct", "harvest_lag_ms_p50", "decode_step_device_ms", "prefill_device_ms_p50",
                  "device_idle_pct.serve", "pool_parked_admission_pct", "decode_step_mixer_ms"} <= layer
    # the other families' operations and bytes count other attentions
    assert not {"decode_step_roofline", "prefill_roofline", "paged_attn_ms_per_step", "latent_attn_roofline"} & layer
    assert all("workloads" in m for m in real["per_layer"])
    assert all(m["workloads"] == [CELL] for m in real["per_layer"] if m["name"] in NEW)
    assert all((ROOT / "chipbench" / "layer_metrics" / f"{m['name']}.py").is_file() for m in real["per_layer"])
    # new entries stand at the end of their lists
    assert real["workloads"][-1]["name"] == CELL and real["configs"][-1] is config
    assert {m["name"] for m in real["per_layer"][-len(NEW):]} == NEW


def test_the_configuration_keeps_every_published_number():
    cfg = _real_cfg()
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        (entry,) = [e for e in map(json.loads, catalog.read_text().splitlines()) if e["name"] == "Keye-VL-2.0-30B-A3B"]
        assert cfg["source"] == entry["source_url"]
        differs = {k for k, v in entry["config"].items() if cfg.get(k, "missing") != v}
        assert differs == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 12 and cfg["published"]["num_hidden_layers"] == 48
    assert cfg["sa_config"]["topk"] == 2048 and cfg["num_experts"] == 128 and cfg["num_experts_per_tok"] == 8
    mix = json.loads((ROOT / "chipbench" / "traffic" / "long_context_lognormal_poisson.json").read_text())
    assert mix["prompt_tokens"] == {"median": 8192, "sigma": 0.5, "min": 4096, "max": 16384}
    assert mix["output_tokens"] == {"median": 384, "sigma": 0.6, "min": 64, "max": 1024}
    assert (mix["ramp_s"], mix["check_requests"], mix["trace_from_s"], mix["trace_seconds"]) == (20.0, 3, 10, 4)
    assert mix["prompt_tokens"]["max"] <= cfg["serving"]["prompt_buckets"][-1]
    assert mix["output_tokens"]["max"] <= cfg["serving"]["max_new_tokens"]
    # every request decodes with the selection active
    assert mix["prompt_tokens"]["min"] > cfg["sa_config"]["topk"]


def test_serve_runner_rehearsal(bench):
    line = _run(bench)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 12
    assert set(line["metrics"]) == {"tpot_ms_p50", "setup_s"}
    assert line["device"]["platform"] == "cpu" and line["compiles_in_window"] == 0


def test_serve_runner_rehearsal_traced(bench):
    line = _run(bench, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    # the counters read on a CPU; what needs a device trace is left out, and no reader raises
    assert {"pool_parked_admission_pct", "slot_occupancy_pct", "ttft_ms_p50", "selected_rows_pct"} <= set(line["metrics"])
    assert not (NEW - {"selected_rows_pct"}) & set(line["metrics"])
    # 16 picks of prompts of 24-64 tokens and what they grow to
    assert 15.0 < line["metrics"]["selected_rows_pct"]["value"] < 70.0


def test_a_timed_path_with_the_selection_removed_is_not_correct(bench, monkeypatch):
    """Dense attention over every cached row (``topk`` past any length, in
    the prefill and in the decode step alike) is another model."""
    from unionml_tpu.models import keye_vl_moe

    real = keye_vl_moe.KeyeVLMoeConfig.from_hf.__func__
    monkeypatch.setattr(
        keye_vl_moe.KeyeVLMoeConfig, "from_hf",
        classmethod(lambda cls, hf, **over: real(cls, hf, **dict(over, index_topk=10 ** 6))),
    )
    line = _run(bench)
    assert line["correct"] is False and line["failed"] == 0


def _sound_and_control(seed):
    import jax.numpy as jnp
    import numpy as np

    from chipbench import judge, weights
    from chipbench.adapters import keye_vl_moe as adapter
    from chipbench.reference import keye_vl_moe as reference

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


def test_the_benchmarks_reference_is_the_repos(monkeypatch):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import weights
    from chipbench.adapters import keye_vl_moe as adapter
    from chipbench.reference import keye_vl_moe as copy
    from unionml_tpu.models import keye_vl_moe_reference as original

    cfg = _tiny_cfg()
    params = weights.make_tree(adapter.build(cfg)["abstract_serve_params"](), 5)
    # the indexer's query projection is int8 like the wide ones; its key and weight projections are float
    attn = params["block_1"]["attn"]
    assert "kernel_q" in attn["index_q"] and "kernel" in attn["index_k"] and "kernel" in attn["index_w"]
    tokens = jnp.asarray(np.random.default_rng(5).integers(1, 256, (1, 80)))
    monkeypatch.setattr(copy, "_Q_BLOCK", 16)   # five blocks of queries
    monkeypatch.setattr(copy, "_ROW_BLOCK", 32)
    with jax.default_matmul_precision("highest"):
        ours = copy.forward_layerwise(params, tokens, cfg)
        theirs, picked = original.forward(params, tokens, cfg, return_selected=True)
        dense = copy.forward_layerwise(params, tokens, cfg, select=False)
    assert isinstance(ours, np.ndarray) and ours.shape == theirs.shape == (1, 80, 256)
    assert np.abs(ours - np.asarray(theirs)).max() < 5e-4
    # the selection is active (16 of up to 80) and dense attention is another model
    assert int(np.asarray(picked)[0, 0].sum(-1).max()) == 16 and np.abs(dense - ours).max() > 0.1
    # and it imports nothing of the program
    assert not re.search(r"^\s*(from|import) unionml_tpu", Path(copy.__file__).read_text(), re.M)


@pytest.mark.parametrize("trailing_zeros", [0, 3, 20], ids=["none", "a-few", "the-whole-stream"])
def test_the_reference_can_keep_to_the_rows_a_served_stream_is_read_from(monkeypatch, trailing_zeros):
    """``correct.reference_logits: served_tail``: the head for the span a
    stream's rows must lie in (``max_new_tokens + 1`` positions before the
    last token that is not zero, ``max_new_tokens`` behind it), zeros
    elsewhere; the judge reads the same gaps from either array, also for a
    stream that ends in token 0 or holds nothing else (rows left at zero
    would read as a gap of 0: a miss that passes)."""
    import numpy as np

    from chipbench import judge, weights
    from chipbench.adapters import keye_vl_moe as adapter
    from chipbench.reference import keye_vl_moe as reference

    assert _real_cfg()["correct"]["reference_logits"] == "served_tail"
    cfg = _tiny_cfg()
    tail_cfg = dict(cfg, correct=dict(cfg["correct"], reference_logits="served_tail"))
    params = weights.make_tree(adapter.build(cfg)["abstract_serve_params"](), 7)
    monkeypatch.setattr(reference, "_ROW_BLOCK", 16)
    rng = np.random.default_rng(7)
    prompt, tokens = rng.integers(1, 256, 50).tolist(), rng.integers(1, 256, 20).tolist()
    tokens[20 - trailing_zeros:] = [0] * trailing_zeros
    seq = np.zeros((1, 128), np.int32)
    seq[0, :70] = prompt + tokens
    whole = reference.forward_layerwise(params, seq, cfg)
    tail = reference.forward_layerwise(params, seq, tail_cfg)
    end = 70 - trailing_zeros
    first = (end - 33) // 16 * 16      # max_new_tokens 32: 33 rows back, down to a block's start
    assert first <= 49 and end + 32 >= 69          # the stream's rows 49..68 lie inside
    assert np.array_equal(tail[0, first:end + 32], whole[0, first:end + 32])
    assert not tail[0, :first].any() and not tail[0, -(-(end + 32) // 16) * 16:].any()
    sample = [{"prompt": prompt, "tokens": tokens}]
    gaps = [judge.served_logit_gaps(lambda s, c=c: reference.forward_layerwise(params, s, c), sample, 128)
            for c in (cfg, tail_cfg)]
    assert gaps[0]["served"] == gaps[1]["served"] and gaps[0]["tokens"] == 20


def test_sparse_ops_and_bytes_against_hand_counts():
    from chipbench import opsbytes_sparse as ob

    cfg = _real_cfg()
    d, vocab = 2048, 151936
    attn = 2048 * 128 * (2 * 32 + 2 * 4)
    assert ob.attention_params(cfg) == attn == 18_874_368
    assert ob.indexer_params(cfg) == 2048 * (16 * 64 + 64 + 16) == 2_260_992
    assert ob.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    # a token passes its eight experts and the router
    token = 12 * (attn + 2_260_992 + d * 128 + 8 * 4_718_592) + d * vocab
    assert ob.matmul_params_a_token(cfg) == token
    # every expert touched: the issue's 7.51 GB of layers + 0.31 of head; one row touches eight
    all_of_them = 12 * (attn + 2048 * 1024 + 128 * 4_718_592) + d * vocab + 12 * 2048 * (128 + 64 + 16) * 4.0
    assert ob.weight_bytes(cfg, 1e6) == pytest.approx(all_of_them) and 7.7e9 < all_of_them < 7.9e9
    assert ob.experts_touched(cfg, 1) == pytest.approx(8.0)
    assert ob.experts_touched(cfg, 16) == pytest.approx(128 * (1 - (120 / 128) ** 16))
    assert ob.weight_bytes(cfg, 1) == pytest.approx(all_of_them - 12 * 120 * 4_718_592)
    assert ob.kv_row_bytes(cfg) == 2048 and ob.index_key_bytes(cfg) == 128
    assert ob.picked_positions(cfg, [100, 2048, 9000]) == 100 + 2048 + 2048
    # 10,000 visible positions: a 128 B key a layer read once, 16 heads x 64 x 2 operations
    flops, moved = ob.index_scores_cost(cfg, 10_000.0)
    assert moved == 12 * 128 * 10_000 and flops == 2.0 * 12 * 16 * 64 * 10_000
    # 4,096 selected positions: 2,048 B a layer, 32 heads x (128 + 128) x 2 operations
    flops, moved = ob.sparse_attention_cost(cfg, 4096.0)
    assert moved == 12 * 2048 * 4096 and flops == 2.0 * 12 * 32 * 256 * 4096
    # a step over 10 sequences seeing 90,000 positions and selecting 20,480
    flops, moved = ob.decode_step_cost(cfg, 10.0, 90_000.0, 20_480.0)
    assert moved == pytest.approx(
        ob.weight_bytes(cfg, 10.0) + 12 * 128 * 90_000 + 12 * 2048 * 20_480 + 10 * 12 * 2176 + 10 * d * 4)
    assert flops == pytest.approx(2.0 * 10 * token + 2.0 * 12 * 16 * 64 * 90_000 + 2.0 * 12 * 32 * 256 * 20_480)
    # a 5,000-token prompt: the head once, index scores over the half square, attention over min(t + 1, 2048)
    flops, moved = ob.prefill_cost(cfg, 5000)
    attended = 2048 * 2049 / 2 + (5000 - 2048) * 2048
    assert flops == pytest.approx(
        2.0 * 5000 * (token - d * vocab) + 2.0 * d * vocab + 2.0 * 12 * 16 * 64 * 5000 * 5001 / 2
        + 2.0 * 12 * 32 * 256 * attended)
    assert moved == pytest.approx(ob.weight_bytes(cfg, 5000) + 5000 * 12 * 2176 + 5000 * d * 4)
    # a prompt no longer than topk selects everything: no index scores
    flops_short, _ = ob.prefill_cost(cfg, 1000)
    assert flops_short == pytest.approx(
        2.0 * 1000 * (token - d * vocab) + 2.0 * d * vocab + 2.0 * 12 * 32 * 256 * 1000 * 1001 / 2)


class _Op:
    def __init__(self, tf_op, start, end, run=0, program="jit_decode_chunk(123)", name="fusion.1", category="fusion"):
        self.tf_op, self.start_s, self.end_s, self.run, self.program = tf_op, start, end, run, program
        self.name, self.hlo_category = name, category


_ROOT = "jit(decode_chunk)/jit(main)/while/body/KeyeVLMoe/block_3/attn/"


def _ops():
    """Two whole chunks and one the trace cut (it holds fewer operations)."""
    out = []
    for run, t0 in ((0, 0.1), (1, 0.3)):
        out += [
            _Op(_ROOT + "indexer/index_q/dot_general", t0, t0 + 0.001, run),
            _Op(_ROOT + "indexer/paged_index_scores/pallas_call", t0 + 0.01, t0 + 0.014, run, name="paged_index_scores.2"),
            _Op(_ROOT + "select/top_k", t0 + 0.02, t0 + 0.025, run, name="sort.5"),
            _Op(_ROOT + "paged_sparse_attention/gather", t0 + 0.03, t0 + 0.036, run),
            _Op(_ROOT + "paged_sparse_attention/dot_general", t0 + 0.04, t0 + 0.042, run),
            _Op(_ROOT + "q/dot_general", t0 + 0.05, t0 + 0.06, run),
        ]
    out += [_Op(_ROOT + "select/top_k", 0.52, 0.53, 2, name="sort.5")]
    return out


def _reader(name):
    from chipbench.run import _load_reader

    return _load_reader(name)


def test_the_new_readers_read_a_trace_and_return_none_without_one(monkeypatch):
    from chipbench import opsbytes_sparse as ob
    from chipbench import opscopes

    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops = _ops()
    monkeypatch.setattr(opscopes, "for_run", lambda run: run.ops)

    class _Trace:
        runs = {"jit_decode_chunk(123)": [(0.1, 0.2), (0.3, 0.4), (0.5, 0.6)], "jit_prefill(9)": [(0.21, 0.29)]}

        def module_runs(self, pattern):
            return [iv for name, ivs in self.runs.items() if re.search(pattern, name) for iv in ivs]

    class Run:
        trace = _Trace()
        config = _real_cfg()
        traffic = {"trace_from_s": 10, "trace_seconds": 4}
        record = {
            "chunk_steps": 2, "trace_dir": "x", "t_zero": 100.0, "window_s": 51.0, "timelines": [],
            "occupancy": {"selected_positions": 2048 * 50, "visible_positions": 9000 * 50},
            "records": [{"error": None, "rid": "a", "n_prompt": 9000, "tokens": [1] * 40,
                         "t_tokens": [105.0 + 0.5 * i for i in range(40)]}],
        }

        def decode_step_s(self):
            return 0.1 / 2

        def prompt_lengths_prefilled_while_traced(self):
            return [9000]

    Run.peaks, Run.ops = peaks, ops
    run = Run()
    # two whole chunks of two steps: indexer projection + scores kernel + top-k
    assert _reader("index_select_ms_per_step").read(run) == pytest.approx(1e3 * 2 * 0.010 / (2 * 2))
    attn_ms = _reader("sparse_attn_ms_per_step").read(run)
    assert attn_ms == pytest.approx(1e3 * 2 * 0.008 / (2 * 2))
    # one sequence live through the traced seconds, 9,000 + 15 positions, of which it selects 2,048
    assert ob.traced_load(run, run.config) == pytest.approx((1.0, 9015.0, 2048.0))
    kernel_ms = 1e3 * 2 * 0.004 / (2 * 2)
    assert _reader("index_scores_roofline").read(run) == pytest.approx(100 * (12 * 128 * 9015 / 819e9) * 1e3 / kernel_ms)
    assert _reader("sparse_attn_roofline").read(run) == pytest.approx(100 * (12 * 2048 * 2048 / 819e9) * 1e3 / attn_ms)
    assert _reader("selected_rows_pct").read(run) == pytest.approx(100 * 2048 / 9000)
    _, moved = ob.decode_step_cost(run.config, 1.0, 9015.0, 2048.0)
    assert _reader("sparse_moe_decode_step_roofline").read(run) == pytest.approx(100 * (moved / 819e9) / 0.05)
    flops, _ = ob.prefill_cost(run.config, 9000)
    assert _reader("sparse_moe_prefill_roofline").read(run) == pytest.approx(100 * (flops / 197e12) / 0.08)
    # another configuration's run, a run without a trace, a program without the scopes (the parent's):
    # nothing, and no raise
    for broken in ("config", "trace", "scopes"):
        other = Run()
        if broken == "config":
            other.config = {"layer_types": []}
        elif broken == "trace":
            other.trace, other.ops, other.record = None, None, dict(Run.record, trace_dir=None, occupancy={})
            other.decode_step_s = lambda: None
        else:
            other.ops = [_Op(_ROOT + "q/dot_general", 0.1, 0.2)]
            other.trace = type("T", (_Trace,), {"runs": {}})()
            other.record = dict(Run.record, occupancy={"occupancy_ratio": 0.5})
            other.decode_step_s = lambda: None
        for name in sorted(NEW):
            if broken == "config" and name in ("index_select_ms_per_step", "sparse_attn_ms_per_step", "selected_rows_pct"):
                continue  # times and a counter, read wherever the scopes or the counter show
            assert _reader(name).read(other) is None, (broken, name)
