"""CPU rehearsals of each runner at tiny sizes, the controls, and a timed path
broken underneath. Nothing here is a device number: these tests only show
that the harness runs end to end and that ``correct`` can come out false."""

import json
from pathlib import Path

import pytest

DATA = Path(__file__).parent / "data"
ROOT = Path(__file__).resolve().parents[2]
# the tiny four-device train cell reports what the one-chip train cell reports
RENAME = {"vit_b16_train": ["tiny_vit_train", "tiny_dense_train4"], "mixtral_chat_decode": ["tiny_chat"]}


@pytest.fixture(scope="module")
def bench():
    """The real BENCHMARK.json's metrics over the tiny cells."""
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    tiny = json.loads((DATA / "tiny_bench.json").read_text())
    out = dict(real, configs=tiny["configs"], workloads=tiny["workloads"])
    for m in out["end_to_end"] + out["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [t for w in m["workloads"] for t in RENAME.get(w, [])]
    return out


def _run(bench, workload, trace=False, seconds=1.0, seed=2 ** 31 + 7):
    from chipbench import run

    return run.run_cell(bench, workload, seed, seconds, trace, require_chip=False, files_root=DATA)


def _load(name):
    return json.loads((DATA / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("workload", ["tiny_vit_train", "tiny_dense_train4"])
def test_train_runner_rehearsal(bench, workload):
    line = _run(bench, workload)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 3
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"  # never mistaken for a chip result
    assert line["compiles_in_window"] == 0


def test_serve_runner_rehearsal_traced(bench):
    line = _run(bench, "tiny_chat", trace=True, seconds=3.0)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 12
    # readers that find something on a CPU report; those that need a device trace leave out
    assert {"slo_met_pct", "ttft_ms_p50", "queue_wait_ms_p90", "http_added_ttft_ms_p50",
            "slot_occupancy_pct"} <= set(line["metrics"])
    assert "decode_step_roofline" not in line["metrics"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(bench, monkeypatch):
    from chipbench.adapters import vit

    real = vit.build

    def broken(cfg):
        built = real(cfg)
        step = built["step_fn"]
        built["step_fn"] = lambda state, batch: (state, step(state, batch)[1])
        return built

    monkeypatch.setattr(vit, "build", broken)
    line = _run(bench, "tiny_vit_train")
    assert line["correct"] is False


def test_a_token_altered_where_it_is_produced_is_not_correct(bench, monkeypatch):
    from unionml_tpu.serving.engine import DecodeEngine

    real = DecodeEngine.generate_stream

    def altered(self, params, prompt, **kw):
        for chunk in real(self, params, prompt, **kw):
            yield [(int(t) + 1) % 256 for t in chunk]

    monkeypatch.setattr(DecodeEngine, "generate_stream", altered)
    line = _run(bench, "tiny_chat", seconds=2.0)
    assert line["correct"] is False and line["failed"] == 0


@pytest.mark.parametrize("name,chips", [("tiny-vit", 1), ("tiny-dense-train", 4)])
def test_training_control_in_fp8_fails_where_the_program_passes(name, chips):
    from chipbench import judge
    from chipbench.runners.train_step_loop import Cell

    cfg = _load(name)
    mix = json.loads((DATA / "traffic" / "tiny_train.json").read_text())
    cell = Cell(cfg, mix, chips)
    for seed in (1, 2, 3):
        batches = cell.make_batches(seed)
        _, got = cell.first_three(cell.make_state(seed), batches, seed)
        want = cell.reference(seed, batches)
        ctrl = cell.reference(seed, batches, control="fp8")
        sound = judge.compare_training(got, want, cfg["correct"])
        control = judge.compare_training(ctrl, want, cfg["correct"])
        assert all(n["value"] <= n["limit"] for n in sound.values())
        assert any(n["value"] > n["limit"] for n in control.values())
        assert control["grad_norm_gap_worst_leaf"]["value"] > 3 * sound["grad_norm_gap_worst_leaf"]["value"]


def test_serving_control_in_int4_fails_where_the_program_passes():
    import jax.numpy as jnp
    import numpy as np

    from chipbench import judge, weights
    from chipbench.adapters import llama_decoder
    from chipbench.reference import decoder

    cfg = _load("tiny-moe-int8")
    built = llama_decoder.build(cfg)
    for seed in (1, 2, 3):
        params = weights.make_tree(built["abstract_serve_params"](), seed)
        rng = np.random.default_rng(seed)
        prompt = rng.integers(1, 256, 12).tolist()
        # greedy tokens of the reference itself stand for a sound served stream
        toks = list(prompt)
        for _ in range(12):
            logits = decoder.forward_layerwise(params, jnp.asarray([toks]), cfg)
            toks.append(int(np.asarray(logits)[0, -1].argmax()))
        sample = [{"prompt": prompt, "tokens": toks[len(prompt):]}]
        gaps = judge.served_logit_gaps(
            lambda seq: decoder.forward_layerwise(params, jnp.asarray(seq), cfg), sample, 32,
            control_forward=lambda seq: decoder.forward_layerwise(params, jnp.asarray(seq), cfg, "int4"),
        )
        assert gaps["served"]["mean"] <= cfg["correct"]["served_logit_gap_mean"] < gaps["control"]["mean"]
        assert gaps["served"]["max"] <= cfg["correct"]["served_logit_gap_max"]
