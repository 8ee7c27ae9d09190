"""Serving-mode auto-selection (serving/auto.py): the measured
engine-vs-batcher crossover rule, decided from evidence instead of
operator guesswork."""

import jax
import jax.numpy as jnp
import pytest

# measured sub-minute module: part of the `-m quick` tier (Makefile
# test-quick) so iteration/CI sharding get a <5-min spec-path pass
pytestmark = pytest.mark.quick

from unionml_tpu.models import Llama, LlamaConfig
from unionml_tpu.serving.auto import (
    choose_serving_mode,
    decide_mode,
    measure_decode_chunk_ms,
    measure_rtt_ms,
)


def test_decide_mode_both_ways():
    # slow-link regime: RTT >> chunk compute → batcher
    assert decide_mode(rtt_ms=119.0, decode_chunk_ms=26.0) == "batcher"
    # directly-attached or big-model regime: chunk >= RTT → engine
    assert decide_mode(rtt_ms=0.5, decode_chunk_ms=26.0) == "engine"
    assert decide_mode(rtt_ms=88.0, decode_chunk_ms=88.0) == "engine"  # tie
    with pytest.raises(ValueError, match="non-negative"):
        decide_mode(rtt_ms=-1.0, decode_chunk_ms=1.0)


def test_choose_serving_mode_injected_timings():
    out = choose_serving_mode(rtt_ms=119.0, decode_chunk_ms=26.7)
    assert out["mode"] == "batcher"
    assert out["rtt_ms"] == 119.0 and out["decode_chunk_ms"] == 26.7
    assert "rule" in out
    out = choose_serving_mode(rtt_ms=10.0, decode_chunk_ms=88.0)
    assert out["mode"] == "engine"


def test_choose_serving_mode_requires_a_measurement_source():
    with pytest.raises(ValueError, match="decode_chunk_ms"):
        choose_serving_mode(rtt_ms=1.0)


def test_measurements_run_and_are_positive():
    rtt = measure_rtt_ms(reps=3)
    assert rtt >= 0.0
    cfg = LlamaConfig.tiny(vocab_size=64)
    module = Llama(cfg)
    params = module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    chunk = measure_decode_chunk_ms(
        module, params, chunk_steps=4, prompt_len=8, reps=1
    )
    assert chunk >= 0.0
    decision = choose_serving_mode(module, params, chunk_steps=4)
    assert decision["mode"] in ("engine", "batcher")
