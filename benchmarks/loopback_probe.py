"""Loopback probe: the engine's co-located claim, measured.

``serving/engine.py`` argues the continuous-batching engine beats the
full-batch micro-batcher when the host↔device round trip is small
relative to a decode chunk. This probe runs the tiny preset on the
in-process CPU backend, where the round trip truly is ~0 — the
co-located regime; its timings are CPU timings, not device results —
and measures:

1. the auto-rule decision (expected: it FLIPS to "engine");
2. closed-loop p50/p95 of engine vs batcher under staggered arrivals.

Staggered (not barrier-aligned) arrivals are the point: clients that
arrive mid-batch wait out the batcher's whole in-flight generate, while
the engine admits them at the next chunk boundary.

Prints one JSON line per result.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")  # the co-located regime
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.serve_latency import serving_config
    from unionml_tpu.models import Llama, make_lm_predictor, quantize_params
    from unionml_tpu.models.quantization import LLAMA_QUANT_PATTERNS
    from unionml_tpu.serving.auto import choose_serving_mode
    from unionml_tpu.serving.engine import DecodeEngine

    cfg0 = serving_config("tiny")
    from unionml_tpu.models import LlamaConfig

    cfg = LlamaConfig(**{**cfg0.__dict__, "quantized": True})
    module = Llama(cfg)
    fp = Llama(cfg0).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    qparams = quantize_params(fp, LLAMA_QUANT_PATTERNS)

    n_clients, reqs_per_client, prompt_len, new_tokens = 4, 6, 16, 32
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab_size, size=(n_clients, prompt_len))

    decision = choose_serving_mode(module, qparams, chunk_steps=8)
    print(json.dumps({"metric": "loopback_auto_decision", **decision}), flush=True)

    def closed_loop(predict) -> dict:
        lat = []
        lock = threading.Lock()

        def client(i):
            # staggered arrivals: offsets are where chunk-boundary joins
            # beat the batcher's full-batch barrier
            time.sleep(0.05 * i)
            for _ in range(reqs_per_client):
                t0 = time.perf_counter()
                predict([prompts[i].tolist()])
                with lock:
                    lat.append(time.perf_counter() - t0)

        threads = [
            threading.Thread(target=client, args=(i,)) for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        from unionml_tpu.serving._stats import percentile_summary

        # shared nearest-rank formula (int(0.95*n) indexed the MAXIMUM
        # for small windows — the bias _stats.percentile_summary fixes)
        s = percentile_summary([v * 1e3 for v in lat])
        return {"p50_ms": s["p50"], "p95_ms": s["p95"], "n": s["n"]}

    # --- engine ---
    engine = DecodeEngine(
        module, slots=n_clients, max_new_tokens=new_tokens,
        prompt_buckets=(prompt_len,), chunk_steps=8, pipeline_depth=2,
    )
    engine.warmup(qparams)
    closed_loop(lambda p: engine.generate(qparams, p))  # warm the path
    engine.reset_stats()
    eng = closed_loop(lambda p: engine.generate(qparams, p))
    engine.close()
    print(json.dumps({"metric": "loopback_engine_closed", **eng}), flush=True)

    # --- batcher (full-batch predictor behind a micro-batching queue) ---
    from unionml_tpu.serving.batcher import MicroBatcher

    predict = make_lm_predictor(
        module, max_new_tokens=new_tokens, bucket_lens=(prompt_len,),
    )
    predict.warmup(qparams, max_batch=n_clients)
    batcher = MicroBatcher(
        lambda feats: predict(qparams, feats), max_batch_size=n_clients,
        max_wait_ms=5.0, row_lists=True,
    )
    closed_loop(lambda p: batcher.submit(p[0]))  # warm
    bat = closed_loop(lambda p: batcher.submit(p[0]))
    batcher.close()
    print(json.dumps({"metric": "loopback_batcher_closed", **bat}), flush=True)

    print(json.dumps({
        "metric": "loopback_verdict",
        "auto_mode": decision["mode"],
        "engine_p50_ms": eng["p50_ms"],
        "batcher_p50_ms": bat["p50_ms"],
        "engine_wins_p50": eng["p50_ms"] <= bat["p50_ms"],
    }), flush=True)


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    main()
