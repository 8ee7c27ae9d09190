"""End-to-end HTTP serving p50 (the BASELINE.json north-star metric at
its true boundary: "FastAPI predictor p50 latency").

serve_latency.py times ``generate()`` directly; THIS script measures the
full request path — HTTP transport -> ServingApp -> batching layer ->
device -> response — for a single client (pure latency) and for
concurrent clients. Two batching modes:

- ``--mode batcher``: the row-list micro-batcher (full-batch generate;
  a late request waits out the whole in-flight decode),
- ``--mode engine`` (default): the continuous-batching DecodeEngine
  (requests join at chunk boundaries — the p95 fix).

Each scenario prints one JSON line; the concurrent line includes the
``/stats`` split (queue-wait vs prefill vs decode) so tail latency is
attributable.

Usage (on the TPU)::

    python benchmarks/serve_http.py [--requests 20] [--clients 8] [--mode engine|batcher]
    UNIONML_TPU_BENCH_PRESET=tiny JAX_PLATFORMS=cpu python benchmarks/serve_http.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--requests", type=int, default=20)
    parser.add_argument("--clients", type=int, default=8)
    parser.add_argument("--prompt-len", type=int, default=64)
    parser.add_argument("--new-tokens", type=int, default=32)
    parser.add_argument(
        "--mode", choices=("engine", "batcher", "auto"), default="auto",
        help="auto (default) measures host<->device RTT and one decode "
        "chunk at startup and picks the measured winner "
        "(unionml_tpu.serving.auto); the decision and its evidence land "
        "in /stats",
    )
    parser.add_argument(
        "--spec-k", type=int, default=4,
        help="speculate_k for the serve_spec preset",
    )
    parser.add_argument("--chunk-steps", type=int, default=8)
    parser.add_argument(
        "--pipeline-depth", type=int, default=None,
        help="decode chunks in flight; default scales to cover ~120 ms of "
        "round-trip with this model's chunk compute (big models need "
        "shallow pipelines or joins queue behind the chunk backlog)",
    )
    parser.add_argument(
        "--prefill-chunk", type=int, default=None,
        help="engine mode: admit buckets larger than this in chunked "
        "prefill programs so resident decodes never stall behind a long "
        "prompt (default: 512 when --prompt-len >= 4096, like the "
        "generator's long-context rule; 0 disables)",
    )
    parser.add_argument(
        "--prefill-impl", choices=("cached", "flash"), default="cached",
        help="flash = Pallas monolithic prefill for FULL prefills. "
        "Unlike serve_latency, this COMPOSES with "
        "--prefill-chunk here: bucketed serving runs flash on monolithic "
        "admissions while chunk-ruled long buckets stay chunked-cached. "
        "Ignored by the speculative presets (their module pair is built "
        "separately).",
    )
    parser.add_argument(
        "--checkpoint", default=None,
        help="HF safetensors checkpoint directory — serve REAL weights, "
        "streamed to int8 on load (models/convert.py); geometry comes "
        "from its config.json and overrides the preset's",
    )
    parser.add_argument(
        "--open-rate", type=float, default=0.0,
        help="also run an open-loop scenario: Poisson arrivals at this "
        "rate (req/s) — the workload where step-boundary joins beat the "
        "full-batch barrier. 0 skips it.",
    )
    args = parser.parse_args()

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu import Dataset, Model
    from unionml_tpu.models import (
        LLAMA_QUANT_PATTERNS,
        Llama,
        LlamaConfig,
        make_lm_predictor,
        quantize_params,
    )
    from unionml_tpu.serving.http import ServingApp
    from benchmarks.serve_latency import serving_config

    preset = os.environ.get(
        "UNIONML_TPU_BENCH_PRESET",
        "tiny" if jax.default_backend() == "cpu" else "serve_1p5b",
    )
    if preset == "tiny":
        args.requests = min(args.requests, 3)
    spec_predict = None
    spec_modules = None
    if preset in ("serve_spec", "tiny_spec"):
        if args.checkpoint:
            # silently serving random weights while reporting them as
            # the checkpoint's numbers would poison the record
            raise SystemExit(
                "--checkpoint is not supported with the speculative "
                "presets (they build a synthetic target/draft pair)"
            )
        # speculative decoding at the HTTP boundary: target + draft pair
        # behind make_speculative_predictor (batcher mode) or the
        # speculative DecodeEngine (engine mode — per-slot draft rounds
        # with one shared verify, round-5)
        from unionml_tpu.models import make_speculative_predictor

        if preset == "tiny_spec":
            t_cfg = LlamaConfig.tiny(vocab_size=512)
            d_cfg = LlamaConfig.tiny(
                vocab_size=512, hidden_dim=32, num_layers=1, num_heads=2,
                num_kv_heads=1, mlp_dim=64,
            )
            t_module, d_module = Llama(t_cfg), Llama(d_cfg)
            toks = jnp.zeros((1, 8), jnp.int32)
            qparams = {
                "target": t_module.init(jax.random.PRNGKey(0), toks)["params"],
                "draft": d_module.init(jax.random.PRNGKey(1), toks)["params"],
            }
            args.requests = min(args.requests, 3)
        else:
            from benchmarks.serve_latency import random_quantized_params

            t_cfg = LlamaConfig(
                **{**serving_config("serve_8b").__dict__, "quantized": True}
            )
            d_cfg = LlamaConfig(
                **{**serving_config("serve_1p5b").__dict__, "quantized": True}
            )
            t_module, d_module = Llama(t_cfg), Llama(d_cfg)
            qparams = {
                "target": random_quantized_params(t_module),
                "draft": random_quantized_params(d_module),
            }
        qcfg = t_cfg
        if args.mode == "engine":
            # the speculative ENGINE: constructed below in the unified
            # engine block, where --pipeline-depth/--prefill-chunk/
            # --chunk-steps are resolved (round-5)
            spec_modules = (t_module, d_module)
        else:
            spec_predict = make_speculative_predictor(
                t_module, d_module, max_new_tokens=args.new_tokens,
                bucket_lens=(args.prompt_len,), speculate_k=args.spec_k,
            )
            if args.mode != "batcher":
                print(json.dumps({
                    "metric": "serving_mode_auto", "mode": "batcher",
                    "rule": "speculative predictor defaults to the "
                            "micro-batcher; pass --mode engine for the "
                            "speculative engine",
                }))
                args.mode = "batcher"

    if spec_predict is not None or spec_modules is not None:
        cfg = None      # the spec path holds its own module pair;
        qmodule = None  # the per-preset serving config never applies
    elif (cfg := serving_config(preset)) and args.checkpoint:
        if getattr(cfg, "weight_bits", 8) == 4:
            raise SystemExit(
                "--checkpoint streams to int8; the serve_8b_w4 preset "
                "would mislabel an int8 run — use serve_8b with "
                "--checkpoint, or the w4 preset without it"
            )
        # REAL weights: geometry from the checkpoint's config.json,
        # serving knobs (cache size, kv_quant, attention impl) from the
        # preset; kernels stream to int8 on load without an fp tree ever
        # materializing (models/convert.py)
        from unionml_tpu.models import load_llama_checkpoint

        qparams, qcfg = load_llama_checkpoint(
            args.checkpoint, quantize=True, quantized=True,
            max_len=cfg.max_len, kv_quant=cfg.kv_quant,
            attn_impl=cfg.attn_impl,
        )
        if args.prefill_impl != "cached":
            import dataclasses

            qcfg = dataclasses.replace(qcfg, prefill_impl=args.prefill_impl)
        qmodule = Llama(qcfg)
    else:
        qcfg = LlamaConfig(**{
            **cfg.__dict__, "quantized": True,
            "prefill_impl": args.prefill_impl,
        })
        qmodule = Llama(qcfg)
        if preset.startswith("serve_8b"):
            # synthetic quantized weights: an 8B master tree can't be
            # materialized on-chip to quantize from (see
            # serve_latency.random_quantized_params); serve_8b_w4 runs
            # the packed-int4 decode kernel
            from benchmarks.serve_latency import random_quantized_params

            qparams = random_quantized_params(qmodule)
        else:
            # int8 artifact, exactly the serve_latency production path
            fp_params = jax.jit(Llama(cfg).init)(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
            )["params"]
            qparams = quantize_params(fp_params, LLAMA_QUANT_PATTERNS)

    dataset = Dataset(name="http_bench_data", targets=[])

    @dataset.reader
    def reader() -> list:
        return []

    model = Model(name="http_bench_lm", init=lambda: qparams, dataset=dataset)

    @model.trainer
    def trainer(params: dict, features: list) -> dict:
        return params

    mode_decision = None
    if args.mode == "auto":
        # encode the crossover rule instead of making the operator
        # choose blind: engine iff one decode chunk
        # costs at least one host<->device round trip
        from unionml_tpu.serving.auto import choose_serving_mode

        mode_decision = choose_serving_mode(
            qmodule, qparams, chunk_steps=args.chunk_steps
        )
        args.mode = mode_decision["mode"]
        print(json.dumps({"metric": "serving_mode_auto", **mode_decision}))

    if args.mode == "engine":
        from unionml_tpu.serving.engine import DecodeEngine

        depth = args.pipeline_depth
        if depth is None:
            # cover one ~120 ms RTT of backlog, no more: deeper pipelines
            # make joining prefills queue behind the whole chunk backlog.
            # Keyed on actual geometry, not the preset name: --checkpoint
            # can swap in an 8B-class model under any preset
            per_step_ms = 11.0 if qcfg.hidden_dim >= 4096 else 3.3
            depth = max(2, int(round(120.0 / (args.chunk_steps * per_step_ms))))
        prefill_chunk = args.prefill_chunk
        if prefill_chunk is None:
            # auto only when the bucket divides evenly — an explicit flag
            # still surfaces DecodeEngine's divisibility error
            prefill_chunk = (
                512 if args.prompt_len >= 4096 and args.prompt_len % 512 == 0
                else 0
            )
        common = dict(
            slots=args.clients, max_new_tokens=args.new_tokens,
            prompt_buckets=(args.prompt_len,), pipeline_depth=depth,
            prefill_chunk=prefill_chunk or None,
        )
        if spec_modules is not None:
            # the speculative engine: same flag wiring as the plain
            # engine (chunked admission composes with speculation);
            # chunk_steps counts ROUNDS here, so scale the decode-steps
            # flag down by the tokens a round can emit
            t_mod, d_mod = spec_modules
            engine = DecodeEngine(
                t_mod, draft_module=d_mod, speculate_k=args.spec_k,
                chunk_steps=max(1, round(args.chunk_steps / (args.spec_k + 1))),
                **common,
            )
        else:
            engine = DecodeEngine(
                qmodule, chunk_steps=args.chunk_steps, **common,
            )

        @model.predictor
        def predictor(params: dict, prompts: list) -> list:
            return engine.generate(params, prompts)

        serving_kwargs = dict(
            warmup=lambda params: engine.warmup(params), stats=engine.stats,
            # SSE token streaming (POST /predict/stream): TTFT ~ queue +
            # prefill instead of the whole generation
            stream=lambda params, prompts: engine.generate_stream(
                params, prompts[0]
            ),
        )
    else:
        if spec_predict is not None:
            predict = spec_predict
        else:
            predict = make_lm_predictor(
                qmodule, max_new_tokens=args.new_tokens,
                bucket_lens=(args.prompt_len,),
            )

        @model.predictor
        def predictor(params: dict, prompts: list) -> list:
            return predict(params, prompts)

        serving_kwargs = dict(
            batch=True, row_lists=True, max_wait_ms=3.0,
            # never coalesce beyond the warmed shapes: an open-loop burst
            # can queue more than `clients` rows, and an unwarmed bucket
            # stalls the batch behind a ~20-40 s XLA compile
            max_batch_size=args.clients,
            # pre-compile every (bucket, batch-power) executable: without
            # this, first-hit shapes stall live requests behind ~20 s XLA
            # compiles (measured 17.9 s p95 under 8 concurrent clients)
            warmup=lambda params: predict.warmup(params, max_batch=args.clients),
        )

    from unionml_tpu.model import ModelArtifact

    model.artifact = ModelArtifact(qparams, {}, {})

    if mode_decision is not None:
        # /stats records the auto decision and its evidence
        serving_kwargs["extra_stats"] = {"mode_decision": mode_decision}
    serving = ServingApp(model, **serving_kwargs)
    host, port = serving.serve(port=0, blocking=False)

    rng = np.random.default_rng(0)
    prompt = rng.integers(1, qcfg.vocab_size, size=(args.prompt_len,)).tolist()
    body = json.dumps({"features": [prompt]}).encode()

    def request() -> float:
        req = urllib.request.Request(
            f"http://{host}:{port}/predict", data=body,
            headers={"Content-Type": "application/json"},
        )
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=300) as resp:
            out = json.loads(resp.read())
        assert isinstance(out, list) and len(out[0]) == args.new_tokens
        return (time.perf_counter() - t0) * 1e3

    request()  # warmup/compile

    from unionml_tpu.serving._stats import percentile_summary

    def reset_stats():
        # each scenario's /stats must describe only that scenario, not
        # dilute its queue-wait/occupancy with warmup or earlier phases
        if args.mode == "engine":
            engine.reset_stats()
        else:
            serving.reset_stats()

    def fetch_stats() -> dict:
        with urllib.request.urlopen(
            f"http://{host}:{port}/stats", timeout=30
        ) as resp:
            stats = json.loads(resp.read())
        return {
            k: stats[k]
            for k in ("queue_wait_ms", "prefill_ms", "decode_ms",
                      "ttft_ms", "device_ms", "slot_occupancy",
                      "mode_decision")
            if k in stats
        }

    # single client: pure request latency
    lat = [request() for _ in range(args.requests)]
    s = percentile_summary(lat)
    print(json.dumps({
        "metric": f"{preset}_http_p50_ms", "mode": args.mode, "clients": 1,
        "prefill_impl": args.prefill_impl, "prefill_chunk": args.prefill_chunk,
        "value": s["p50"], "p95_ms": s["p95"], "unit": "ms",
    }))
    reset_stats()

    if args.mode == "engine":
        # streaming: time-to-first-token at the HTTP boundary (the UX
        # metric SSE exists for) vs the same request's full duration
        import http.client

        def stream_request():
            conn = http.client.HTTPConnection(host, port, timeout=300)
            t0 = time.perf_counter()
            conn.request(
                "POST", "/predict/stream", body=json.dumps({"features": prompt}),
                headers={"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            assert resp.status == 200, resp.read()
            ttft = None
            n_tokens = 0
            buf = b""
            while True:
                chunk = resp.read1(65536)
                if not chunk:
                    break
                buf += chunk
                while b"\n\n" in buf:
                    event, buf = buf.split(b"\n\n", 1)
                    if not event.startswith(b"data: "):
                        continue
                    data = json.loads(event[len(b"data: "):])
                    if "tokens" in data:
                        if ttft is None:
                            ttft = (time.perf_counter() - t0) * 1e3
                        n_tokens += len(data["tokens"])
                    elif data.get("done"):
                        assert data["n_tokens"] == n_tokens == args.new_tokens
            conn.close()
            return ttft, (time.perf_counter() - t0) * 1e3

        stream_request()  # warm the path
        reset_stats()
        pairs = [stream_request() for _ in range(args.requests)]
        ttft_s = percentile_summary([p[0] for p in pairs])
        full_s = percentile_summary([p[1] for p in pairs])
        print(json.dumps({
            "metric": f"{preset}_http_ttft_ms", "mode": "engine-stream",
            "clients": 1, "value": ttft_s["p50"], "p95_ms": ttft_s["p95"],
            "full_response_p50_ms": full_s["p50"], "unit": "ms",
            "stats": fetch_stats(),
        }))
        reset_stats()

    # concurrent clients: the micro-batcher coalesces in-flight requests
    all_lat: list = []
    lock = threading.Lock()

    def client():
        mine = [request() for _ in range(args.requests)]
        with lock:
            all_lat.extend(mine)

    threads = [threading.Thread(target=client) for _ in range(args.clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    s = percentile_summary(all_lat)
    n = args.clients * args.requests
    print(json.dumps({
        "metric": f"{preset}_http_p50_ms", "mode": args.mode,
        "clients": args.clients,
        "prefill_impl": args.prefill_impl, "prefill_chunk": args.prefill_chunk,
        "value": s["p50"], "p95_ms": s["p95"],
        "requests_per_sec": round(n / wall, 2),
        "tokens_per_sec": round(n * args.new_tokens / wall, 1),
        "unit": "ms",
        "stats": fetch_stats(),
    }))
    if args.open_rate > 0:
        # open loop: arrivals are scheduled, not gated on completions —
        # a late arrival during an in-flight decode exposes the batcher's
        # full-batch barrier (it waits the whole generation out) vs the
        # engine's chunk-boundary join
        reset_stats()
        n_open = args.clients * args.requests
        gaps = np.random.default_rng(1).exponential(1.0 / args.open_rate, n_open)
        arrivals = np.cumsum(gaps)
        open_lat: list = []

        def timed_request(delay: float):
            time.sleep(max(0.0, delay))
            open_lat.append(request())

        start = time.perf_counter()
        threads = [
            threading.Thread(target=timed_request, args=(a - (time.perf_counter() - start),))
            for a in arrivals
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - start
        s = percentile_summary(open_lat)
        print(json.dumps({
            "metric": f"{preset}_http_open_p50_ms", "mode": args.mode,
            "offered_rps": args.open_rate,
            "value": s["p50"], "p95_ms": s["p95"],
            "requests_per_sec": round(n_open / wall, 2), "unit": "ms",
            "stats": fetch_stats(),
        }))
    serving.shutdown()
    if args.mode == "engine":
        engine.close()


if __name__ == "__main__":
    main()
