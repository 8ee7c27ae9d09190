"""Serving-latency benchmark: Llama generation p50/p95.

Jitted prefill + scan decode
via :func:`unionml_tpu.models.make_generator` on a ~1.5B-param Llama-3
geometry (the largest that fits one v5e chip in bf16; the 8B config
needs the tensor-parallel path). Prints one JSON line per
(quantized, batch) combination.

Usage::

    python benchmarks/serve_latency.py [--batches 1 8] [--trials 20]
    UNIONML_TPU_BENCH_PRESET=tiny python benchmarks/serve_latency.py  # CPU smoke
    UNIONML_TPU_BENCH_PRESET=serve_prefix_cache python benchmarks/serve_latency.py
    # ^ automatic prefix KV-cache: shared-prefix stream, cache on vs off
    UNIONML_TPU_BENCH_PRESET=serve_overload python benchmarks/serve_latency.py
    # ^ admission control under saturation: shed rate + accepted p99 on
    #   an over-admitted stream, and recovery time after an injected
    #   device fault (docs/robustness.md)
    UNIONML_TPU_BENCH_PRESET=serve_introspection python benchmarks/serve_latency.py
    # ^ program introspection: instrumentation-on vs -off wall delta
    #   with token parity asserted, plus the decode program's measured
    #   flops / recompiles / MFU (docs/observability.md)
    UNIONML_TPU_BENCH_PRESET=serve_tracing python benchmarks/serve_latency.py
    # ^ distributed tracing: W3C traceparent propagation + OTLP export
    #   (against the in-process collector stub) on vs off — token
    #   parity asserted, per-request p50/p99 overhead delta reported
    #   (docs/observability.md "Distributed tracing & SLOs")
    UNIONML_TPU_BENCH_PRESET=serve_paged python benchmarks/serve_latency.py
    # ^ paged KV attention: contiguous vs block-paged device cache at a
    #   FIXED HBM byte budget under a long-tail prompt mix — effective
    #   max batch ratio (target >= 1.5x), decode tokens/s at equal
    #   batch, token parity asserted (docs/performance.md)
    UNIONML_TPU_BENCH_PRESET=serve_usage python benchmarks/serve_latency.py
    # ^ per-tenant usage metering: attribution identity (per-tenant
    #   attributed device-seconds + tokens explain >= 95% of engine
    #   totals under a mixed 3-tenant stream), exported tenant-label
    #   cardinality <= top_k + 1 under a 40-distinct-tenant burst, and
    #   ledger-on vs -off p99 overhead <= 2% at token parity
    #   (docs/observability.md "Usage metering & cost attribution")
    UNIONML_TPU_BENCH_PRESET=serve_preempt python benchmarks/serve_latency.py
    # ^ preemptive priority scheduling: a low-priority bulk tenant
    #   floods the paged KV pool while a high-priority tenant streams
    #   — asserts premium p99 holds within 1.5x of its unloaded
    #   baseline, preempted streams resume with exact token parity,
    #   and zero caller-visible failures (docs/robustness.md
    #   "Preemption & fairness")
    UNIONML_TPU_BENCH_PRESET=serve_router python benchmarks/serve_latency.py
    # ^ fleet router (cluster front door): 3 engine replicas under a
    #   concurrent stream with a mid-run replica KILL (OOM-shaped
    #   device fault) plus a drain→rejoin cycle — asserts ZERO
    #   caller-visible failures with per-request token parity, retry
    #   amplification within the fleet retry budget; then a 1-replica
    #   passthrough leg asserting <= 2% p99 overhead vs the direct
    #   engine (docs/robustness.md "Fleet robustness")
    UNIONML_TPU_BENCH_PRESET=serve_autoscale python benchmarks/serve_latency.py
    # ^ SLO-driven autoscaling (the self-operating fleet): a
    #   burn-inducing flood on a 2-replica fleet triggers a scale-out
    #   within the SLO fast window, warm-joined from a donor's hot
    #   prefix blocks (>= 1 warm hit on the joiner's first request
    #   asserted); a mid-run replica kill is reaped and replaced
    #   automatically; the load drop scales the fleet back to
    #   baseline — zero caller-visible failures and exact token
    #   parity vs the solo oracle throughout (docs/robustness.md
    #   "Autoscaling & self-healing")
    UNIONML_TPU_BENCH_PRESET=serve_disagg python benchmarks/serve_latency.py
    # ^ disaggregated prefill/decode serving: colocated vs phase-split
    #   fleets of identical size under mixed long/short-prompt traffic
    #   — asserts the disaggregated short-prompt TTFT p99 beats
    #   colocated with decode tokens/s no worse, all completions
    #   bit-identical to the colocated solo oracle, 0 caller-visible
    #   failures; then a chaos leg killing the prefill replica
    #   mid-handoff with lease/pool refcounts back to baseline
    #   (docs/serving.md "Disaggregated serving")
    UNIONML_TPU_BENCH_PRESET=serve_fleet_obs python benchmarks/serve_latency.py
    # ^ fleet observability plane: a 3-replica fleet under load with
    #   cross-hop trace stitching ON and a concurrent federated
    #   /metrics scraper — zero caller-visible failures, exact token
    #   parity, every replica labeled in the one-scrape body, the
    #   probe request's stitched timeline complete; then per-request
    #   paired plane-on/off legs asserting <= 2% p99 overhead at
    #   bit-identical tokens (docs/observability.md "Fleet
    #   observability")
    UNIONML_TPU_BENCH_PRESET=serve_perf python benchmarks/serve_latency.py
    # ^ serving goodput plane: a single-replica router fleet under
    #   load with the plane ON — zero caller-visible failures, exact
    #   token parity, fleet-merged /debug/goodput sane and the
    #   per-token ITL histogram populated; then per-request paired
    #   plane-on/off legs on the SAME engine (the engine.perf setter
    #   seam) asserting <= 1% pooled-p99 overhead at bit-identical
    #   tokens, with one tail probe per sweep resolved /debug/tail →
    #   /debug/trace (docs/observability.md "Serving goodput & tail
    #   attribution")
    UNIONML_TPU_BENCH_PRESET=serve_rollout python benchmarks/serve_latency.py
    # ^ zero-downtime model lifecycle: a 2-engine fleet under flood
    #   has a bad version rolled forward and auto-rolled back on its
    #   shadow parity regression, then a clean version baked and
    #   promoted through rolling drain/bind/rejoin — per sweep, three
    #   sweeps; 0 caller-visible failures, exact token parity on the
    #   live path, lifecycle-churn TTFT p99 within 2x of the
    #   steady-state baseline measured by the same min-over-rounds /
    #   unrounded-nearest-rank / median-of-three estimator
    #   (docs/robustness.md "Rollouts & rollback")
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def serving_config(preset: str):
    from unionml_tpu.models import LlamaConfig

    if preset == "tiny":
        return LlamaConfig.tiny(vocab_size=256)
    if preset == "serve_8b":
        # the BASELINE.json config #5 model: full Llama-3-8B geometry.
        # bf16 (16 GB) exceeds one v5e chip's HBM; int8 weights (~8.6 GB)
        # fit with room for bucketed KV caches -> int8-only legs.
        return LlamaConfig.llama3_8b()
    if preset == "serve_1p5b_w4":
        # packed-int4 at the 1.5B scale: the second confirmation point
        # for the ops/int4_matmul.py decode kernel
        base = serving_config("serve_1p5b")
        return LlamaConfig(**{**base.__dict__, "weight_bits": 4})
    if preset == "serve_8b_w4":
        # packed-int4 weights (~4.3 GB): the ops/int4_matmul.py Pallas
        # decode path — halves the weight traffic that bounds 8B decode
        return LlamaConfig(**{
            **LlamaConfig.llama3_8b().__dict__, "weight_bits": 4,
        })
    if preset == "serve_moe":
        # ~1.1B-total-param 8-expert top-2 MoE (~0.4B active per token)
        return LlamaConfig(
            vocab_size=128_256, hidden_dim=1024, num_layers=12, num_heads=16,
            num_kv_heads=8, mlp_dim=2816, max_len=2048,
            num_experts=8, num_selected=2,
        )
    # ~1.5B params: Llama-3 geometry scaled to one v5e chip (bf16 ~3 GB)
    return LlamaConfig(
        vocab_size=128_256, hidden_dim=2048, num_layers=20, num_heads=16,
        num_kv_heads=8, mlp_dim=5632, max_len=2048,
    )


def random_quantized_params(qmodule, seed: int = 0):
    """Synthetic weights with the quantized module's exact tree/dtypes.

    The 8B bf16 master tree (16 GB) cannot be materialized on one v5e
    chip to run ``quantize_params`` over, and decode latency is
    weight-VALUE-independent (HBM traffic + MXU work depend only on
    shapes/dtypes — TPUs have no denormal slow paths), so the 8B bench
    fills each leaf directly on device: random int8 kernels, lecun-scaled
    fp32 scales, N(0, 0.02) embeddings, ones for norm gains. Leaves are
    created one at a time — peak transient memory is one leaf's int32
    sample buffer, never a second full tree.
    """
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(
        qmodule.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    # leaf-name -> sibling-names map: a "scale" leaf is quant metadata only
    # next to its int8 kernel (RMSNorm gains are ALSO named "scale" and
    # must get ones, not the tiny dequant constant)
    sibling_names = {}
    for path, _ in flat:
        parent = tuple(p.key if hasattr(p, "key") else str(p) for p in path[:-1])
        sibling_names.setdefault(parent, set()).add(
            path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        )

    @partial(jax.jit, static_argnums=(1,))
    def int8_leaf(key, shape):
        return jax.random.randint(key, shape, -127, 128, jnp.int32).astype(jnp.int8)

    @partial(jax.jit, static_argnums=(1, 2))
    def embed_leaf(key, shape, dtype):
        return (0.02 * jax.random.normal(key, shape)).astype(dtype)

    key = jax.random.PRNGKey(seed)
    leaves = []
    for path, s in flat:
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        parent = tuple(p.key if hasattr(p, "key") else str(p) for p in path[:-1])
        siblings = sibling_names[parent]
        is_quant_scale = (
            name in ("scale", "scale_g")
            and ("kernel_q" in siblings or "kernel_p" in siblings)
        ) or (
            name.endswith("_scale") and f"{name[: -len('_scale')]}_q" in siblings
        )
        key, sub = jax.random.split(key)
        if s.dtype == jnp.int8:
            leaves.append(int8_leaf(sub, s.shape))
        elif is_quant_scale:
            # uniform int8 in [-127,127] has std ~73; scale so the
            # effective weight std lands near lecun 1/sqrt(K)
            k_in = qmodule.config.hidden_dim
            leaves.append(
                jnp.full(s.shape, 1.0 / (73.0 * math.sqrt(k_in)), jnp.float32)
            )
        elif name == "embedding":
            leaves.append(embed_leaf(sub, s.shape, s.dtype))
        else:
            leaves.append(jnp.ones(s.shape, s.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--batches", type=int, nargs="+", default=[1, 8])
    parser.add_argument("--trials", type=int, default=20)
    parser.add_argument("--prompt-len", type=int, default=64)
    parser.add_argument("--new-tokens", type=int, default=32)
    parser.add_argument(
        "--prefill-impl", choices=("cached", "flash"), default="cached",
        help="flash = Pallas monolithic prefill (the long-prompt lever)",
    )
    parser.add_argument(
        "--prefill-chunk", type=int, default=None,
        help="chunked cached prefill (bounds the [B,H,chunk,max_len] "
        "score buffer; the pre-flash long-prompt path and the flash A/B "
        "baseline)",
    )
    parser.add_argument(
        "--kv-quant", action="store_true",
        help="int8 KV cache (composes with either prefill impl)",
    )
    args = parser.parse_args()

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.models import (
        LLAMA_QUANT_PATTERNS,
        LlamaConfig,
        Llama,
        make_generator,
        quantize_params,
        serving_params,
    )

    backend = jax.default_backend()
    preset = os.environ.get(
        "UNIONML_TPU_BENCH_PRESET", "tiny" if backend == "cpu" else "serve_1p5b"
    )
    if preset == "tiny":
        args.trials = min(args.trials, 3)
    if args.prefill_impl == "flash" and args.prefill_chunk:
        # chunking makes the tail call partial, so generate() never takes
        # the flash path — measuring this silently would record a chunked
        # number as a flash datapoint
        parser.error("--prefill-impl flash is mutually exclusive with "
                     "--prefill-chunk (a chunked prefill is never a full "
                     "prefill; see docs/serving.md)")
    cfg = serving_config(preset)
    overrides = {}
    if args.prefill_impl != "cached":
        overrides["prefill_impl"] = args.prefill_impl
    if args.kv_quant:
        overrides["kv_quant"] = True
    if overrides:
        import dataclasses

        cfg = dataclasses.replace(cfg, **overrides)
    rng = np.random.default_rng(0)

    if preset.startswith("serve_8b"):
        # bf16 8B exceeds single-chip HBM: quantized-only, synthetic weights
        legs = (True,)
        module, params, fp_params = None, None, None
    elif preset.endswith("_w4"):
        # w4 presets measure the quantized leg only (the fp leg is the
        # base preset's, already recorded)
        legs = (True,)
        module, params = None, None
        tokens0 = jnp.zeros((1, 8), jnp.int32)
        fp_params = jax.jit(Llama(cfg).init)(jax.random.PRNGKey(0), tokens0)["params"]
    else:
        legs = (False, True)
        module = Llama(cfg)
        tokens0 = jnp.zeros((1, 8), jnp.int32)
        fp_params = jax.jit(module.init)(jax.random.PRNGKey(0), tokens0)["params"]
        # serving residency: one-time bf16 cast (decode re-reads weights per token)
        params = serving_params(fp_params)

    for quantized in legs:
        if quantized:
            qcfg = LlamaConfig(**{**cfg.__dict__, "quantized": True})
            qmodule = Llama(qcfg)
            if preset.startswith("serve_8b"):
                qparams = random_quantized_params(qmodule)
            else:
                # quantize from the fp32 masters (the production path), not
                # the bf16 serving copy: scales from bf16 weights double-round
                qparams = quantize_params(
                    fp_params, LLAMA_QUANT_PATTERNS,
                    bits=getattr(cfg, "weight_bits", 8),
                )
            run_module, run_params = qmodule, qparams
        else:
            run_module, run_params = module, params
        # cache sized to the request (make_lm_predictor does this per bucket)
        generate = make_generator(
            run_module, max_new_tokens=args.new_tokens,
            max_len=args.prompt_len + args.new_tokens,
            prefill_chunk=args.prefill_chunk,
        )
        for batch in args.batches:
            prompt = jnp.asarray(
                rng.integers(1, cfg.vocab_size, size=(batch, args.prompt_len)),
                jnp.int32,
            )
            # warmup/compile
            out = generate(run_params, prompt)
            _ = np.asarray(out)
            lat = []
            for _ in range(args.trials):
                t0 = time.perf_counter()
                out = generate(run_params, prompt)
                _ = np.asarray(out)  # host readback = end of request
                lat.append((time.perf_counter() - t0) * 1e3)
            from unionml_tpu.serving._stats import percentile_summary

            s = percentile_summary(lat)  # shared nearest-rank formula
            p50, p95 = s["p50"], s["p95"]
            toks = batch * args.new_tokens / (p50 / 1e3)
            print(json.dumps({
                "metric": f"{preset}_generate_p50_ms",
                "quantized": quantized,
                "batch": batch,
                "prompt_len": args.prompt_len,
                "new_tokens": args.new_tokens,
                "prefill_impl": args.prefill_impl,
                "prefill_chunk": args.prefill_chunk,
                "kv_quant": bool(cfg.kv_quant),
                "value": round(p50, 1),
                "p95_ms": round(p95, 1),
                "tokens_per_sec": round(toks, 1),
                "unit": "ms",
            }))


def kv_cache_legs() -> None:
    """Long-context decode: bf16 vs int8 KV cache
    (``UNIONML_TPU_BENCH_KV=1``, composes with the preset env var).

    Decode streams weights AND the filled cache every step; at serving's
    short prompts the cache is noise next to the weights, but at long
    prompts it rivals them (1.5B int8 weights ~1.5 GB vs ~0.75 GB bf16
    cache at batch 8 x 1152 ctx). ``kv_quant`` halves the cache bytes —
    both the per-step HBM traffic share and the resident footprint that
    caps engine slot counts.
    """
    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.models import LlamaConfig, Llama, make_generator

    backend = jax.default_backend()
    preset = os.environ.get(
        "UNIONML_TPU_BENCH_PRESET", "tiny" if backend == "cpu" else "serve_1p5b"
    )
    cfg = serving_config(preset)
    trials = 3 if preset == "tiny" else 20
    if preset == "tiny":
        prompt_len, new_tokens, batch = 16, 4, 2
    elif preset == "serve_8b":
        # the capability-unlock config: 8B x 8k context x batch 8. The
        # bf16 cache alone is 32L x 2 x 8 x 8192 x 8 x 128 x 2B = 8.6 GB
        # — plus the 8.6 GB int8 weights it EXCEEDS one v5e's HBM (the
        # bf16 leg is expected to OOM and is reported as such); the int8
        # cache (4.4 GB) fits with ~3 GB to spare.
        prompt_len, new_tokens, batch, trials = 8064, 128, 8, 5
    else:
        prompt_len, new_tokens, batch = 1024, 128, 8
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(1, cfg.vocab_size, size=(batch, prompt_len)), jnp.int32
    )
    base = LlamaConfig(**{**cfg.__dict__, "quantized": True})
    # params are identical for both legs (kv_quant changes only the cache)
    # — build ONE tree; a per-leg copy would transiently double-hold the
    # weights (17 GB at the 8B preset on a 16 GB chip)
    qparams = random_quantized_params(Llama(base))
    for kv_quant in (False, True):
        qcfg = LlamaConfig(**{**base.__dict__, "kv_quant": kv_quant})
        qmodule = Llama(qcfg)
        generate = make_generator(
            qmodule, max_new_tokens=new_tokens,
            max_len=prompt_len + new_tokens,
            # 8k prefill needs both long-context knobs: chunked prefill
            # bounds the [B, H, chunk, total] score buffer (~1 GB at 128)
            # and the last-position-only head avoids [B, S, vocab] logits
            prefill_chunk=128 if prompt_len >= 4096 else None,
        )
        cache_mb = (
            cfg.num_layers * 2 * batch * (prompt_len + new_tokens)
            * cfg.num_kv_heads * cfg.head_dim
            * ((1 + 4 / cfg.head_dim) if kv_quant else 2) / 1e6
        )
        metric = f"{preset}_longctx_kv_{'int8' if kv_quant else 'bf16'}_p50_ms"
        try:
            _ = np.asarray(generate(qparams, prompt))  # compile
        except jax.errors.JaxRuntimeError as e:
            # only genuine memory exhaustion is the expected "bf16 cache
            # doesn't fit" datapoint; anything else is a regression and
            # must fail the run, not masquerade as the OOM result
            if not any(
                marker in str(e)
                for marker in ("Ran out of memory", "RESOURCE_EXHAUSTED",
                               "Exceeded hbm capacity")
            ):
                raise
            print(json.dumps({
                "metric": metric,
                "batch": batch, "prompt_len": prompt_len,
                "new_tokens": new_tokens, "cache_mb": round(cache_mb, 1),
                "value": None, "oom": True,
                "error": f"{type(e).__name__}: {str(e)[:160]}",
                "unit": "ms",
            }))
            continue
        lat = []
        for _ in range(trials):
            t0 = time.perf_counter()
            _ = np.asarray(generate(qparams, prompt))
            lat.append((time.perf_counter() - t0) * 1e3)
        lat.sort()
        p50 = lat[len(lat) // 2]
        print(json.dumps({
            "metric": metric,
            "batch": batch, "prompt_len": prompt_len, "new_tokens": new_tokens,
            "cache_mb": round(cache_mb, 1),
            "value": round(p50, 1),
            "tokens_per_sec": round(batch * new_tokens / (p50 / 1e3), 1),
            "unit": "ms",
        }))


def prefix_cache_legs() -> None:
    """Shared-prefix (system prompt) serving: cached vs naive
    (``UNIONML_TPU_BENCH_PREFIX=1``, composes with the preset env var).

    Per-request prefill work is proportional to prompt length; a system
    prompt shared by every request multiplies it for no information
    gain. ``make_lm_predictor(system_prefix=...)`` prefills the prefix
    once per weights and broadcasts its KV rows, so requests pay only
    their own suffix.
    """
    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from unionml_tpu.models import Llama, LlamaConfig, make_lm_predictor

    backend = jax.default_backend()
    preset = os.environ.get(
        "UNIONML_TPU_BENCH_PRESET", "tiny" if backend == "cpu" else "serve_1p5b"
    )
    cfg = serving_config(preset)
    trials = 3 if preset == "tiny" else 20
    prefix_len, prompt_len, new_tokens, batch = (
        (8, 4, 4, 2) if preset == "tiny" else (512, 64, 32, 8)
    )
    rng = np.random.default_rng(0)
    prefix = rng.integers(1, cfg.vocab_size, prefix_len).tolist()
    prompts = [
        rng.integers(1, cfg.vocab_size, prompt_len).tolist() for _ in range(batch)
    ]
    base = LlamaConfig(**{**cfg.__dict__, "quantized": True})
    qmodule = Llama(base)
    qparams = random_quantized_params(qmodule)

    for cached in (False, True):
        if cached:
            pred = make_lm_predictor(
                qmodule, max_new_tokens=new_tokens,
                bucket_lens=(prompt_len,), max_len=cfg.max_len,
                system_prefix=prefix,
            )
            reqs = prompts
        else:
            pred = make_lm_predictor(
                qmodule, max_new_tokens=new_tokens,
                bucket_lens=(prefix_len + prompt_len,), max_len=cfg.max_len,
            )
            reqs = [prefix + p for p in prompts]
        pred(qparams, reqs)  # compile (+ prefix prefill when cached)
        lat = []
        for _ in range(trials):
            t0 = time.perf_counter()
            pred(qparams, reqs)
            lat.append((time.perf_counter() - t0) * 1e3)
        lat.sort()
        p50 = lat[len(lat) // 2]
        print(json.dumps({
            "metric": f"{preset}_prefix_{'cached' if cached else 'naive'}_p50_ms",
            "batch": batch, "prefix_len": prefix_len, "prompt_len": prompt_len,
            "new_tokens": new_tokens,
            "value": round(p50, 1),
            "unit": "ms",
        }))


def prefix_cache_engine_leg() -> None:
    """Automatic prefix KV-cache under a shared-prefix request stream
    (``UNIONML_TPU_BENCH_PRESET=serve_prefix_cache``).

    The workload RadixAttention/vLLM prefix caching exist for: a stream
    of prompts where 75% share one long system-prompt-style prefix
    (64 prompts x 512 shared tokens on an accelerator; a scaled-down
    16 x 32 smoke on CPU). Runs the SAME stream through a DecodeEngine
    with the cache off and on, asserts the produced tokens are
    bit-identical, and reports hit rate, prefill-tokens-saved, and the
    TTFT delta — the prefill work the cache deleted, as a latency
    number.
    """
    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.models import Llama, LlamaConfig
    from unionml_tpu.serving.engine import DecodeEngine
    from unionml_tpu.serving.prefix_cache import RadixPrefixCache

    backend = jax.default_backend()
    if backend == "cpu":
        cfg = serving_config("tiny")
        module = Llama(cfg)
        tokens0 = jnp.zeros((1, 8), jnp.int32)
        params = jax.jit(module.init)(jax.random.PRNGKey(0), tokens0)["params"]
        n_req, prefix_len, suffix_len, new_tokens = 16, 32, 8, 8
        bucket, slots, chunk_steps = 48, 4, 4
    else:
        cfg = serving_config("serve_1p5b")
        qcfg = LlamaConfig(**{**cfg.__dict__, "quantized": True})
        module = Llama(qcfg)
        params = random_quantized_params(module)
        n_req, prefix_len, suffix_len, new_tokens = 64, 512, 64, 32
        bucket, slots, chunk_steps = 640, 8, 8
    rng = np.random.default_rng(0)
    prefix = rng.integers(1, cfg.vocab_size, prefix_len).tolist()
    prompts = []
    for i in range(n_req):
        if i % 4 < 3:  # 75% share the prefix, unique suffixes
            prompts.append(
                prefix + rng.integers(1, cfg.vocab_size, suffix_len).tolist()
            )
        else:          # 25% fully distinct, same total length
            prompts.append(
                rng.integers(1, cfg.vocab_size, prefix_len + suffix_len).tolist()
            )
    results = {}
    for cached in (False, True):
        engine = DecodeEngine(
            module, slots=slots, max_new_tokens=new_tokens,
            prompt_buckets=(bucket,), chunk_steps=chunk_steps,
            prefix_cache=RadixPrefixCache() if cached else None,
        )
        try:
            engine.warmup(params)
            if cached:
                # seed request: the stream measures steady-state reuse,
                # not the first-ever prefix computation
                engine.generate(params, [prompts[0]])
            engine.reset_stats()
            t0 = time.perf_counter()
            outs = engine.generate(params, prompts)
            wall_ms = (time.perf_counter() - t0) * 1e3
            stats = engine.stats()
            results[cached] = (outs, stats, wall_ms)
        finally:
            engine.close()
    assert results[False][0] == results[True][0], (
        "prefix cache changed produced tokens — parity violation"
    )
    off_ttft = results[False][1].get("ttft_ms", {})
    on_ttft = results[True][1].get("ttft_ms", {})
    cache_stats = results[True][1]["prefix_cache"]
    for cached in (False, True):
        _, stats, wall_ms = results[cached]
        ttft = stats.get("ttft_ms", {})
        print(json.dumps({
            "metric": "serve_prefix_cache_ttft_p50_ms",
            "cached": cached,
            "requests": n_req,
            "prefix_len": prefix_len,
            "suffix_len": suffix_len,
            "new_tokens": new_tokens,
            "value": round(ttft.get("p50", 0.0), 1),
            "p95_ms": round(ttft.get("p95", 0.0), 1),
            "wall_ms": round(wall_ms, 1),
            "unit": "ms",
        }))
    print(json.dumps({
        "metric": "serve_prefix_cache_summary",
        "hit_rate": cache_stats["hit_rate"],
        "prefill_tokens_saved": cache_stats["prefill_tokens_saved"],
        "ttft_p50_delta_ms": round(
            off_ttft.get("p50", 0.0) - on_ttft.get("p50", 0.0), 1
        ),
        "tokens_identical": True,
        "unit": "ms",
    }))


def introspection_leg() -> None:
    """Program-introspection overhead + hardware-truth report
    (``UNIONML_TPU_BENCH_PRESET=serve_introspection``).

    Runs the SAME request stream through a DecodeEngine with
    introspection (cost-analysis tracker + MFU gauges + flight
    recorder) OFF and ON, asserts the produced tokens are
    bit-identical, and reports the wall-clock overhead delta — the
    number that keeps the "introspection is off the steady-state hot
    path" claim honest — plus the decode program's measured flops,
    recompile count, and MFU/roofline ratios.
    """
    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu import telemetry
    from unionml_tpu.models import Llama, LlamaConfig
    from unionml_tpu.serving.engine import DecodeEngine

    backend = jax.default_backend()
    if backend == "cpu":
        cfg = serving_config("tiny")
        module = Llama(cfg)
        tokens0 = jnp.zeros((1, 8), jnp.int32)
        params = jax.jit(module.init)(jax.random.PRNGKey(0), tokens0)["params"]
        n_req, new_tokens, bucket, slots, chunk_steps = 24, 8, 16, 4, 4
    else:
        cfg = serving_config("serve_1p5b")
        qcfg = LlamaConfig(**{**cfg.__dict__, "quantized": True})
        module = Llama(qcfg)
        params = random_quantized_params(module)
        n_req, new_tokens, bucket, slots, chunk_steps = 128, 32, 64, 8, 8
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, cfg.vocab_size, bucket // 2).tolist()
        for _ in range(n_req)
    ]
    results = {}
    for introspect in (False, True):
        engine = DecodeEngine(
            module, slots=slots, max_new_tokens=new_tokens,
            prompt_buckets=(bucket,), chunk_steps=chunk_steps,
            introspect=introspect,
            # isolated sinks: the off leg must not even share a registry
            registry=telemetry.MetricsRegistry(),
            tracer=telemetry.TraceRecorder(),
            flight=telemetry.FlightRecorder() if introspect else None,
        )
        try:
            engine.warmup(params)
            engine.reset_stats()
            t0 = time.perf_counter()
            outs = engine.generate(params, prompts)
            wall_ms = (time.perf_counter() - t0) * 1e3
            results[introspect] = (outs, engine.stats(), wall_ms)
        finally:
            engine.close()
    assert results[False][0] == results[True][0], (
        "introspection changed produced tokens — parity violation"
    )
    off_ms, on_ms = results[False][2], results[True][2]
    for introspect in (False, True):
        print(json.dumps({
            "metric": "serve_introspection_wall_ms",
            "introspect": introspect,
            "requests": n_req,
            "new_tokens": new_tokens,
            "value": round(results[introspect][2], 1),
            "unit": "ms",
        }))
    programs = results[True][1]["programs"]
    decode = programs["engine.decode"]
    print(json.dumps({
        "metric": "serve_introspection_summary",
        "overhead_ms": round(on_ms - off_ms, 1),
        "overhead_pct": round(100.0 * (on_ms - off_ms) / max(off_ms, 1e-9), 2),
        "tokens_identical": True,
        "decode_calls": decode["calls"],
        "decode_compiles": decode["compiles"],
        "decode_flops_per_call": decode["flops_per_call"],
        "decode_bytes_per_call": decode["bytes_per_call"],
        "decode_mfu": decode["mfu"],
        "decode_hbm_utilization": decode["hbm_utilization"],
        "device": programs["device"],
        "unit": "ms",
    }))


def tracing_leg() -> None:
    """Distributed-tracing overhead report
    (``UNIONML_TPU_BENCH_PRESET=serve_tracing``).

    Runs the SAME request stream through a DecodeEngine twice — once
    bare, once with W3C trace-context propagation (every request
    submitted inside a ``trace_scope`` carrying a synthetic inbound
    ``traceparent``) AND a live OTLP exporter shipping every finished
    request's span tree plus metric snapshots to an in-process
    collector stub — asserts the produced tokens are bit-identical,
    and reports the per-request p50/p99 overhead delta. This is the
    number that keeps the "propagation + push export stay off the
    decode hot path" claim honest (the acceptance bar is ≤ 2% p99 on
    the CPU smoke configuration).
    """
    import threading

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu import telemetry
    from unionml_tpu.exporters import OtlpCollectorStub, OtlpExporter
    from unionml_tpu.models import Llama, LlamaConfig
    from unionml_tpu.serving._stats import percentile_summary
    from unionml_tpu.serving.engine import DecodeEngine

    backend = jax.default_backend()
    if backend == "cpu":
        cfg = serving_config("tiny")
        module = Llama(cfg)
        tokens0 = jnp.zeros((1, 8), jnp.int32)
        params = jax.jit(module.init)(jax.random.PRNGKey(0), tokens0)["params"]
        n_req, clients, new_tokens, bucket, slots, chunk_steps = 48, 4, 8, 16, 4, 4
    else:
        cfg = serving_config("serve_1p5b")
        qcfg = LlamaConfig(**{**cfg.__dict__, "quantized": True})
        module = Llama(qcfg)
        params = random_quantized_params(module)
        n_req, clients, new_tokens, bucket, slots, chunk_steps = 128, 8, 32, 64, 8, 8
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, cfg.vocab_size, bucket // 2).tolist()
        for _ in range(n_req)
    ]
    results = {}
    for traced in (False, True):
        registry = telemetry.MetricsRegistry()
        tracer = telemetry.TraceRecorder(registry=registry)
        stub = exporter = None
        if traced:
            stub = OtlpCollectorStub()
            exporter = OtlpExporter(
                stub.endpoint, registry=registry, tracer=tracer,
                interval_s=0.25, seed=0,
            )
        engine = DecodeEngine(
            module, slots=slots, max_new_tokens=new_tokens,
            prompt_buckets=(bucket,), chunk_steps=chunk_steps,
            registry=registry, tracer=tracer,
            flight=telemetry.FlightRecorder(),
        )
        try:
            engine.warmup(params)
            engine.reset_stats()
            outs = [None] * n_req
            lat, lock = [], threading.Lock()

            def client(idx0):
                for i in range(idx0, n_req, clients):
                    ctx = telemetry.TraceContext(
                        telemetry.new_trace_id(), telemetry.new_span_id()
                    )
                    t0 = time.perf_counter()
                    if traced:
                        with telemetry.trace_scope(ctx):
                            out = engine.generate(params, [prompts[i]])
                    else:
                        out = engine.generate(params, [prompts[i]])
                    dt = (time.perf_counter() - t0) * 1e3
                    outs[i] = out[0]
                    with lock:
                        lat.append(dt)

            threads = [
                threading.Thread(target=client, args=(c,))
                for c in range(clients)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall_ms = (time.perf_counter() - t0) * 1e3
            exported = dropped = 0
            if exporter is not None:
                exporter.flush()
                exported = int(exporter._m_exported.value)
                dropped = int(exporter._m_dropped.value)
            results[traced] = {
                "outs": outs,
                "summary": percentile_summary(lat),
                "wall_ms": wall_ms,
                "exported_spans": exported,
                "dropped": dropped,
            }
        finally:
            engine.close()
            if exporter is not None:
                exporter.close(flush=False)
            if stub is not None:
                stub.close()
    assert results[False]["outs"] == results[True]["outs"], (
        "tracing + OTLP export changed produced tokens — parity violation"
    )
    for traced in (False, True):
        r = results[traced]
        print(json.dumps({
            "metric": "serve_tracing_latency_ms",
            "traced": traced,
            "requests": n_req,
            "clients": clients,
            "new_tokens": new_tokens,
            "p50_ms": r["summary"]["p50"],
            "value": r["summary"]["p99"],
            "wall_ms": round(r["wall_ms"], 1),
            "unit": "ms",
        }))
    off, on = results[False]["summary"], results[True]["summary"]
    print(json.dumps({
        "metric": "serve_tracing_summary",
        "tokens_identical": True,
        "p50_delta_pct": round(
            100.0 * (on["p50"] - off["p50"]) / max(off["p50"], 1e-9), 2
        ),
        "p99_delta_pct": round(
            100.0 * (on["p99"] - off["p99"]) / max(off["p99"], 1e-9), 2
        ),
        "exported_spans": results[True]["exported_spans"],
        "export_dropped": results[True]["dropped"],
        "unit": "pct",
    }))


def paged_leg() -> None:
    """Block-paged device KV at a fixed HBM byte budget
    (``UNIONML_TPU_BENCH_PRESET=serve_paged``).

    The workload paging exists for: a LONG-TAIL prompt mix (75% short
    prompts at 1/8 of the bucket, 25% at the full bucket) where the
    contiguous engine reserves every slot's worst case — bucket +
    max_new + pipeline spare — and the byte budget therefore caps the
    slot count. The paged engine spends the SAME budget on a global
    block pool; short prompts charge only their own blocks, so more
    sequences fit.

    Phase 1 — **effective batch at fixed budget**: the budget is what a
    ``contig_slots``-slot contiguous engine costs; both engines serve
    the same saturating stream while a sampler records peak concurrent
    residents. Acceptance: paged peak >= 1.5x contiguous peak, tokens
    bit-identical (the reference paged kernel).

    Phase 2 — **decode tokens/s at equal batch**: both engines at the
    SAME slot count, decode throughput recorded (paged must not
    regress when the layout is the only change); PR 4's per-program
    MFU/HBM gauges attribute where the time goes.
    """
    import threading

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu import telemetry
    from unionml_tpu.models import Llama, LlamaConfig
    from unionml_tpu.serving.engine import DecodeEngine

    backend = jax.default_backend()
    if backend == "cpu":
        cfg = serving_config("tiny")
        # the parity assert is defined on the REFERENCE paged kernel
        # (bit-identical to the contiguous path by construction); the
        # Pallas kernel matches only up to float reduction order, so a
        # near-tie argmax could flip a greedy token and fail the bench
        # spuriously on TPU. Kernel speed is measured by the paged leg
        # of benchmarks/attn_kernels.py instead.
        module = Llama(
            LlamaConfig(**{**cfg.__dict__, "paged_impl": "reference"})
        )
        tokens0 = jnp.zeros((1, 8), jnp.int32)
        params = jax.jit(module.init)(jax.random.PRNGKey(0), tokens0)["params"]
        # new_tokens long enough that residents ACCUMULATE (the peak
        # must be memory-limited, not admission-rate-limited, for the
        # effective-batch comparison to measure the layout)
        n_req, new_tokens, bucket, chunk_steps = 24, 32, 64, 4
        blk, contig_slots, paged_slots = 16, 2, 8
    else:
        cfg = serving_config("serve_1p5b")
        qcfg = LlamaConfig(**{
            **cfg.__dict__, "quantized": True, "paged_impl": "reference",
        })
        module = Llama(qcfg)
        params = random_quantized_params(module)
        n_req, new_tokens, bucket, chunk_steps = 128, 32, 512, 8
        blk, contig_slots, paged_slots = 16, 4, 16
    rng = np.random.default_rng(0)
    prompts = []
    for i in range(n_req):
        # the long-tail mix: 75% short (bucket/8), 25% full-bucket
        n = bucket // 8 if i % 4 < 3 else bucket - 1
        prompts.append(rng.integers(1, cfg.vocab_size, n).tolist())

    def engine_for(paged: bool, slots: int, budget=None):
        kw = dict(
            slots=slots, max_new_tokens=new_tokens,
            prompt_buckets=(bucket,), chunk_steps=chunk_steps,
            registry=telemetry.MetricsRegistry(),
        )
        if paged:
            kw.update(paged=True, kv_block_size=blk)
            if budget is not None:
                kw.update(kv_pool_bytes=budget)
        return DecodeEngine(module, **kw)

    def run_stream(engine):
        """Serve the whole stream; sample peak concurrent residents."""
        peak = [0]
        stop = threading.Event()

        def sampler():
            while not stop.is_set():
                peak[0] = max(peak[0], int(engine._m_slots_busy.value))
                time.sleep(0.001)

        t = threading.Thread(target=sampler, daemon=True)
        engine.warmup(params)
        engine.reset_stats()
        t.start()
        t0 = time.perf_counter()
        outs = engine.generate(params, prompts)
        wall_s = time.perf_counter() - t0
        stop.set()
        t.join(timeout=5)
        stats = engine.stats()
        decode_tokens = sum(len(o) for o in outs)
        return {
            "outs": outs,
            "peak_batch": peak[0],
            "wall_s": wall_s,
            "tokens_per_s": decode_tokens / wall_s,
            "decode": stats.get("programs", {}).get("engine.decode", {}),
            "kv_pool": stats.get("kv_pool"),
        }

    # ---- phase 1: effective batch at a FIXED byte budget ----
    contig = engine_for(False, contig_slots)
    try:
        row_bytes = contig._kv_block_nbytes(1)
        budget = contig_slots * contig.cache_len * row_bytes
        r_contig = run_stream(contig)
    finally:
        contig.close()
    paged = engine_for(True, paged_slots, budget=budget)
    try:
        pool_blocks = paged.kv_pool.capacity
        r_paged = run_stream(paged)
    finally:
        paged.close()
    assert r_paged["outs"] == r_contig["outs"], (
        "paged KV changed produced tokens — parity violation"
    )
    assert r_paged["kv_pool"]["blocks_in_use"] == 0, (
        f"leaked pool blocks: {r_paged['kv_pool']}"
    )
    ratio = r_paged["peak_batch"] / max(1, r_contig["peak_batch"])
    for name, r in (("contiguous", r_contig), ("paged", r_paged)):
        print(json.dumps({
            "metric": "serve_paged_effective_batch",
            "layout": name,
            "budget_bytes": budget,
            "requests": n_req,
            "bucket": bucket,
            "new_tokens": new_tokens,
            "value": r["peak_batch"],
            "wall_s": round(r["wall_s"], 2),
            "decode_tokens_per_s": round(r["tokens_per_s"], 1),
            "decode_mfu": r["decode"].get("mfu"),
            "decode_hbm_utilization": r["decode"].get("hbm_utilization"),
            "unit": "concurrent residents",
        }))
    print(json.dumps({
        "metric": "serve_paged_summary",
        "effective_batch_ratio": round(ratio, 2),
        "block_size": blk,
        "pool_blocks": pool_blocks,
        "budget_bytes": budget,
        "tokens_identical": True,
        "pool_alloc_failures": r_paged["kv_pool"]["alloc_failures"],
        "unit": "x",
    }))
    assert ratio >= 1.5, (
        f"paged effective batch {r_paged['peak_batch']} < 1.5x contiguous "
        f"{r_contig['peak_batch']} at the same byte budget"
    )

    # ---- phase 2: decode tokens/s at EQUAL batch (layout-only delta) --
    equal = {}
    for is_paged in (False, True):
        e = engine_for(is_paged, contig_slots)
        try:
            equal[is_paged] = run_stream(e)
        finally:
            e.close()
    assert equal[True]["outs"] == equal[False]["outs"]
    for name, r in (("contiguous", equal[False]), ("paged", equal[True])):
        print(json.dumps({
            "metric": "serve_paged_equal_batch_tokens_per_s",
            "layout": name,
            "slots": contig_slots,
            "value": round(r["tokens_per_s"], 1),
            "wall_s": round(r["wall_s"], 2),
            "decode_mfu": r["decode"].get("mfu"),
            "decode_hbm_utilization": r["decode"].get("hbm_utilization"),
            "unit": "tokens/s",
        }))


def preempt_leg() -> None:
    """Preemptive, priority-aware scheduling under pool overload
    (``UNIONML_TPU_BENCH_PRESET=serve_preempt``; docs/robustness.md
    "Preemption & fairness").

    The workload preemption exists for: a low-priority BULK tenant
    floods the paged KV pool (more concurrent long decodes than the
    pool can hold resident) while a high-priority PREMIUM tenant keeps
    sending short interactive requests. Without the scheduler the
    premium requests queue FIFO behind the bulk backlog and a full
    pool; with it they jump the parked bulk head (promote), evict a
    bulk resident to the host prefix-cache store when blocks are short
    (preempt), and the victims resume via the splice path.

    Phase 1 — **unloaded baseline**: the premium stream alone on the
    warmed engine; per-request wall-time p99 recorded (min over
    rounds — CPU scheduler tails).

    Phase 2 — **overload**: the bulk flood saturates the pool, then
    the same premium stream runs high-priority through the contention.

    Acceptance: premium p99 under overload holds within **1.5x** of
    its unloaded baseline, at least one preemption actually fired,
    every preempted bulk stream reaches exact token parity with its
    solo run, and there are ZERO caller-visible failures.
    """
    import threading

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu import telemetry
    from unionml_tpu.models import Llama, LlamaConfig
    from unionml_tpu.models.generate import make_generator
    from unionml_tpu.serving.engine import DecodeEngine
    from unionml_tpu.serving.prefix_cache import RadixPrefixCache

    backend = jax.default_backend()
    if backend == "cpu":
        cfg = serving_config("tiny")
        module = Llama(
            LlamaConfig(**{**cfg.__dict__, "paged_impl": "reference"})
        )
        tokens0 = jnp.zeros((1, 8), jnp.int32)
        params = jax.jit(module.init)(jax.random.PRNGKey(0), tokens0)["params"]
        bulk_clients, bulk_per_client, premium_n = 4, 3, 12
        bulk_len, bulk_new, prem_len, prem_new = 16, 48, 8, 8
        bucket, blk, slots, rounds = 64, 16, 4, 3
        # capacity fits TWO bulk residents (ceil((16+48)/16)=4 blocks
        # each): the 4-client flood keeps the pool exhausted, and the
        # long bulk decodes make waiting for a natural retirement
        # strictly worse than preempting
        pool_blocks = 9
    else:
        cfg = serving_config("serve_1p5b")
        qcfg = LlamaConfig(**{
            **cfg.__dict__, "quantized": True, "paged_impl": "reference",
        })
        module = Llama(qcfg)
        params = random_quantized_params(module)
        bulk_clients, bulk_per_client, premium_n = 8, 4, 32
        bulk_len, bulk_new, prem_len, prem_new = 128, 128, 32, 16
        bucket, blk, slots, rounds = 512, 16, 8, 3
        pool_blocks = 1 + 4 * ((bulk_len + bulk_new) // blk)

    registry = telemetry.MetricsRegistry()
    engine = DecodeEngine(
        module, slots=slots, max_new_tokens=max(bulk_new, prem_new),
        prompt_buckets=(bucket,), chunk_steps=4, paged=True,
        # a shallow pipeline bounds the deferred-free fence an evicted
        # victim's blocks wait behind — the dominant term in the
        # premium tenant's preempt-then-admit latency
        pipeline_depth=2,
        kv_block_size=blk, kv_pool_blocks=pool_blocks,
        prefix_cache=RadixPrefixCache(block_size=blk, registry=registry),
        registry=registry,
    )
    rng = np.random.default_rng(0)
    bulk_prompts = [
        rng.integers(1, cfg.vocab_size, bulk_len).tolist()
        for _ in range(bulk_clients * bulk_per_client)
    ]
    prem_prompts = [
        rng.integers(1, cfg.vocab_size, prem_len).tolist()
        for _ in range(premium_n)
    ]
    solo_bulk = make_generator(
        module, max_new_tokens=bulk_new, max_len=engine.cache_len
    )
    solo_prem = make_generator(
        module, max_new_tokens=prem_new, max_len=engine.cache_len
    )

    def solo(gen, prompt):
        return np.asarray(
            gen(params, jnp.asarray([prompt], jnp.int32))
        )[0].tolist()

    # ONE solo reference per distinct prompt (the premium stream
    # re-runs rounds x 2 times — recomputing its references each pass
    # would multiply the oracle's device work for identical answers)
    prem_solo = {tuple(p): solo(solo_prem, p) for p in prem_prompts}

    def premium_pass():
        """Sequential premium stream; per-request DECODE latency
        (first harvested chunk → stream end, measured client-side via
        the SSE-shaped generator — the ISSUE's bar: queue/admission
        wait under overload is what the promote/preempt machinery
        spends, decode-lane progress is what it protects)."""
        decode_ms = []
        for p in prem_prompts:
            out: list = []
            t_first = None
            for chunk in engine.generate_stream(
                params, p, max_new_tokens=prem_new,
                tenant="premium", priority="high",
            ):
                if t_first is None:
                    t_first = time.perf_counter()
                out.extend(chunk)
            decode_ms.append((time.perf_counter() - t_first) * 1e3)
            assert out == prem_solo[tuple(p)], "premium token parity"
        return decode_ms

    def premium_phase():
        """Per-request MIN over rounds, then nearest-rank p99 across
        requests (the PR 8 estimator lessons: a nearest-rank p99 of a
        dozen samples IS the max, so one CPU-scheduler tail decides
        the stat — the per-request min cancels it while keeping the
        loaded-vs-unloaded contrast the bar is about)."""
        per_req = None
        for _ in range(rounds):
            ms = premium_pass()
            per_req = (
                ms if per_req is None
                else [min(a, b) for a, b in zip(per_req, ms)]
            )
        per_req.sort()
        return per_req[max(0, math.ceil(0.99 * len(per_req)) - 1)]

    try:
        engine.warmup(params)
        engine.prefix_cache.clear()

        # ---- phase 1: unloaded premium baseline ----
        p99_base = premium_phase()

        # ---- phase 2: bulk flood + premium through the contention --
        failures: list = []
        bulk_outs: dict = {}
        lock = threading.Lock()

        def bulk_client(idx: int):
            for j in range(bulk_per_client):
                p = bulk_prompts[idx * bulk_per_client + j]
                try:
                    out = engine.generate(
                        params, [p], max_new_tokens=bulk_new,
                        tenant="bulk", priority="low",
                    )[0]
                    with lock:
                        bulk_outs[tuple(p)] = out
                except Exception as exc:  # ZERO of these allowed
                    with lock:
                        failures.append(repr(exc))

        threads = [
            threading.Thread(target=bulk_client, args=(i,), daemon=True)
            for i in range(bulk_clients)
        ]
        for t in threads:
            t.start()
        # wait for real pool pressure before measuring the premium leg
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if engine.stats()["kv_pool"]["alloc_failures"] > 0:
                break
            time.sleep(0.002)
        p99_loaded = premium_phase()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads), "bulk stream hung"
        assert not failures, f"caller-visible failures: {failures}"
        # preempted bulk streams reached exact token parity
        for p in bulk_prompts:
            assert bulk_outs[tuple(p)] == solo(solo_bulk, p), (
                "preempted bulk stream lost token parity"
            )
        stats = engine.stats()
        preemptions = stats["scheduler"]["preemptions"]
        pool = stats["kv_pool"]
        ratio = p99_loaded / max(1e-9, p99_base)
        print(json.dumps({
            "metric": "serve_preempt_premium_decode_p99_ms",
            "unloaded": round(p99_base, 2),
            "overloaded": round(p99_loaded, 2),
            "ratio": round(ratio, 3),
            "bound": 1.5,
            "unit": "ms",
        }))
        print(json.dumps({
            "metric": "serve_preempt_summary",
            "preemptions": preemptions,
            "preempted_blocks": pool["preempted_blocks"],
            "alloc_failures": pool["alloc_failures"],
            "bulk_requests": len(bulk_prompts),
            "premium_requests": premium_n * rounds * 2,
            "caller_visible_failures": 0,
            "tokens_identical": True,
            "unit": "",
        }))
        assert preemptions >= 1, (
            "the overload never triggered a preemption — the scenario "
            "is not exercising the scheduler"
        )
        assert pool["blocks_in_use"] == 0, f"leaked pool blocks: {pool}"
        assert ratio <= 1.5, (
            f"premium p99 decode latency {p99_loaded:.1f} ms under "
            f"overload exceeds 1.5x its unloaded baseline "
            f"{p99_base:.1f} ms"
        )
    finally:
        engine.close()


def usage_leg() -> None:
    """Per-tenant usage metering: attribution identity, cardinality
    bound, and ledger overhead
    (``UNIONML_TPU_BENCH_PRESET=serve_usage``).

    Phase 1 — **attribution identity + cardinality**: a mixed 3-tenant
    stream (interleaved concurrent clients, uneven request counts)
    through a ledger-on engine. Asserts per-tenant attributed
    device-seconds and tokens each explain >= 95% of the engine totals
    (the measurement-substrate contract fair scheduling will build on),
    then fires a burst of 40 distinct one-request tenants and asserts
    the exported ``unionml_tenant_*`` label cardinality stays
    <= top_k + 1 (the ``other`` rollup absorbing the tail).

    Phase 2 — **overhead at token parity**: the same prompts through
    ONE engine with the ledger toggled on/off between rounds (the
    ``engine.usage`` idle-swap seam), tokens asserted bit-identical,
    per-request p99 delta asserted <= 2%. The estimator is built for a
    2% bar on a millisecond-scale CPU workload (the goodput bench's
    overhead-leg lessons, adapted):

    - BOTH legs run on the SAME engine instance — two separately-
      constructed engines differ by several percent (p50 included)
      from thread/allocator placement alone, a persistent instance
      bias that min-over-rounds cannot wash out; toggling the seam
      leaves only the ledger's own cost in the delta,
    - the stream is SEQUENTIAL — per-request p99 under 4 GIL-bound
      client threads differs +-5% between two IDENTICAL ledger-off
      engines (scheduler tails), swamping the bar; concurrency belongs
      to phase 1's attribution identity, the overhead question is
      per-request cost,
    - legs are paired PER REQUEST (each request runs ledger-off and
      ledger-on back-to-back, order alternating by round+index), not
      per pass — the host's minute-scale drift moves whole sequential
      passes by +-2%, which leg-level alternation leaves on one leg
      but a milliseconds-apart pair cancels,
    - per-request MIN over rounds, then nearest-rank p99 across
      requests, UNROUNDED (``percentile_summary`` rounds to 0.1 ms =
      2% of this workload): the min discards scheduler outliers per
      request the way interleaved min-of-N discards bad rounds, while
      the p99 across requests keeps the workload's own tail,
    - gc paused over the timed rounds (a collection mid-round lands a
      ~30 ms outlier on whichever leg happens to be running).
    """
    import gc
    import threading

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu import telemetry
    from unionml_tpu.models import Llama, LlamaConfig
    from unionml_tpu.serving.engine import DecodeEngine
    from unionml_tpu.serving.usage import UsageLedger, tenant_scope

    backend = jax.default_backend()
    if backend == "cpu":
        cfg = serving_config("tiny")
        module = Llama(cfg)
        tokens0 = jnp.zeros((1, 8), jnp.int32)
        params = jax.jit(module.init)(jax.random.PRNGKey(0), tokens0)["params"]
        n_req, new_tokens, bucket, slots, chunk_steps = 48, 8, 16, 4, 4
        rounds = 6
    else:
        cfg = serving_config("serve_1p5b")
        qcfg = LlamaConfig(**{**cfg.__dict__, "quantized": True})
        module = Llama(qcfg)
        params = random_quantized_params(module)
        n_req, new_tokens, bucket, slots, chunk_steps = 128, 32, 64, 8, 8
        rounds = 4
    top_k = 4
    burst_tenants = 40
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, cfg.vocab_size, bucket // 2).tolist()
        for _ in range(n_req)
    ]
    # uneven tenant mix: tenant-a 3/6, tenant-b 2/6, tenant-c 1/6
    mix = ("tenant-a", "tenant-a", "tenant-a", "tenant-b", "tenant-b",
           "tenant-c")
    tenants = [mix[i % len(mix)] for i in range(n_req)]

    def run_stream(engine, traced_tenants):
        """Serve the stream with `clients` concurrent workers, each
        request under its tenant's scope; outputs index-aligned."""
        clients = 4
        outs = [None] * n_req

        def client(idx0):
            for i in range(idx0, n_req, clients):
                with tenant_scope(traced_tenants[i]):
                    out = engine.generate(params, [prompts[i]])
                outs[i] = out[0]

        threads = [
            threading.Thread(target=client, args=(c,))
            for c in range(clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return outs

    # ---- phase 1: attribution identity + cardinality bound ----
    registry = telemetry.MetricsRegistry()
    ledger = UsageLedger(registry=registry, top_k=top_k)
    engine = DecodeEngine(
        module, slots=slots, max_new_tokens=new_tokens,
        prompt_buckets=(bucket,), chunk_steps=chunk_steps,
        registry=registry, tracer=telemetry.TraceRecorder(),
        flight=telemetry.FlightRecorder(), usage=ledger,
    )
    try:
        engine.warmup(params)
        engine.reset_stats()
        run_stream(engine, tenants)
        report = ledger.report()
        per_tenant = report["tenants"]
        attributed_s = report["attribution"]["attributed_device_seconds"]
        attributed_tok = report["attribution"]["attributed_tokens"]
        totals = report["totals"]
        s_cov = report["attribution"]["device_seconds_coverage"]
        t_cov = report["attribution"]["token_coverage"]
        print(json.dumps({
            "metric": "serve_usage_attribution",
            "requests": n_req,
            "tenants": {
                t: {
                    "device_seconds": v["device_seconds"],
                    "decode_tokens": v["decode_tokens"],
                    "requests": v["requests"],
                }
                for t, v in per_tenant.items()
            },
            "total_device_seconds": totals["device_seconds"],
            "total_tokens": totals["tokens"],
            "attributed_device_seconds": attributed_s,
            "attributed_tokens": attributed_tok,
            "value": s_cov,
            "token_coverage": t_cov,
            "capacity_headroom": report["capacity"]["headroom"],
            "unit": "coverage ratio",
        }))
        assert s_cov >= 0.95, (
            f"attributed device-seconds cover only {s_cov:.3f} of "
            "engine totals (bar: 0.95)"
        )
        assert t_cov >= 0.95, (
            f"attributed tokens cover only {t_cov:.3f} of engine "
            "totals (bar: 0.95)"
        )
        # cardinality: a burst of distinct one-request tenants must
        # roll into `other`, not mint series
        for i in range(burst_tenants):
            with tenant_scope(f"burst-{i}"):
                engine.generate(params, [prompts[i % n_req]])
        text = registry.exposition()
        label_values = set()
        for line in text.splitlines():
            if line.startswith("unionml_tenant_") and 'tenant="' in line:
                label_values.add(
                    line.split('tenant="', 1)[1].split('"', 1)[0]
                )
        print(json.dumps({
            "metric": "serve_usage_cardinality",
            "distinct_tenants": ledger.report()["distinct_tenants"],
            "top_k": top_k,
            "exported_tenant_labels": sorted(label_values),
            "value": len(label_values),
            "unit": "label values",
        }))
        assert len(label_values) <= top_k + 1, (
            f"exported tenant-label cardinality {len(label_values)} "
            f"exceeds top_k + 1 = {top_k + 1}: {sorted(label_values)}"
        )
    finally:
        engine.close()

    # ---- phase 2: overhead at token parity (sequential paired rounds,
    # alternating leg order, per-request min, unrounded p99) ----
    # per-request base doubled on CPU so the ledger's ~10 us/chunk and
    # the timer/scheduler jitter are small FRACTIONS of every sample
    p2_new_tokens = new_tokens * 2 if backend == "cpu" else new_tokens
    # sample sizes sized for the nearest-rank p99 of per-request MINs:
    # at n=48 that rank IS the maximum, so one request unlucky in every
    # round decides the stat — >=120 requests drop the single worst,
    # and 10 rounds tighten each request's min (an outlier must recur
    # in ALL rounds to survive)
    p2_n_req, p2_rounds = (120, 10) if backend == "cpu" else (128, rounds)
    p2_prompts = [
        rng.integers(1, cfg.vocab_size, bucket // 2).tolist()
        for _ in range(p2_n_req)
    ]
    p2_tenants = [mix[i % len(mix)] for i in range(p2_n_req)]
    # ONE engine for both legs, toggling the off-switch seam between
    # rounds (swapped only while idle): two separately-constructed
    # engines differ by several percent — p50 included — from thread/
    # allocator placement alone on this host, a persistent instance
    # bias that per-request min-over-rounds cannot wash out because
    # every round of the slow leg runs on the slow instance. The
    # attribution window is clamped at dispatch time, so the off-leg's
    # idle gap never inflates the first on-leg window.
    registry = telemetry.MetricsRegistry()
    p2_engine = DecodeEngine(
        module, slots=slots, max_new_tokens=p2_new_tokens,
        prompt_buckets=(bucket,), chunk_steps=chunk_steps,
        registry=registry, tracer=telemetry.TraceRecorder(),
        flight=telemetry.FlightRecorder(), usage=None,
    )
    p2_ledger = UsageLedger(registry=registry)

    try:
        p2_engine.warmup(params)
        p2_engine.reset_stats()
        per_req = {m: [[] for _ in range(p2_n_req)] for m in (False, True)}
        outs = {m: [None] * p2_n_req for m in (False, True)}
        gc.collect()
        gc.disable()
        try:
            for r in range(p2_rounds):
                for i in range(p2_n_req):
                    # request-level pairing: each request runs BOTH
                    # legs back-to-back (~ms apart, order alternating
                    # by round+index), so the host's minute-scale
                    # drift — which moved whole leg-level passes by
                    # +-2% and swamped the bar — cancels within the
                    # pair instead of landing on one leg
                    legs = (
                        (False, True) if (r + i) % 2 == 0
                        else (True, False)
                    )
                    for metered in legs:
                        p2_engine.usage = p2_ledger if metered else None
                        t0 = time.perf_counter()
                        with tenant_scope(p2_tenants[i]):
                            out = p2_engine.generate(
                                params, [p2_prompts[i]]
                            )
                        per_req[metered][i].append(
                            (time.perf_counter() - t0) * 1e3
                        )
                        outs[metered][i] = out[0]
        finally:
            p2_engine.usage = None
            gc.enable()
        assert outs[False] == outs[True], (
            "usage metering changed produced tokens — parity violation"
        )

        def tail_p99(metered: bool) -> float:
            best = sorted(min(vs) for vs in per_req[metered])
            return best[max(0, math.ceil(0.99 * len(best)) - 1)]

        off_p99, on_p99 = tail_p99(False), tail_p99(True)
        overhead_pct = 100.0 * (on_p99 - off_p99) / max(off_p99, 1e-9)
        for metered in (False, True):
            best = [min(vs) for vs in per_req[metered]]
            print(json.dumps({
                "metric": "serve_usage_latency_p99_ms",
                "metered": metered,
                "requests": p2_n_req,
                "rounds": p2_rounds,
                "new_tokens": p2_new_tokens,
                "protocol": "sequential, per-request paired legs, "
                            "min-per-request over rounds",
                "value": round(tail_p99(metered), 3),
                "p50_ms": round(sorted(best)[len(best) // 2], 3),
                "unit": "ms",
            }))
        print(json.dumps({
            "metric": "serve_usage_summary",
            "tokens_identical": True,
            "value": round(overhead_pct, 2),
            "unit": "pct p99 overhead",
        }))
        assert overhead_pct <= 2.0, (
            f"usage-ledger p99 overhead {overhead_pct:.2f}% exceeds "
            "the 2% bar"
        )
    finally:
        p2_engine.close()


def overload_leg() -> None:
    """Admission control + supervised recovery under saturation
    (``UNIONML_TPU_BENCH_PRESET=serve_overload``).

    Phase 1 — **over-admitted stream**: more concurrent clients than
    the bounded engine (slots + ``max_queue_depth``) can hold, no
    client backoff. Reports the shed rate (Overloaded rejections /
    offered requests) and the accepted requests' p50/p99 — the
    admission-control contract: bounded latency for what is accepted,
    fast typed rejection for the rest, instead of unbounded queueing
    where EVERY request eventually times out.

    Phase 2 — **recovery time**: with every slot resident, a
    FaultInjector raises an OOM-shaped XLA error on the next decode
    dispatch; the metric is the wall time from arming the fault to the
    first successfully completed request on the rebuilt state.
    """
    import threading

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.models import Llama, LlamaConfig
    from unionml_tpu.serving._stats import percentile_summary
    from unionml_tpu.serving.engine import DecodeEngine
    from unionml_tpu.serving.faults import (
        FaultInjector, Overloaded, xla_oom_error,
    )

    backend = jax.default_backend()
    if backend == "cpu":
        cfg = serving_config("tiny")
        module = Llama(cfg)
        tokens0 = jnp.zeros((1, 8), jnp.int32)
        params = jax.jit(module.init)(jax.random.PRNGKey(0), tokens0)["params"]
        n_req, clients, slots, queue_depth = 48, 8, 2, 4
        new_tokens, bucket, chunk_steps = 16, 16, 4
    else:
        cfg = serving_config("serve_1p5b")
        qcfg = LlamaConfig(**{**cfg.__dict__, "quantized": True})
        module = Llama(qcfg)
        params = random_quantized_params(module)
        n_req, clients, slots, queue_depth = 256, 32, 8, 16
        new_tokens, bucket, chunk_steps = 32, 64, 8
    fi = FaultInjector()
    engine = DecodeEngine(
        module, slots=slots, max_new_tokens=new_tokens,
        prompt_buckets=(bucket,), chunk_steps=chunk_steps,
        max_queue_depth=queue_depth, fault_injector=fi,
    )
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, cfg.vocab_size, bucket // 2).tolist()
        for _ in range(n_req)
    ]
    try:
        engine.warmup(params)
        engine.reset_stats()

        lat, shed, failed, lock = [], [0], [], threading.Lock()

        def client(rows):
            for p in rows:
                t0 = time.perf_counter()
                try:
                    engine.generate(params, [p])
                except Overloaded:
                    with lock:
                        shed[0] += 1
                    continue
                except Exception as exc:
                    # anything else (timeout, breaker, ...) must be
                    # COUNTED, not silently truncate the sample — a
                    # survivorship-biased p99 would report a healthy
                    # tail exactly when the system is misbehaving
                    with lock:
                        failed.append(f"{type(exc).__name__}: {exc}")
                    continue
                with lock:
                    lat.append((time.perf_counter() - t0) * 1e3)

        threads = [
            threading.Thread(target=client, args=(prompts[i::clients],))
            for i in range(clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall_ms = (time.perf_counter() - t0) * 1e3
        s = percentile_summary(lat)
        print(json.dumps({
            "metric": "serve_overload_accepted_p99_ms",
            "offered": n_req,
            "clients": clients,
            "slots": slots,
            "max_queue_depth": queue_depth,
            "accepted": len(lat),
            "shed": shed[0],
            "failed": len(failed),
            "failed_errors": sorted(set(failed))[:3],
            "shed_rate": round(shed[0] / n_req, 3),
            "value": round(s.get("p99", 0.0), 1),
            "p50_ms": round(s.get("p50", 0.0), 1),
            "wall_ms": round(wall_ms, 1),
            "unit": "ms",
        }))

        # ---- phase 2: recovery time after an injected device fault ----
        def occupant(p):
            try:
                engine.generate(params, [p])
            except BaseException:
                pass  # the poisoned batch: expected to fail

        occ = [
            threading.Thread(target=occupant, args=(prompts[i],))
            for i in range(slots)
        ]
        for t in occ:
            t.start()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            with engine._lock:  # resident-count poll (bench-only peek)
                if sum(r is not None for r in engine._occupant) == slots:
                    break
            time.sleep(0.002)
        fi.arm("engine.dispatch", exc=xla_oom_error())
        t_fault = time.perf_counter()
        while True:  # first completed request marks recovered service
            try:
                engine.generate(params, [prompts[0]])
                break
            except Exception:
                time.sleep(0.002)
        recovery_ms = (time.perf_counter() - t_fault) * 1e3
        for t in occ:
            t.join(timeout=60)
        print(json.dumps({
            "metric": "serve_overload_recovery_ms",
            "slots": slots,
            "value": round(recovery_ms, 1),
            "recoveries": engine.stats()["robustness"]["recoveries"],
            "unit": "ms",
        }))
    finally:
        engine.close()


def router_leg() -> None:
    """Fleet-router robustness + overhead
    (``UNIONML_TPU_BENCH_PRESET=serve_router``).

    Phase 1 — **chaos under traffic**: 3 engine replicas behind a
    ``FleetRouter``, concurrent clients streaming requests. Mid-run,
    one replica takes an OOM-shaped device fault on a decode dispatch
    (the poisoned batch dies inside that engine; the router's retry
    envelope absorbs it) and another replica is drained and rejoined
    (the rolling-restart choreography). Asserts: ZERO caller-visible
    failures, every response token-identical to its solo run, and
    total retries within the fleet retry budget
    (``burst + ratio * requests`` — the storm-control bound,
    docs/robustness.md "Fleet robustness").

    Phase 2 — **passthrough overhead**: the same engine serves the
    same requests directly and through a 1-replica router,
    interleaved per request in alternating order (the PR 8 estimator
    lessons: whole-pass legs drift percents at minute scale; pairing
    per request cancels it), per-request MIN over rounds, nearest-rank
    p99 computed UNROUNDED. Asserts the router adds <= 2% p99 and
    bit-identical tokens.
    """
    import gc
    import threading

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu import telemetry
    from unionml_tpu.models import Llama
    from unionml_tpu.serving.engine import DecodeEngine
    from unionml_tpu.serving.faults import FaultInjector, xla_oom_error
    from unionml_tpu.serving.router import (
        EngineReplica, FleetRouter, RouterPolicy,
    )

    backend = jax.default_backend()
    if backend == "cpu":
        cfg = serving_config("tiny")
        module = Llama(cfg)
        tokens0 = jnp.zeros((1, 8), jnp.int32)
        params = jax.jit(module.init)(jax.random.PRNGKey(0), tokens0)["params"]
        n_req, clients, slots = 48, 6, 2
        new_tokens, bucket, chunk_steps = 16, 16, 4
        overhead_reqs, overhead_rounds = 40, 6
    else:
        cfg = serving_config("serve_1p5b")
        module = Llama(cfg)
        params = random_quantized_params(module)
        n_req, clients, slots = 192, 24, 8
        new_tokens, bucket, chunk_steps = 32, 64, 8
        overhead_reqs, overhead_rounds = 120, 8

    n_replicas = 3
    ratio, burst = 0.2, 3.0
    fis = [FaultInjector() for _ in range(n_replicas)]
    engines = [
        DecodeEngine(
            module, slots=slots, max_new_tokens=new_tokens,
            prompt_buckets=(bucket,), chunk_steps=chunk_steps,
            max_queue_depth=64, fault_injector=fis[i],
        )
        for i in range(n_replicas)
    ]
    registry = telemetry.MetricsRegistry()
    flight = telemetry.FlightRecorder()
    router = FleetRouter(
        [
            EngineReplica(engines[i], params, name=f"r{i}")
            for i in range(n_replicas)
        ],
        policy=RouterPolicy(
            retry_budget_ratio=ratio, retry_budget_burst=burst,
            backoff_base_s=0.001, jitter_s=0.0, health_ttl_s=0.05,
        ),
        registry=registry,
        flight=flight,
    )
    rng = np.random.default_rng(0)
    # a small distinct-prompt set reused across the stream keeps the
    # solo-parity oracle cheap (one solo run per distinct prompt)
    distinct = [
        rng.integers(1, cfg.vocab_size, bucket // 2).tolist()
        for _ in range(8)
    ]
    try:
        for e in engines:
            e.warmup(params)
        solo = {
            tuple(p): engines[0].generate(params, [p])[0] for p in distinct
        }
        for e in engines:
            e.reset_stats()

        results, failures, lock = [], [], threading.Lock()
        started = threading.Event()

        def client(idx):
            for j, p in enumerate(
                distinct[(idx + k) % len(distinct)]
                for k in range(n_req // clients)
            ):
                if idx == 0 and j == 1:
                    started.set()  # traffic confirmed in flight
                try:
                    out = router.generate(p)
                    with lock:
                        results.append((tuple(p), out))
                except BaseException as exc:  # EVERY failure counts
                    with lock:
                        failures.append(f"{type(exc).__name__}: {exc}")

        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(clients)
        ]
        for t in threads:
            t.start()
        started.wait(timeout=60)
        # mid-run: KILL r0 (next decode dispatch dies OOM-shaped) ...
        fis[0].arm("engine.dispatch", exc=xla_oom_error())
        time.sleep(0.05)
        # ... and roll r2: drain (in-flight streams finish), rejoin
        router.drain_replica("r2", timeout=120)
        time.sleep(0.02)
        router.rejoin_replica("r2")
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads), "clients hung"

        assert not failures, (
            f"{len(failures)} caller-visible failures (want 0): "
            f"{sorted(set(failures))[:3]}"
        )
        bad = sum(1 for key, out in results if out != solo[key])
        assert bad == 0, f"{bad}/{len(results)} responses lost token parity"
        assert fis[0].injected("engine.dispatch") == 1, (
            "the replica kill must actually have fired"
        )
        retries = sum(
            child.value
            for _, child in router._m_retries.children()
        )
        budget_cap = burst + ratio * n_req
        assert retries <= budget_cap, (
            f"retry amplification {retries} exceeds budget {budget_cap}"
        )
        kinds = {e["kind"] for e in flight.dump()}
        assert {"route", "retry", "drain", "rejoin"} <= kinds, kinds
        print(json.dumps({
            "metric": "serve_router_failover",
            "replicas": n_replicas,
            "offered": n_req,
            "clients": clients,
            "completed": len(results),
            "caller_visible_failures": len(failures),
            "retries": retries,
            "retry_budget_cap": budget_cap,
            "recoveries_r0": engines[0].stats()["robustness"]["recoveries"],
            "drain_rejoin_cycles": 1,
            "token_parity": "exact",
            "unit": "requests",
        }))
    finally:
        for e in engines:
            e.close()

    # ---- phase 2: 1-replica passthrough overhead vs direct engine ----
    engine = DecodeEngine(
        module, slots=slots, max_new_tokens=new_tokens,
        prompt_buckets=(bucket,), chunk_steps=chunk_steps,
    )
    router1 = FleetRouter(
        [EngineReplica(engine, params, name="solo")],
        policy=RouterPolicy(health_ttl_s=0.05),
        registry=telemetry.MetricsRegistry(),
        flight=telemetry.FlightRecorder(),
    )
    try:
        engine.warmup(params)
        prompts = [
            rng.integers(1, cfg.vocab_size, bucket // 2).tolist()
            for _ in range(overhead_reqs)
        ]
        direct_min = [math.inf] * overhead_reqs
        routed_min = [math.inf] * overhead_reqs
        token_mismatch = 0
        gc_was_enabled = gc.isenabled()
        gc.disable()
        try:
            for r in range(overhead_rounds):
                for i, p in enumerate(prompts):
                    legs = [("direct", i), ("routed", i)]
                    if (r + i) % 2:
                        legs.reverse()  # drift cancels inside the pair
                    outs = {}
                    for legname, idx in legs:
                        t0 = time.perf_counter()
                        if legname == "direct":
                            out = engine.generate(params, [p])[0]
                            dt = time.perf_counter() - t0
                            direct_min[idx] = min(direct_min[idx], dt)
                        else:
                            out = router1.generate(p)
                            dt = time.perf_counter() - t0
                            routed_min[idx] = min(routed_min[idx], dt)
                        outs[legname] = out
                    if outs["direct"] != outs["routed"]:
                        token_mismatch += 1
        finally:
            if gc_was_enabled:
                gc.enable()
        assert token_mismatch == 0, (
            f"{token_mismatch} routed responses diverged from direct"
        )

        def p99(vals):  # nearest-rank, UNROUNDED (0.1 ms rounding is
            v = sorted(vals)  # percents of this workload)
            return v[max(0, math.ceil(0.99 * len(v)) - 1)]

        d99, r99 = p99(direct_min), p99(routed_min)
        overhead = (r99 - d99) / d99 if d99 > 0 else 0.0
        assert overhead <= 0.02, (
            f"router passthrough adds {overhead:.1%} p99 "
            f"(direct {d99 * 1e3:.2f} ms vs routed {r99 * 1e3:.2f} ms); "
            "bar is 2%"
        )
        print(json.dumps({
            "metric": "serve_router_passthrough_p99_overhead",
            "requests": overhead_reqs,
            "rounds": overhead_rounds,
            "direct_p99_ms": round(d99 * 1e3, 3),
            "routed_p99_ms": round(r99 * 1e3, 3),
            "value": round(overhead * 100, 2),
            "token_parity": "exact",
            "unit": "percent",
        }))
        print(json.dumps({
            "metric": "serve_router_summary",
            "failover": "0 caller-visible failures, parity exact",
            "retry_budget": "bounded",
            "passthrough_p99_overhead_pct": round(overhead * 100, 2),
        }))
    finally:
        engine.close()


def autoscale_leg() -> None:
    """Self-operating fleet
    (``UNIONML_TPU_BENCH_PRESET=serve_autoscale``).

    One continuous chaos scenario on a 2-replica baseline fleet with a
    closed-loop :class:`~unionml_tpu.serving.autoscaler
    .FleetAutoscaler` (docs/robustness.md "Autoscaling &
    self-healing"):

    1. **Burn-induced scale-out, fleet-warmed.** A concurrent
       shared-prefix flood drives the fleet TTFT objective into
       sustained fast+slow-window burn; the autoscaler provisions a
       third replica WITHIN the SLO fast window, warm-joined from the
       warmest donor's hot prefix blocks — the joiner's first
       shared-prefix request is asserted to HIT (prefill tokens
       saved > 0 against imported-only content).
    2. **Mid-run kill, replaced automatically.** A replica takes an
       OOM-shaped device fault and then reads as a dead process; the
       router absorbs the in-flight failures (retries), the
       autoscaler reaps the corpse and provisions its replacement.
    3. **Load drop, scale-in.** The flood ends, burn clears, and the
       fleet consolidates back to the 2-replica baseline through the
       hysteresis band.

    Asserts ZERO caller-visible failures and exact per-request token
    parity vs the solo oracle across all three phases, and that every
    scale decision is present in the flight record.
    """
    import threading

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu import telemetry
    from unionml_tpu.models import Llama
    from unionml_tpu.serving.autoscaler import (
        AutoscalerPolicy, EngineReplicaProvisioner, FleetAutoscaler,
    )
    from unionml_tpu.serving.engine import DecodeEngine
    from unionml_tpu.serving.faults import (
        EngineUnavailable, FaultInjector, xla_oom_error,
    )
    from unionml_tpu.serving.router import (
        EngineReplica, FleetRouter, RouterPolicy,
    )
    from unionml_tpu.serving.usage import UsageLedger
    from unionml_tpu.slo import LatencyObjective, SloWatchdog

    backend = jax.default_backend()
    if backend == "cpu":
        cfg = serving_config("tiny")
        module = Llama(cfg)
        tokens0 = jnp.zeros((1, 8), jnp.int32)
        params = jax.jit(module.init)(jax.random.PRNGKey(0), tokens0)["params"]
        n_req, clients, slots = 2400, 8, 2
        new_tokens, bucket, chunk_steps = 16, 32, 4
        ttft_threshold_ms = 10.0
    else:
        cfg = serving_config("serve_1p5b")
        module = Llama(cfg)
        params = random_quantized_params(module)
        n_req, clients, slots = 384, 32, 4
        new_tokens, bucket, chunk_steps = 32, 64, 8
        ttft_threshold_ms = 250.0

    registry = telemetry.MetricsRegistry()
    flight = telemetry.FlightRecorder()
    ledger = UsageLedger(registry=registry)
    fi0 = FaultInjector()

    def make_engine(fi=None):
        return DecodeEngine(
            module, slots=slots, max_new_tokens=new_tokens,
            prompt_buckets=(bucket,), chunk_steps=chunk_steps,
            prefix_cache=True, usage=ledger, max_queue_depth=128,
            registry=registry,
            **({"fault_injector": fi} if fi is not None else {}),
        )

    class KillableEngineReplica(EngineReplica):
        """Models a crashed process: the armed fault poisons the
        in-flight batch (retryable), the kill flag makes every later
        dispatch/health read unreachable."""

        killed = False

        def kill(self):
            self.killed = True

        def generate_stream(self, prompt, *, max_new_tokens=None):
            if self.killed:
                raise EngineUnavailable(
                    f"{self.name} process died", reason="unreachable",
                )
            return super().generate_stream(
                prompt, max_new_tokens=max_new_tokens
            )

        def generate(self, prompt, *, max_new_tokens=None):
            if self.killed:
                raise EngineUnavailable(
                    f"{self.name} process died", reason="unreachable",
                )
            return super().generate(prompt, max_new_tokens=max_new_tokens)

        def health(self):
            if self.killed:
                raise ConnectionError(f"{self.name} process died")
            return super().health()

    engines = [make_engine(fi0), make_engine()]
    replicas = [
        KillableEngineReplica(engines[i], params, name=f"r{i}")
        for i in range(2)
    ]
    router = FleetRouter(
        replicas,
        policy=RouterPolicy(
            health_ttl_s=0.0, jitter_s=0.0, backoff_base_s=0.001,
            max_attempts=4, retry_budget_burst=50.0,
            retry_budget_ratio=1.0, eject_consecutive=1,
            eject_cooldown_s=1000.0,   # corpses stay ejected; reap ends them
        ),
        registry=registry, flight=flight,
    )
    # the fleet SLO: TTFT over every engine in the shared registry —
    # the flood's queueing pushes it over the (bucket-edge) threshold,
    # the short windows make the burn measurable within the bench
    fast_window_s, slow_window_s = 5.0, 10.0
    watchdog = SloWatchdog(
        [LatencyObjective(
            "fleet_ttft", "unionml_engine_ttft_ms",
            threshold_ms=ttft_threshold_ms, target=0.5, min_events=4,
            fast_burn=1.0, slow_burn=1.0,
        )],
        registry=registry,
        fast_window_s=fast_window_s, slow_window_s=slow_window_s,
    )
    aux_engines = []

    def factory():
        engine = make_engine()
        engine.warmup(params)   # a joiner must never serve cold compiles
        aux_engines.append(engine)
        return engine, params

    auto = FleetAutoscaler(
        router,
        EngineReplicaProvisioner(factory),
        policy=AutoscalerPolicy(
            min_replicas=2, max_replicas=4,
            fast_burn_threshold=1.0, slow_burn_threshold=1.0,
            sustain_evals=2,
            headroom_out=0.0,          # burn is THE out trigger here
            headroom_in=0.5,
            cooldown_out_s=2.0, cooldown_in_s=0.5,
            warm_blocks=64, reap_unhealthy_evals=2,
        ),
        slo=watchdog, usage=ledger,
        registry=registry, flight=flight,
    )
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, 16).tolist()
    distinct = [
        shared + rng.integers(1, cfg.vocab_size, 8).tolist()
        for _ in range(6)
    ]
    try:
        for e in engines:
            e.warmup(params)
        solo = {
            tuple(p): engines[0].generate(params, [p])[0] for p in distinct
        }
        # prime the SURVIVOR's cache so the first (repair) join always
        # has a warm donor — in production the fleet has served for
        # hours before a scale event; the oracle above only warmed r0
        engines[1].generate(params, [distinct[0]])
        for e in engines:
            e.reset_stats()
        ledger.reset_stats()

        results, failures, lock = [], [], threading.Lock()
        started = threading.Event()

        def client(idx):
            for j in range(n_req // clients):
                p = distinct[(idx + j) % len(distinct)]
                if idx == 0 and j == 1:
                    started.set()
                try:
                    out = router.generate(p)
                    with lock:
                        results.append((tuple(p), out))
                except BaseException as exc:   # EVERY failure counts
                    with lock:
                        failures.append(f"{type(exc).__name__}: {exc}")

        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(clients)
        ]
        flood_t0 = time.perf_counter()
        for t in threads:
            t.start()
        started.wait(timeout=120)

        scale_out_s = None
        trigger_s = None
        warm_hit_tokens = 0
        killed = False
        deadline = time.perf_counter() + 600.0
        while any(t.is_alive() for t in threads):
            if time.perf_counter() > deadline:
                raise AssertionError("flood did not complete")
            decision = auto.evaluate()
            if trigger_s is None and decision.get("burn_streak", 0) >= 1:
                # burn DETECTED (the multiwindow trigger is arming) —
                # the fast-window bar applies here; the action latency
                # additionally pays the synchronous provision+warmup
                trigger_s = time.perf_counter() - flood_t0
            if decision["decision"] == "scale_out" and scale_out_s is None:
                scale_out_s = time.perf_counter() - flood_t0
                assert decision["reason"] == "slo_burn", decision
                assert decision["warmed_blocks"] > 0, (
                    f"join was not fleet-warmed: {decision}"
                )
                # the joiner's FIRST request: a shared-prefix prompt
                # straight into the fresh engine. Its cache holds ONLY
                # imported blocks at this instant (its own inserts need
                # a completed request), so any prefill tokens saved
                # here are warm-join hits by construction.
                joiner = aux_engines[-1]
                saved0 = joiner.prefix_cache.stats()["prefill_tokens_saved"]
                probe = shared + rng.integers(1, cfg.vocab_size, 8).tolist()
                probe_out = joiner.generate(params, [probe])[0]
                warm_hit_tokens = (
                    joiner.prefix_cache.stats()["prefill_tokens_saved"]
                    - saved0
                )
                assert warm_hit_tokens > 0, (
                    "joiner's first request missed the warm prefix"
                )
                assert probe_out == engines[1].generate(params, [probe])[0]
                # mid-run KILL: wait for r0 to hold resident streams
                # (the kill must be caller-visible-but-absorbed, never
                # a free idle-replica removal), then its in-flight
                # batch dies OOM-shaped and the replica reads as dead
                k_deadline = time.perf_counter() + 60.0
                busy = 0
                while time.perf_counter() < k_deadline:
                    with engines[0]._lock:
                        busy = sum(
                            r is not None for r in engines[0]._occupant
                        )
                    if busy:
                        break
                    time.sleep(0.002)
                assert busy, "victim replica never took residents"
                fi0.arm("engine.dispatch", exc=xla_oom_error())
                replicas[0].kill()
                killed = True
            time.sleep(0.02)
        for t in threads:
            t.join(timeout=120)
        flood_s = time.perf_counter() - flood_t0

        assert scale_out_s is not None, "the flood never triggered scale-out"
        assert trigger_s is not None and trigger_s <= fast_window_s, (
            f"burn detection took {trigger_s}s — outside the "
            f"{fast_window_s:.0f}s SLO fast window"
        )
        # the action = detection + sustain + synchronous provision &
        # warmup (XLA compiles); generous allowance so CI hosts pass
        assert scale_out_s <= fast_window_s + 15.0, (
            f"scale-out took {scale_out_s:.1f}s — detection "
            f"{trigger_s:.1f}s plus an implausible provision time"
        )
        assert killed
        assert not failures, (
            f"{len(failures)} caller-visible failures (want 0): "
            f"{sorted(set(failures))[:3]}"
        )
        bad = sum(1 for key, out in results if out != solo[key])
        assert bad == 0, f"{bad}/{len(results)} responses lost token parity"
        assert len(results) == n_req

        # the corpse is reaped and replaced; then the idle fleet
        # consolidates back to baseline through the hysteresis band
        settle_deadline = time.perf_counter() + 60.0
        while time.perf_counter() < settle_deadline:
            auto.evaluate()
            members = router.health()["replicas"]
            if "r0" not in members and len(members) <= 2 and all(
                m["state"] == "live" for m in members.values()
            ):
                break
            time.sleep(0.05)
        members = router.health()["replicas"]
        assert "r0" not in members, f"corpse not reaped: {members}"
        assert len(members) == 2, f"did not scale back in: {members}"
        assert router.health()["live_replicas"] == 2

        kinds = [e["kind"] for e in flight.dump()]
        for kind in ("scale_out", "scale_reap", "scale_in", "retry"):
            assert kind in kinds, f"missing {kind} in flight record"
        decisions = {
            values: int(child.value)
            for values, child in auto._m_decisions.children()
        }
        # the burn-driven growth AND the post-kill replacement both
        # provisioned (the replacement rides whichever trigger is hot:
        # still-burning SLO, or the below-min repair after the reap)
        n_scale_outs = sum(
            v for (d, _r), v in decisions.items() if d == "scale_out"
        )
        assert n_scale_outs >= 2, decisions
        assert decisions.get(("scale_out", "slo_burn"), 0) >= 1, decisions
        print(json.dumps({
            "metric": "serve_autoscale",
            "offered": n_req,
            "clients": clients,
            "completed": len(results),
            "caller_visible_failures": len(failures),
            "token_parity": "exact",
            "flood_s": round(flood_s, 2),
            "burn_detect_latency_s": round(trigger_s, 2),
            "scale_out_latency_s": round(scale_out_s, 2),
            "slo_fast_window_s": fast_window_s,
            "warm_join_hit_tokens": int(warm_hit_tokens),
            "warmed_blocks_total": int(auto._m_warmed.value),
            "reaped": int(auto._m_reaped.value),
            "final_replicas": len(members),
            "decisions": {"|".join(k): v for k, v in decisions.items()},
            "unit": "requests",
        }))
    finally:
        auto.close()
        for e in engines + aux_engines:
            e.close()


def disagg_leg() -> None:
    """Disaggregated prefill/decode serving
    (``UNIONML_TPU_BENCH_PRESET=serve_disagg``;
    docs/serving.md "Disaggregated serving").

    Phase 1 — **colocated vs disaggregated on identical hardware**
    under MIXED long/short-prompt traffic: two fleets of two engines
    each — colocated (both serve everything, plain ``FleetRouter``)
    vs phase-split (one prefill + one decode engine sharing a host
    block store, ``DisaggRouter``). Long-prompt clients loop chunked-
    prefill streams for continuous pressure while short-prompt clients
    measure streaming TTFT (call → first chunk). Colocated, a short
    prompt behind a long admission waits out the whole chunked prefill
    (admissions serialize) and the long chunks steal dispatcher passes
    from its decode; disaggregated, long prefills live on the prefill
    engine and the decode engine admits shorts at a flat cadence.

    Estimator protocol (PR 8/13 lineage): per-short-request MIN over
    rounds (each round fully contended — the long loop runs the whole
    sweep), nearest-rank p99 across requests computed UNROUNDED, and
    the headline is the MEDIAN OF THREE independent sweeps per leg.
    Bars: disaggregated short-TTFT p99 strictly beats colocated;
    decode tokens/s (all tokens harvested / sweep wall) no worse than
    0.9x colocated (the noise floor of GIL-scheduled CPU fleets — on
    real hardware the pools are separate chips); every completion
    bit-identical to the solo oracle; 0 caller-visible failures.

    Phase 2 — **chaos mid-handoff**: on the disaggregated fleet, the
    prefill replica is killed between one request's KV export and its
    decode-side splice (export hook dies + the engine OOM-poisoned),
    then a follow-up burst runs against the dead prefill pool. Asserts
    zero caller-visible failures, exact token parity, and lease/pool
    refcounts back to baseline — degrade, never error.
    """
    import gc
    import statistics
    import threading

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu import telemetry
    from unionml_tpu.models import Llama, make_generator
    from unionml_tpu.serving.disagg import DisaggRouter
    from unionml_tpu.serving.engine import DecodeEngine
    from unionml_tpu.serving.faults import FaultInjector, xla_oom_error
    from unionml_tpu.serving.prefix_cache import RadixPrefixCache
    from unionml_tpu.serving.router import (
        EngineReplica, FleetRouter, RouterPolicy,
    )

    from unionml_tpu.models import LlamaConfig

    backend = jax.default_backend()
    if backend == "cpu":
        # max_len widened so the long bucket holds a genuinely long
        # chunked prefill (14 lead chunks — the interference source)
        cfg = LlamaConfig.tiny(vocab_size=256, max_len=512)
        module = Llama(cfg)
        tokens0 = jnp.zeros((1, 8), jnp.int32)
        params = jax.jit(module.init)(jax.random.PRNGKey(0), tokens0)["params"]
        short_n, rounds, sweeps = 12, 3, 3
        long_clients, n_long, n_new = 3, 6, 16
        buckets, chunk, chunk_steps = (16, 256), 16, 4
        # equal slot budget per fleet (6): colocated splits it evenly;
        # the phase-split fleet shapes it to the phases — decode
        # batches wide (memory-bound), prefill barely needs residency
        # at all (a prefill leg occupies its slot only until the first
        # harvest — the DistServe asymmetry)
        colo_slots, prefill_slots, decode_slots = 3, 1, 5
        short_len, long_len = 8, 224
    else:
        cfg = serving_config("serve_1p5b")
        module = Llama(cfg)
        params = random_quantized_params(module)
        short_n, rounds, sweeps = 24, 3, 3
        long_clients, n_long, n_new = 4, 8, 32
        buckets, chunk, chunk_steps = (64, 2048), 64, 8
        colo_slots, prefill_slots, decode_slots = 6, 4, 8
        short_len, long_len = 48, 1536

    rng = np.random.default_rng(0)
    shorts = [
        rng.integers(1, cfg.vocab_size, short_len).tolist()
        for _ in range(short_n)
    ]
    # the solo oracle's cache length must MATCH the engines'
    # (engine.cache_len): attention over a differently-sized masked
    # cache is bf16-numerically different, and at 200+-token random-
    # weight prompts ~5% of requests sit on a near-tie argmax that
    # flips — a mismatched oracle reads that as lost token parity
    # (root-caused in this bench's first run: engine == generator at
    # equal max_len, 0/40; generators at 272 vs 308 rows disagree on
    # exactly the requests the engine "failed"). `gen` binds lazily,
    # after the first fleet reports its cache_len.
    gen = None

    def solo_run(p):
        return np.asarray(
            gen(params, jnp.asarray([p], jnp.int32))
        )[0].tolist()

    from unionml_tpu.serving.scheduler import SchedulerConfig

    def build_engine(phase, cache, reg, slots, fi=None, mix=None,
                     eng_chunk=None):
        # per-pool tuning — the freedom disaggregation buys, and what
        # the colocated baseline structurally cannot copy:
        # - the COLOCATED engines run a FINE prefill chunk (the
        #   TTFT-optimal colocated config: long admissions yield to
        #   the decode lane every `chunk` tokens — coarser chunks
        #   would stall their own residents harder);
        # - the DECODE pool runs a COARSE chunk + a matching mixing
        #   budget (docs/robustness.md, the Sarathi knob — splices
        #   are budget-free): its long admissions are warm SPLICES,
        #   so a whole decode-leg admission collapses to ~4 cheap
        #   dispatches in one pass instead of 15 serialized ones;
        # - the PREFILL pool runs a prefill-sized budget — it has no
        #   decode lane to protect at all.
        # Bucket geometry stays identical across every engine (both
        # chunks divide the long bucket), so the solo oracle and
        # token parity are shared.
        return DecodeEngine(
            module, slots=slots, max_new_tokens=n_new,
            prompt_buckets=buckets,
            prefill_chunk=eng_chunk if eng_chunk is not None else chunk,
            chunk_steps=chunk_steps, prefix_cache=cache, phase=phase,
            registry=reg, fault_injector=fi, paged=True,
            scheduler=SchedulerConfig(
                mix_prefill_tokens=mix if mix is not None else chunk,
            ),
        )

    def run_sweeps(router, engines, label, seed_base):
        """Three sweeps; each: long clients stream a continuous
        sequence of DISTINCT prompts (real long-context traffic —
        repeats would warm the prefix cache and erase the prefill
        pressure) while the short set replays `rounds` times with
        per-request-min TTFT. Long parity is verified post-hoc
        against lazily computed solo oracles (every served long,
        exact). Returns medians over the sweeps."""
        p99s, tps, failures = [], [], []
        long_served = []
        for sweep in range(sweeps):
            for e in engines:
                e.reset_stats()
            stop = threading.Event()
            long_tokens = []

            def long_client(seed):
                crng = np.random.default_rng(seed)
                while not stop.is_set():
                    p = crng.integers(
                        1, cfg.vocab_size, long_len,
                    ).tolist()
                    try:
                        out = []
                        for c in router.generate_stream(p):
                            out.extend(c)
                        long_served.append((tuple(p), out))
                        long_tokens.append(len(out))
                    except BaseException as exc:
                        failures.append(f"long: {type(exc).__name__}")
                        return

            lts = [
                threading.Thread(
                    target=long_client,
                    args=(seed_base + sweep * long_clients + i,),
                )
                for i in range(long_clients)
            ]
            ttft_min = [math.inf] * short_n
            short_tokens = [0]
            gc_was = gc.isenabled()
            gc.disable()
            t_sweep0 = time.perf_counter()
            for t in lts:
                t.start()
            try:
                for _ in range(rounds):
                    for i, p in enumerate(shorts):
                        try:
                            t0 = time.perf_counter()
                            stream = router.generate_stream(p)
                            out = []
                            for j, c in enumerate(stream):
                                if j == 0:
                                    dt = time.perf_counter() - t0
                                    ttft_min[i] = min(ttft_min[i], dt)
                                out.extend(c)
                            if out != solo[tuple(p)]:
                                failures.append("short token mismatch")
                            short_tokens[0] += len(out)
                        except BaseException as exc:
                            failures.append(
                                f"short: {type(exc).__name__}"
                            )
            finally:
                stop.set()
                for t in lts:
                    t.join(timeout=120)
                if gc_was:
                    gc.enable()
            wall = time.perf_counter() - t_sweep0
            v = sorted(ttft_min)
            p99 = v[max(0, math.ceil(0.99 * len(v)) - 1)]  # UNROUNDED
            p99s.append(p99)
            tps.append((short_tokens[0] + sum(long_tokens)) / wall)
        # exact parity for EVERY served long (prompts are distinct, so
        # this is one solo oracle run per long request)
        for key, out in long_served:
            if out != solo.setdefault(key, solo_run(list(key))):
                failures.append("long token mismatch")
        return (
            statistics.median(p99s), statistics.median(tps),
            failures, p99s, tps, len(long_served),
        )

    # ---- colocated fleet: 2 engines, both serve everything ----------
    reg_c = telemetry.MetricsRegistry()
    colo_engines = [
        build_engine(
            "colocated", RadixPrefixCache(registry=reg_c), reg_c,
            colo_slots,
        )
        for _ in range(2)
    ]
    colo = FleetRouter(
        [
            EngineReplica(colo_engines[i], params, name=f"c{i}")
            for i in range(2)
        ],
        policy=RouterPolicy(health_ttl_s=0.05),
        registry=reg_c, flight=telemetry.FlightRecorder(),
    )
    # the oracle, at the engines' exact cache geometry (see above) —
    # slots don't enter cache_len, so every engine in BOTH fleets
    # shares it (asserted when the disagg fleet builds)
    oracle_len = colo_engines[0].cache_len
    gen = make_generator(module, max_new_tokens=n_new, max_len=oracle_len)
    solo = {tuple(p): solo_run(p) for p in shorts}
    try:
        for e in colo_engines:
            e.warmup(params)
        (colo_p99, colo_tps, colo_fail, colo_p99s, colo_tpss,
         colo_longs) = run_sweeps(colo, colo_engines, "colocated", 10_000)
    finally:
        for e in colo_engines:
            e.close()
    assert not colo_fail, colo_fail[:3]

    # ---- disaggregated fleet: 1 prefill + 1 decode, one store ------
    reg_d = telemetry.MetricsRegistry()
    store = RadixPrefixCache(registry=reg_d)
    fi = FaultInjector()
    coarse = chunk * 4
    pre = build_engine(
        "prefill", store, reg_d, prefill_slots, fi, mix=buckets[-1],
        eng_chunk=coarse,
    )
    dec = build_engine(
        "decode", store, reg_d, decode_slots, mix=coarse,
        eng_chunk=coarse,
    )
    disagg = DisaggRouter(
        [EngineReplica(pre, params, name="p0"),
         EngineReplica(dec, params, name="d0")],
        handoff_min_tokens=buckets[0] + 1,  # shorts stay single-leg
        policy=RouterPolicy(
            health_ttl_s=0.05, backoff_base_s=0.001, jitter_s=0.0,
        ),
        registry=reg_d, flight=telemetry.FlightRecorder(),
    )
    try:
        for e in (pre, dec):
            # one oracle serves both fleets only because the cache
            # geometry is identical — a drifted knob would silently
            # turn tie-flips into "parity failures" again
            assert e.cache_len == oracle_len, (e.cache_len, oracle_len)
            e.warmup(params)
        (dis_p99, dis_tps, dis_fail, dis_p99s, dis_tpss,
         dis_longs) = run_sweeps(disagg, (pre, dec), "disagg", 20_000)
        assert not dis_fail, dis_fail[:3]

        print(json.dumps({
            "metric": "serve_disagg_short_ttft_p99_ms",
            "colocated": round(colo_p99 * 1e3, 3),
            "disaggregated": round(dis_p99 * 1e3, 3),
            "value": round(dis_p99 * 1e3, 3),
            "sweeps_colocated_ms": [round(x * 1e3, 3) for x in colo_p99s],
            "sweeps_disagg_ms": [round(x * 1e3, 3) for x in dis_p99s],
            "speedup": round(colo_p99 / max(dis_p99, 1e-9), 2),
            "unit": "ms",
        }))
        print(json.dumps({
            "metric": "serve_disagg_decode_tokens_per_sec",
            "colocated": round(colo_tps, 1),
            "disaggregated": round(dis_tps, 1),
            "value": round(dis_tps, 1),
            "ratio": round(dis_tps / max(colo_tps, 1e-9), 3),
            "long_requests": {"colocated": colo_longs,
                              "disaggregated": dis_longs},
            "unit": "tokens/s",
        }))
        assert dis_p99 < colo_p99, (
            f"disaggregated short TTFT p99 {dis_p99 * 1e3:.2f} ms does "
            f"not beat colocated {colo_p99 * 1e3:.2f} ms"
        )
        assert dis_tps >= 0.9 * colo_tps, (
            f"decode throughput regressed: {dis_tps:.1f} vs colocated "
            f"{colo_tps:.1f} tokens/s (bar: >= 0.9x, the CPU fleet "
            "noise floor)"
        )

        # ---- phase 2: prefill replica killed mid-handoff -----------
        p0 = disagg.replica_handle("p0")
        orig_export = p0.export_request_blocks

        def export_and_die(prompt):
            entries = orig_export(prompt)
            # the kill window: KV exported, splice not yet — the
            # prefill engine OOM-poisons and every later prefill-pool
            # call fails
            fi.arm("engine.prefill", exc=xla_oom_error())
            p0.prefill_export = lambda *a, **k: (
                (_ for _ in ()).throw(RuntimeError("prefill dead"))
            )
            p0.export_request_blocks = lambda *a, **k: (
                (_ for _ in ()).throw(RuntimeError("prefill dead"))
            )
            raise RuntimeError("prefill process died mid-handoff")

        # force the long path two-leg so the handoff actually fires
        p0.export_request_blocks = export_and_die
        # distinct stores now, or the shared store would hide the kill
        dec.prefix_cache = RadixPrefixCache(registry=reg_d)
        disagg.transfer = True
        crng = np.random.default_rng(99)
        chaos_prompts = [
            crng.integers(1, cfg.vocab_size, long_len).tolist()
            for _ in range(3)
        ] + shorts[:4]
        chaos_fail, chaos_done = [], []
        for p in chaos_prompts:
            try:
                out = []
                for c in disagg.generate_stream(p):
                    out.extend(c)
                if out != solo.setdefault(tuple(p), solo_run(p)):
                    chaos_fail.append("token mismatch")
                chaos_done.append(tuple(p))
            except BaseException as exc:
                chaos_fail.append(f"{type(exc).__name__}: {exc}")
        assert not chaos_fail, chaos_fail[:3]
        assert len(chaos_done) == len(chaos_prompts)

        # lease/pool refcounts back to baseline on the survivor
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            s = dec.kv_pool.stats()
            if s["blocks_in_use"] == 0 and s["blocks_reserved"] == 0:
                break
            time.sleep(0.05)
        s = dec.kv_pool.stats()
        assert s["blocks_in_use"] == 0 and s["blocks_reserved"] == 0, s
        leaked = []
        for cache in (dec.prefix_cache, store):
            stack = list(cache._root.children.values())
            while stack:
                node = stack.pop()
                stack.extend(node.children.values())
                if node.refcount != 0:
                    leaked.append(node.refcount)
        assert not leaked, f"leaked lease refcounts: {leaked}"
        print(json.dumps({
            "metric": "serve_disagg_chaos",
            "requests": len(chaos_done),
            "caller_visible_failures": 0,
            "token_parity": "exact",
            "lease_refcounts": "baseline",
            "pool_blocks": "baseline",
        }))
        print(json.dumps({
            "metric": "serve_disagg_summary",
            "short_ttft_p99_speedup": round(
                colo_p99 / max(dis_p99, 1e-9), 2
            ),
            "decode_tps_ratio": round(dis_tps / max(colo_tps, 1e-9), 3),
            "chaos": "0 caller-visible failures, parity exact",
        }))
    finally:
        pre.close()
        dec.close()


def fleet_obs_leg() -> None:
    """Fleet observability plane
    (``UNIONML_TPU_BENCH_PRESET=serve_fleet_obs``;
    docs/observability.md "Fleet observability").

    Phase 1 — **the plane under load**: a 3-replica engine fleet
    behind a router with cross-hop trace stitching ON, concurrent
    clients streaming requests while a background scraper hammers the
    federated ``/metrics`` merge. Asserts ZERO caller-visible
    failures, exact token parity vs the solo oracle, every replica's
    series present under its ``replica`` label in the federated body,
    and a probe request's stitched timeline complete (route root,
    pick/attempt spans, engine timelines parented under the attempt
    that dispatched them, one trace id).

    Phase 2 — **plane overhead**: the same fleet serves the same
    requests with the plane OFF (``router.tracer = None``) and ON,
    paired PER REQUEST in alternating order (the PR 8 estimator
    protocol: whole-pass legs drift percents at minute scale; pairing
    cancels it), per-request MIN over rounds, nearest-rank p99
    computed UNROUNDED over enough requests that the p99 is not the
    sample max — and the bar held against the MEDIAN of three
    independent sweeps (a single 120×20 order statistic still swings
    ~±1.5% from thread-scheduling jitter; measured medians 0.4–1.0%
    across solo runs). The scraper stops first — federation is
    scrape-path work that never rides a request, and a scrape landing
    inside one leg of a pair is exactly the tail noise pairing exists
    to cancel. Asserts ≤ 2% p99 and bit-identical tokens on both
    legs.
    """
    import gc
    import threading

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu import telemetry
    from unionml_tpu.models import Llama
    from unionml_tpu.serving.engine import DecodeEngine
    from unionml_tpu.serving.router import (
        EngineReplica, FleetRouter, RouterPolicy, make_router_app,
    )

    backend = jax.default_backend()
    if backend == "cpu":
        cfg = serving_config("tiny")
        module = Llama(cfg)
        tokens0 = jnp.zeros((1, 8), jnp.int32)
        params = jax.jit(module.init)(jax.random.PRNGKey(0), tokens0)["params"]
        n_req, clients, slots = 48, 6, 2
        new_tokens, bucket, chunk_steps = 16, 16, 4
        overhead_reqs, overhead_rounds = 40, 6
    else:
        cfg = serving_config("serve_1p5b")
        module = Llama(cfg)
        params = random_quantized_params(module)
        n_req, clients, slots = 192, 24, 8
        new_tokens, bucket, chunk_steps = 32, 64, 8
        overhead_reqs, overhead_rounds = 120, 8

    n_replicas = 3
    # estimator hardening (the PR 8 lessons, plus this preset's own
    # measured spread): 120+ requests so nearest-rank p99 is the
    # 2nd-worst min rather than the sample max, and 20 rounds on CPU —
    # at 10 rounds the per-request min still carries ±3-5% of harvester
    # thread-scheduling jitter at the p99, swamping a 2% bar (measured:
    # 10-round runs spread -6.5%..+5.6%, 20-round runs -0.1%..+1.8%)
    overhead_reqs = max(overhead_reqs, 120)
    if backend == "cpu":
        overhead_rounds = max(overhead_rounds, 20)
    tracer = telemetry.TraceRecorder()
    app_registry = telemetry.MetricsRegistry()
    flight = telemetry.FlightRecorder()
    # per-engine registries: the federation merge has real per-replica
    # bodies to label (the shared-registry path is the degenerate case)
    engines = [
        DecodeEngine(
            module, slots=slots, max_new_tokens=new_tokens,
            prompt_buckets=(bucket,), chunk_steps=chunk_steps,
            max_queue_depth=64, registry=telemetry.MetricsRegistry(),
            tracer=tracer,
        )
        for _ in range(n_replicas)
    ]
    router = FleetRouter(
        [
            EngineReplica(engines[i], params, name=f"r{i}")
            for i in range(n_replicas)
        ],
        policy=RouterPolicy(health_ttl_s=0.05),
        registry=app_registry,
        flight=flight,
        tracer=tracer,
    )
    app = make_router_app(
        router, registry=app_registry, tracer=tracer, flight=flight,
    )
    rng = np.random.default_rng(0)
    distinct = [
        rng.integers(1, cfg.vocab_size, bucket // 2).tolist()
        for _ in range(8)
    ]
    scrape_stop = threading.Event()
    scrape_bodies = [0]

    def scraper():
        while not scrape_stop.is_set():
            body = app.metrics_text()
            if 'replica="r0"' in body:
                scrape_bodies[0] += 1
            scrape_stop.wait(0.05)

    scraper_thread = threading.Thread(target=scraper, daemon=True)
    try:
        for e in engines:
            e.warmup(params)
        solo = {
            tuple(p): engines[0].generate(params, [p])[0] for p in distinct
        }
        scraper_thread.start()

        # ---- phase 1: loaded run, plane ON ----
        results, failures, lock = [], [], threading.Lock()

        def client(idx):
            for p in (
                distinct[(idx + k) % len(distinct)]
                for k in range(n_req // clients)
            ):
                try:
                    out = router.generate(p)
                    with lock:
                        results.append((tuple(p), out))
                except BaseException as exc:  # EVERY failure counts
                    with lock:
                        failures.append(f"{type(exc).__name__}: {exc}")

        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in threads), "clients hung"
        assert not failures, (
            f"{len(failures)} caller-visible failures (want 0): "
            f"{sorted(set(failures))[:3]}"
        )
        bad = sum(1 for key, out in results if out != solo[key])
        assert bad == 0, f"{bad}/{len(results)} responses lost token parity"

        # a probe STREAMING request right after the flood: its routing
        # timeline is now deterministically the NEWEST route timeline,
        # and the stitched-timeline acceptance rides it
        probe_prompt = distinct[0]
        probe_tokens = [
            t for c in router.generate_stream(probe_prompt) for t in c
        ]
        assert probe_tokens == solo[tuple(probe_prompt)]
        probe_rid = next(
            rid_done
            for rid_done, meta_done, _ in reversed(tracer._done)
            if meta_done.get("kind") == "route"
        )
        # the probe's engine timeline retires on the harvester thread
        # moments after the stream's last chunk: bounded wait
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            doc, _ = app.debug_trace(rid=probe_rid)
            if any(
                s.get("root") and s["kind"] == "stream"
                for s in doc["spans"]
            ):
                break
            time.sleep(0.01)

        body = app.metrics_text()
        for i in range(n_replicas):
            assert f'replica="r{i}"' in body, (
                f"federated body is missing replica r{i}"
            )
        assert "unionml_router_requests_total" in body
        assert scrape_bodies[0] > 0, "no federated scrape completed"

        doc, _ = app.debug_trace(rid=probe_rid)
        assert doc["trace_id"], "probe request has no stitched trace"
        span_names = [s["name"] for s in doc["spans"]]
        assert "route" in span_names and "pick" in span_names, span_names
        attempts = {
            s["span_id"] for s in doc["spans"] if s["name"] == "attempt"
        }
        stream_roots = [
            s for s in doc["spans"]
            if s.get("root") and s["kind"] == "stream"
        ]
        assert stream_roots, "engine timeline missing from the stitch"
        assert all(
            s["parent_span_id"] in attempts for s in stream_roots
        ), "engine timelines not parented under the dispatch attempt"
        print(json.dumps({
            "metric": "serve_fleet_obs_plane_under_load",
            "replicas": n_replicas,
            "offered": n_req + 1,
            "completed": len(results) + 1,
            "caller_visible_failures": len(failures),
            "federated_scrapes": scrape_bodies[0],
            "stitched_spans": len(doc["spans"]),
            "token_parity": "exact",
            "unit": "requests",
        }))

        # ---- phase 2: paired per-request plane on/off overhead ----
        # the scraper stops first: federation is scrape-path work (its
        # merge cost never rides a request), and a background scrape
        # landing inside one leg of a pair is exactly the tail noise
        # the paired protocol exists to cancel
        scrape_stop.set()
        scraper_thread.join(timeout=5.0)
        prompts = [
            rng.integers(1, cfg.vocab_size, bucket // 2).tolist()
            for _ in range(overhead_reqs)
        ]

        def p99(vals):  # nearest-rank, UNROUNDED (0.1 ms rounding is
            v = sorted(vals)  # percents of this workload)
            return v[max(0, math.ceil(0.99 * len(v)) - 1)]

        def sweep(sweep_i):
            """One full paired measurement; even a 120×20 min-of-rounds
            p99 still swings ~±1.5% from thread-scheduling jitter on a
            CPU host, so the BAR is held against the median of three
            independent sweeps — the single-order-statistic estimate
            is the noise, not the plane."""
            off_min = [math.inf] * overhead_reqs
            on_min = [math.inf] * overhead_reqs
            token_mismatch = 0
            gc_was_enabled = gc.isenabled()
            gc.disable()
            try:
                for r in range(overhead_rounds):
                    for i, p in enumerate(prompts):
                        legs = [("off", i), ("on", i)]
                        if (r + i + sweep_i) % 2:
                            legs.reverse()  # drift cancels in the pair
                        outs = {}
                        for legname, idx in legs:
                            router.tracer = (
                                tracer if legname == "on" else None
                            )
                            t0 = time.perf_counter()
                            out = router.generate(p)
                            dt = time.perf_counter() - t0
                            mins = on_min if legname == "on" else off_min
                            mins[idx] = min(mins[idx], dt)
                            outs[legname] = out
                        if outs["off"] != outs["on"]:
                            token_mismatch += 1
            finally:
                router.tracer = tracer
                if gc_was_enabled:
                    gc.enable()
            assert token_mismatch == 0, (
                f"{token_mismatch} plane-on responses diverged from "
                "plane-off"
            )
            return p99(off_min), p99(on_min)

        sweeps = [sweep(s) for s in range(3)]
        overheads = sorted(
            (on99 - off99) / off99 if off99 > 0 else 0.0
            for off99, on99 in sweeps
        )
        overhead = overheads[1]  # median of 3 independent sweeps
        off99, on99 = sweeps[0]
        assert overhead <= 0.02, (
            f"observability plane adds {overhead:.1%} median p99 "
            f"(sweeps: {', '.join(f'{o:.2%}' for o in overheads)}); "
            "bar is 2%"
        )
        print(json.dumps({
            "metric": "serve_fleet_obs_p99_overhead",
            "requests": overhead_reqs,
            "rounds": overhead_rounds,
            "sweeps": 3,
            "sweep_overheads_pct": [
                round(o * 100, 2) for o in overheads
            ],
            "plane_off_p99_ms": round(off99 * 1e3, 3),
            "plane_on_p99_ms": round(on99 * 1e3, 3),
            "value": round(overhead * 100, 2),
            "token_parity": "exact",
            "unit": "percent",
        }))
        print(json.dumps({
            "metric": "serve_fleet_obs_summary",
            "plane_under_load": "0 caller-visible failures, parity exact",
            "federation": f"{n_replicas} replicas under one scrape",
            "p99_overhead_pct": round(overhead * 100, 2),
        }))
    finally:
        scrape_stop.set()
        scraper_thread.join(timeout=5.0)
        for e in engines:
            e.close()


def perf_leg() -> None:
    """Serving goodput plane overhead + tail attribution
    (``UNIONML_TPU_BENCH_PRESET=serve_perf``; docs/observability.md
    "Serving goodput & tail attribution").

    Phase 1 — **the plane live**: a single-replica router fleet (the
    engine, router app, and plane share one registry/flight/tracer, so
    the tail endpoints resolve without federation) serves a concurrent
    flood with the goodput plane ON. Asserts ZERO caller-visible
    failures, exact token parity vs the solo oracle, a sane
    fleet-merged ``/debug/goodput`` (ratios recomputed on summed
    slot-step ledgers, goodput in (0, 1]), and a populated per-token
    ITL histogram.

    Phase 2 — **plane overhead**: the same requests with the plane OFF
    and ON, paired PER REQUEST in alternating order on the SAME engine
    instance via the ``engine.perf`` setter seam (two
    separately-constructed engines differ by several percent from
    thread/allocator placement alone, swamping a 1% bar). Flight ring
    and tracer stay ON in both legs — only the goodput plane toggles,
    so the delta is the plane's own cost. Same paired estimator as the
    fleet-obs leg — per-request MIN over rounds, nearest-rank p99
    computed UNROUNDED, three independent sweeps — but the BAR is held
    against the p99 of the per-request mins POOLED across all three
    sweeps rather than the median of per-sweep p99s: the plane's
    measured cost (~26 us/request, ~0.3% of a tiny-model CPU request)
    sits an order of magnitude below the per-sweep p99's own
    scheduling noise on the 1-core host (measured per-sweep deltas
    swing ±2-7% while the pooled estimate settles at +0.4-0.9% from
    32 pooled rounds on), so the median-of-3 verdict would be a coin
    flip about the host, not the plane. Per-sweep overheads and their
    median are still reported as diagnostics. Asserts <= 1% pooled p99
    and bit-identical tokens, and per sweep runs one streaming tail
    probe whose decode exemplar resolves ``/debug/tail`` → per-phase
    segments → ``/debug/trace`` (histogram bucket to stitched timeline
    in one hop).
    """
    import gc
    import threading

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu import telemetry
    from unionml_tpu.models import Llama
    from unionml_tpu.serving.engine import DecodeEngine
    from unionml_tpu.serving.router import (
        EngineReplica, FleetRouter, RouterPolicy, make_router_app,
    )

    backend = jax.default_backend()
    if backend == "cpu":
        cfg = serving_config("tiny")
        module = Llama(cfg)
        tokens0 = jnp.zeros((1, 8), jnp.int32)
        params = jax.jit(module.init)(jax.random.PRNGKey(0), tokens0)["params"]
        n_req, clients, slots = 48, 6, 2
        new_tokens, bucket, chunk_steps = 16, 16, 4
        overhead_reqs, overhead_rounds = 40, 6
    else:
        cfg = serving_config("serve_1p5b")
        module = Llama(cfg)
        params = random_quantized_params(module)
        n_req, clients, slots = 192, 24, 8
        new_tokens, bucket, chunk_steps = 32, 64, 8
        overhead_reqs, overhead_rounds = 120, 8

    # same estimator hardening as the fleet-obs leg, and MORE binding
    # here: the bar is 1%, half the fleet-obs bar, while the plane's
    # measured per-request cost is ~26 us (~0.3% of a tiny-model CPU
    # request) — so the verdict hinges on min-over-rounds convergence,
    # not the plane. 32 rounds per sweep × 3 sweeps = 96 pooled tries
    # per request per leg, where the pooled p99 delta was measured
    # stable (+0.4-0.9%); the per-sweep p99s individually still swing
    # ±2-7% on the 1-core host and are reported as diagnostics only
    overhead_reqs = max(overhead_reqs, 120)
    if backend == "cpu":
        overhead_rounds = max(overhead_rounds, 32)
    registry = telemetry.MetricsRegistry()
    flight = telemetry.FlightRecorder()
    tracer = telemetry.TraceRecorder()
    engine = DecodeEngine(
        module, slots=slots, max_new_tokens=new_tokens,
        prompt_buckets=(bucket,), chunk_steps=chunk_steps,
        max_queue_depth=64, registry=registry, flight=flight,
        tracer=tracer,
    )
    router = FleetRouter(
        [EngineReplica(engine, params, name="r0")],
        policy=RouterPolicy(health_ttl_s=0.05),
        registry=registry,
        flight=flight,
        tracer=tracer,
    )
    app = make_router_app(
        router, registry=registry, tracer=tracer, flight=flight,
    )
    plane = engine.perf
    assert plane is not None, (
        "goodput plane should be ON by default while introspect=True"
    )
    rng = np.random.default_rng(0)
    distinct = [
        rng.integers(1, cfg.vocab_size, bucket // 2).tolist()
        for _ in range(8)
    ]
    try:
        engine.warmup(params)
        solo = {tuple(p): engine.generate(params, [p])[0] for p in distinct}

        # ---- phase 1: loaded run, plane ON ----
        results, failures, lock = [], [], threading.Lock()

        def client(idx):
            for p in (
                distinct[(idx + k) % len(distinct)]
                for k in range(n_req // clients)
            ):
                try:
                    out = router.generate(p)
                    with lock:
                        results.append((tuple(p), out))
                except BaseException as exc:  # EVERY failure counts
                    with lock:
                        failures.append(f"{type(exc).__name__}: {exc}")

        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in threads), "clients hung"
        assert not failures, (
            f"{len(failures)} caller-visible failures (want 0): "
            f"{sorted(set(failures))[:3]}"
        )
        bad = sum(1 for key, out in results if out != solo[key])
        assert bad == 0, f"{bad}/{len(results)} responses lost token parity"

        goodput = app.debug_goodput()
        fleet = goodput["fleet"]
        assert fleet["replicas"] == 1
        assert sum(fleet["passes"].values()) > 0, "no dispatcher passes"
        assert 0.0 < fleet["goodput_ratio"] <= 1.0, fleet
        assert fleet["occupancy_ratio"] >= fleet["goodput_ratio"], fleet
        assert fleet["tokens"] > 0, fleet
        itl = next(
            f for f in registry.collect()
            if f.name == "unionml_engine_itl_ms"
        )
        itl_n = sum(len(child.samples()) for _, child in itl.children())
        assert itl_n > 0, "per-token ITL histogram is empty under load"
        print(json.dumps({
            "metric": "serve_perf_plane_under_load",
            "offered": n_req,
            "completed": len(results),
            "caller_visible_failures": len(failures),
            "goodput_ratio": fleet["goodput_ratio"],
            "occupancy_ratio": fleet["occupancy_ratio"],
            "itl_observations": itl_n,
            "token_parity": "exact",
            "unit": "requests",
        }))

        # ---- phase 2: paired per-request plane on/off overhead ----
        prompts = [
            rng.integers(1, cfg.vocab_size, bucket // 2).tolist()
            for _ in range(overhead_reqs)
        ]

        def p99(vals):  # nearest-rank, UNROUNDED (0.1 ms rounding is
            v = sorted(vals)  # percents of this workload)
            return v[max(0, math.ceil(0.99 * len(v)) - 1)]

        def tail_probe(sweep_i):
            """One streaming request, then its decode exemplar walked
            /debug/tail → segments → /debug/trace. Runs with the plane
            ON (exemplar capture is plane-gated); the finish event and
            exemplar land on the harvester thread moments after the
            last chunk, so the resolution is a bounded wait."""
            probe = distinct[sweep_i % len(distinct)]
            streams_before = sum(
                1 for _, meta_done, _ in tracer._done
                if meta_done.get("kind") == "stream"
            )
            out = [t for c in router.generate_stream(probe) for t in c]
            assert out == solo[tuple(probe)], "tail probe lost parity"
            deadline = time.monotonic() + 10.0
            row = None
            while time.monotonic() < deadline:
                stream_rids = [
                    rid_done for rid_done, meta_done, _ in tracer._done
                    if meta_done.get("kind") == "stream"
                ]
                if len(stream_rids) > streams_before:
                    rows = app.debug_tail(
                        metric="unionml_engine_decode_ms", n=64,
                    )["requests"]
                    row = next(
                        (
                            r for r in rows
                            if r["rid"] == stream_rids[-1]
                            and "segments" in r
                        ),
                        None,
                    )
                    if row is not None:
                        break
                time.sleep(0.01)
            assert row is not None, (
                "tail probe's decode exemplar never became resolvable "
                "via /debug/tail"
            )
            assert row["segments"]["tokens"] == new_tokens, row
            assert row["segments"]["itl_tokens"] == new_tokens - 1, row
            doc, _ = app.debug_trace(rid=row["rid"])
            assert doc["trace_id"] and doc["spans"], (
                "tail exemplar rid did not resolve in /debug/trace"
            )

        def sweep(sweep_i):
            """One full paired measurement; returns the per-request
            min arrays so the caller can both report this sweep's own
            p99 delta and pool the mins across sweeps for the bar."""
            off_min = [math.inf] * overhead_reqs
            on_min = [math.inf] * overhead_reqs
            token_mismatch = 0
            gc_was_enabled = gc.isenabled()
            gc.collect()  # every sweep starts from the same heap state
            gc.disable()
            try:
                for r in range(overhead_rounds):
                    for i, p in enumerate(prompts):
                        legs = [("off", i), ("on", i)]
                        if (r + i + sweep_i) % 2:
                            legs.reverse()  # drift cancels in the pair
                        outs = {}
                        for legname, idx in legs:
                            # the setter seam: swap only while idle —
                            # requests here are strictly serial
                            engine.perf = (
                                plane if legname == "on" else None
                            )
                            t0 = time.perf_counter()
                            out = router.generate(p)
                            dt = time.perf_counter() - t0
                            mins = on_min if legname == "on" else off_min
                            mins[idx] = min(mins[idx], dt)
                            outs[legname] = out
                        if outs["off"] != outs["on"]:
                            token_mismatch += 1
            finally:
                engine.perf = plane
                if gc_was_enabled:
                    gc.enable()
            assert token_mismatch == 0, (
                f"{token_mismatch} plane-on responses diverged from "
                "plane-off"
            )
            tail_probe(sweep_i)
            return off_min, on_min

        sweeps = [sweep(s) for s in range(3)]
        sweep_overheads = sorted(
            (p99(on_m) - p99(off_m)) / p99(off_m)
            for off_m, on_m in sweeps
        )
        pooled_off = [
            min(off_m[i] for off_m, _ in sweeps)
            for i in range(overhead_reqs)
        ]
        pooled_on = [
            min(on_m[i] for _, on_m in sweeps)
            for i in range(overhead_reqs)
        ]
        off99, on99 = p99(pooled_off), p99(pooled_on)
        overhead = (on99 - off99) / off99 if off99 > 0 else 0.0
        assert overhead <= 0.01, (
            f"goodput plane adds {overhead:.2%} pooled p99 "
            f"(per-sweep: {', '.join(f'{o:.2%}' for o in sweep_overheads)}); "
            "bar is 1%"
        )
        print(json.dumps({
            "metric": "serve_perf_p99_overhead",
            "requests": overhead_reqs,
            "rounds": overhead_rounds,
            "sweeps": 3,
            "sweep_overheads_pct": [
                round(o * 100, 2) for o in sweep_overheads
            ],
            "sweep_overhead_median_pct": round(
                sweep_overheads[1] * 100, 2
            ),
            "plane_off_p99_ms": round(off99 * 1e3, 3),
            "plane_on_p99_ms": round(on99 * 1e3, 3),
            "value": round(overhead * 100, 2),
            "token_parity": "exact",
            "unit": "percent",
        }))
        print(json.dumps({
            "metric": "serve_perf_summary",
            "plane_under_load": "0 caller-visible failures, parity exact",
            "goodput_ratio": fleet["goodput_ratio"],
            "tail_probes_resolved": 3,
            "p99_overhead_pct": round(overhead * 100, 2),
        }))
    finally:
        engine.close()


def rollout_leg() -> None:
    """Zero-downtime model lifecycle under flood
    (``UNIONML_TPU_BENCH_PRESET=serve_rollout``;
    docs/robustness.md "Rollouts & rollback").

    A 2-engine fleet serves continuous background flood plus a
    measured short-request set. Leg 1 measures the STEADY-STATE
    streaming TTFT baseline. Leg 2 repeats the identical measurement
    while a full release lifecycle churns underneath each sweep: a bad
    version (negated weights) is rolled forward, its shadow diffs
    catch the parity regression and auto-roll it back; then a clean
    version rolls forward, bakes through shadow matches, and is
    operator-promoted through rolling drain → bind → rejoin.

    Estimator protocol (PR 8/13 lineage): per-request MIN over rounds
    (each round fully contended), nearest-rank p99 across requests
    computed UNROUNDED, headline = MEDIAN OF THREE sweeps per leg.
    Bars: 0 caller-visible failures across BOTH legs (rollback and
    promotion drains retry inside the router envelope — callers never
    see them); every completed request bit-identical to the solo
    oracle (canary_percent=0: live traffic is never steered onto the
    canary, and shadow dispatches are free-riders); lifecycle-churn
    p99 within 2.0x of steady-state (per-request min absorbs the
    drain windows — the bar says churn costs tail, never availability
    or correctness); after the last sweep the fleet serves the final
    promoted version with the canary pool reaped and the decision
    counters telling the whole story.
    """
    import gc
    import statistics
    import tempfile
    import threading

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu import telemetry
    from unionml_tpu.models import Llama, LlamaConfig, make_generator
    from unionml_tpu.serving.autoscaler import EngineReplicaProvisioner
    from unionml_tpu.serving.engine import DecodeEngine
    from unionml_tpu.serving.prefix_cache import RadixPrefixCache
    from unionml_tpu.serving.rollout import (
        RolloutController, RolloutPolicy, VersionRegistry,
    )
    from unionml_tpu.serving.router import (
        EngineReplica, FleetRouter, RouterPolicy,
    )

    backend = jax.default_backend()
    if backend == "cpu":
        cfg = LlamaConfig.tiny(vocab_size=256)
        module = Llama(cfg)
        tokens0 = jnp.zeros((1, 8), jnp.int32)
        params = jax.jit(module.init)(jax.random.PRNGKey(0), tokens0)["params"]
        short_n, rounds, sweeps = 10, 3, 3
        flood_clients, n_new, slots = 2, 8, 4
        buckets, chunk_steps, short_len = (16,), 4, 8
    else:
        cfg = serving_config("serve_1p5b")
        module = Llama(cfg)
        params = random_quantized_params(module)
        short_n, rounds, sweeps = 24, 3, 3
        flood_clients, n_new, slots = 4, 32, 8
        buckets, chunk_steps, short_len = (64,), 8, 48

    # same VALUES, new identity: promotion exercises the full drain →
    # bind → rejoin machinery without changing one emitted token
    params_good = jax.tree_util.tree_map(lambda x: jnp.array(x), params)
    params_bad = jax.tree_util.tree_map(lambda x: -x, params)

    reg = telemetry.MetricsRegistry()
    flight = telemetry.FlightRecorder()

    def make_engine():
        return DecodeEngine(
            module, slots=slots, max_new_tokens=n_new,
            prompt_buckets=buckets, chunk_steps=chunk_steps,
            prefix_cache=RadixPrefixCache(registry=reg), registry=reg,
        )

    engines = [make_engine() for _ in range(2)]
    canary_engines = []

    def factory():
        e = make_engine()
        canary_engines.append(e)
        return e, params

    router = FleetRouter(
        [EngineReplica(engines[i], params, name=f"r{i}") for i in range(2)],
        policy=RouterPolicy(
            health_ttl_s=0.05, backoff_base_s=0.001, jitter_s=0.0,
        ),
        registry=reg, flight=flight,
    )

    vroot = tempfile.mkdtemp(prefix="unionml_rollout_bench_")
    vreg = VersionRegistry(vroot)
    for k in range(1, sweeps + 1):
        vreg.publish(f"bad-{k}", {"w": np.zeros(2, np.float32)})
        vreg.publish(f"good-{k}", {"w": np.ones(2, np.float32)})
    ctl = RolloutController(
        router, EngineReplicaProvisioner(factory), vreg,
        policy=RolloutPolicy(
            canary_replicas=1, canary_percent=0.0, shadow=True,
            shadow_queue=128, bake_evals=2, sustain_evals=2,
            auto_promote=False, warm_blocks=0, drain_timeout_s=60.0,
        ),
        params_loader=lambda v: (
            params_bad if v.startswith("bad") else params_good
        ),
        registry=reg, flight=flight,
    )

    # the solo oracle at the engines' exact cache geometry (the disagg
    # leg's root cause — a padded-length mismatch flips near-tie
    # argmaxes and reads as lost parity)
    oracle_len = engines[0].cache_len
    gen = make_generator(module, max_new_tokens=n_new, max_len=oracle_len)
    rng = np.random.default_rng(7)
    shorts = [
        rng.integers(1, cfg.vocab_size, short_len).tolist()
        for _ in range(short_n)
    ]
    solo = {
        tuple(p): np.asarray(
            gen(params, jnp.asarray([p], jnp.int32))
        )[0].tolist()
        for p in shorts
    }

    failures: list = []

    def run_sweep(churn_version_k=None):
        """One sweep: background flood + measured rounds; when
        ``churn_version_k`` is set, a choreographer thread drives the
        full bad-rollback + good-promote lifecycle underneath."""
        stop = threading.Event()

        def flood_client(seed):
            crng = np.random.default_rng(seed)
            while not stop.is_set():
                p = shorts[int(crng.integers(0, short_n))]
                try:
                    out = router.generate(p)
                    if out != solo[tuple(p)]:
                        failures.append("flood token mismatch")
                except BaseException as exc:
                    failures.append(f"flood: {type(exc).__name__}")
                    return

        def choreograph(k):
            try:
                deadline = time.monotonic() + 120.0
                ctl.start_rollout(f"bad-{k}")
                # provisioning ticks through; then shadow divergences
                # sustain into the automatic rollback
                while time.monotonic() < deadline:
                    d = ctl.dashboard()
                    if d["stage"] == "idle" and any(
                        h["reason"] == "parity_regression"
                        for h in d["history"]
                    ):
                        break
                    time.sleep(0.02)
                else:
                    failures.append("bad version did not roll back")
                    return
                ctl.start_rollout(f"good-{k}")
                while time.monotonic() < deadline:
                    d = ctl.dashboard()
                    if d["stage"] == "baking" and (
                        d["shadow"]["match"] >= 1
                    ):
                        break
                    time.sleep(0.02)
                ctl.promote()
                while time.monotonic() < deadline:
                    if ctl.dashboard()["stage"] == "idle":
                        break
                    time.sleep(0.02)
                if router.live_version != f"good-{k}":
                    failures.append(
                        f"good-{k} did not promote "
                        f"(live={router.live_version})"
                    )
            except BaseException as exc:
                failures.append(f"choreography: {type(exc).__name__}: {exc}")

        flts = [
            threading.Thread(target=flood_client, args=(1000 + i,))
            for i in range(flood_clients)
        ]
        chor = None
        if churn_version_k is not None:
            ctl.start(interval_s=0.05)
            chor = threading.Thread(
                target=choreograph, args=(churn_version_k,)
            )
        ttft_min = [math.inf] * short_n
        gc_was = gc.isenabled()
        gc.disable()
        for t in flts:
            t.start()
        if chor is not None:
            chor.start()
        try:
            done = False
            while not done:
                # keep measuring full rounds until the lifecycle (when
                # one is running) has completed — churn must overlap
                # the measurement window, not straddle past it
                for _ in range(rounds):
                    for i, p in enumerate(shorts):
                        try:
                            t0 = time.perf_counter()
                            stream = router.generate_stream(p)
                            out = []
                            for j, c in enumerate(stream):
                                if j == 0:
                                    dt = time.perf_counter() - t0
                                    ttft_min[i] = min(ttft_min[i], dt)
                                out.extend(c)
                            if out != solo[tuple(p)]:
                                failures.append("short token mismatch")
                        except BaseException as exc:
                            failures.append(
                                f"short: {type(exc).__name__}"
                            )
                done = chor is None or not chor.is_alive()
        finally:
            stop.set()
            for t in flts:
                t.join(timeout=120)
            if chor is not None:
                chor.join(timeout=120)
                ctl.stop()
            if gc_was:
                gc.enable()
        v = sorted(ttft_min)
        return v[max(0, math.ceil(0.99 * len(v)) - 1)]  # UNROUNDED

    try:
        for e in engines:
            e.warmup(params)
        steady_p99s = [run_sweep() for _ in range(sweeps)]
        churn_p99s = [run_sweep(k) for k in range(1, sweeps + 1)]
        assert not failures, failures[:5]
        steady = statistics.median(steady_p99s)
        churn = statistics.median(churn_p99s)
        print(json.dumps({
            "metric": "serve_rollout_ttft_p99_ms",
            "steady": round(steady * 1e3, 3),
            "under_lifecycle_churn": round(churn * 1e3, 3),
            "value": round(churn * 1e3, 3),
            "sweeps_steady_ms": [round(x * 1e3, 3) for x in steady_p99s],
            "sweeps_churn_ms": [round(x * 1e3, 3) for x in churn_p99s],
            "ratio": round(churn / max(steady, 1e-9), 3),
            "unit": "ms",
        }))
        assert churn <= 2.0 * steady, (
            f"lifecycle churn p99 {churn * 1e3:.2f} ms blew the bar "
            f"(2.0x steady-state {steady * 1e3:.2f} ms) — a rollout "
            "must cost tail latency, never availability"
        )
        # the fleet landed on the LAST promoted version with the
        # canary pool reaped and the ledger at baseline
        assert router.live_version == f"good-{sweeps}"
        assert set(router.members()) == {"r0", "r1"}
        assert len(canary_engines) == 2 * sweeps
        snap = reg.snapshot()
        assert snap["unionml_rollout_canary_replicas"] == {"": 0.0}
        decisions = snap["unionml_rollout_decisions_total"]
        rollbacks = sum(
            v for k, v in decisions.items()
            if "reason=parity_regression" in k
        )
        completes = sum(
            v for k, v in decisions.items() if "reason=complete" in k
        )
        assert rollbacks >= sweeps and completes >= sweeps, decisions
        print(json.dumps({
            "metric": "serve_rollout_summary",
            "lifecycles": sweeps,
            "auto_rollbacks": int(rollbacks),
            "promotions": int(completes),
            "caller_visible_failures": 0,
            "token_parity": "exact",
            "live_version": router.live_version,
        }))
    finally:
        ctl.close()
        vreg.close()
        router.close()
        for e in engines + canary_engines:
            e.close()


if __name__ == "__main__":
    if os.environ.get("UNIONML_TPU_BENCH_PRESET") == "serve_tracing":
        if len(sys.argv) > 1 or os.environ.get("UNIONML_TPU_BENCH_KV") or (
            os.environ.get("UNIONML_TPU_BENCH_PREFIX")
        ):
            # hardcoded workload, same rule as the other engine legs
            raise SystemExit(
                "UNIONML_TPU_BENCH_PRESET=serve_tracing takes no CLI "
                f"flags or KV/PREFIX env legs (got {sys.argv[1:]}); its "
                "workload is hardcoded in tracing_leg"
            )
        tracing_leg()
    elif os.environ.get("UNIONML_TPU_BENCH_PRESET") == "serve_introspection":
        if len(sys.argv) > 1 or os.environ.get("UNIONML_TPU_BENCH_KV") or (
            os.environ.get("UNIONML_TPU_BENCH_PREFIX")
        ):
            # hardcoded workload, same rule as the other engine legs
            raise SystemExit(
                "UNIONML_TPU_BENCH_PRESET=serve_introspection takes no CLI "
                f"flags or KV/PREFIX env legs (got {sys.argv[1:]}); its "
                "workload is hardcoded in introspection_leg"
            )
        introspection_leg()
    elif os.environ.get("UNIONML_TPU_BENCH_PRESET") == "serve_paged":
        if len(sys.argv) > 1 or os.environ.get("UNIONML_TPU_BENCH_KV") or (
            os.environ.get("UNIONML_TPU_BENCH_PREFIX")
        ):
            # hardcoded workload, same rule as the other engine legs
            raise SystemExit(
                "UNIONML_TPU_BENCH_PRESET=serve_paged takes no CLI "
                f"flags or KV/PREFIX env legs (got {sys.argv[1:]}); its "
                "workload is hardcoded in paged_leg"
            )
        paged_leg()
    elif os.environ.get("UNIONML_TPU_BENCH_PRESET") == "serve_disagg":
        if len(sys.argv) > 1 or os.environ.get("UNIONML_TPU_BENCH_KV") or (
            os.environ.get("UNIONML_TPU_BENCH_PREFIX")
        ):
            # hardcoded workload, same rule as the other engine legs
            raise SystemExit(
                "UNIONML_TPU_BENCH_PRESET=serve_disagg takes no CLI "
                f"flags or KV/PREFIX env legs (got {sys.argv[1:]}); its "
                "workload is hardcoded in disagg_leg"
            )
        disagg_leg()
    elif os.environ.get("UNIONML_TPU_BENCH_PRESET") == "serve_fleet_obs":
        if len(sys.argv) > 1 or os.environ.get("UNIONML_TPU_BENCH_KV") or (
            os.environ.get("UNIONML_TPU_BENCH_PREFIX")
        ):
            # hardcoded workload, same rule as the other engine legs
            raise SystemExit(
                "UNIONML_TPU_BENCH_PRESET=serve_fleet_obs takes no CLI "
                f"flags or KV/PREFIX env legs (got {sys.argv[1:]}); its "
                "workload is hardcoded in fleet_obs_leg"
            )
        fleet_obs_leg()
    elif os.environ.get("UNIONML_TPU_BENCH_PRESET") == "serve_perf":
        if len(sys.argv) > 1 or os.environ.get("UNIONML_TPU_BENCH_KV") or (
            os.environ.get("UNIONML_TPU_BENCH_PREFIX")
        ):
            # hardcoded workload, same rule as the other engine legs
            raise SystemExit(
                "UNIONML_TPU_BENCH_PRESET=serve_perf takes no CLI "
                f"flags or KV/PREFIX env legs (got {sys.argv[1:]}); its "
                "workload is hardcoded in perf_leg"
            )
        perf_leg()
    elif os.environ.get("UNIONML_TPU_BENCH_PRESET") == "serve_rollout":
        if len(sys.argv) > 1 or os.environ.get("UNIONML_TPU_BENCH_KV") or (
            os.environ.get("UNIONML_TPU_BENCH_PREFIX")
        ):
            # hardcoded workload, same rule as the other engine legs
            raise SystemExit(
                "UNIONML_TPU_BENCH_PRESET=serve_rollout takes no CLI "
                f"flags or KV/PREFIX env legs (got {sys.argv[1:]}); its "
                "workload is hardcoded in rollout_leg"
            )
        rollout_leg()
    elif os.environ.get("UNIONML_TPU_BENCH_PRESET") == "serve_autoscale":
        if len(sys.argv) > 1 or os.environ.get("UNIONML_TPU_BENCH_KV") or (
            os.environ.get("UNIONML_TPU_BENCH_PREFIX")
        ):
            # hardcoded workload, same rule as the other engine legs
            raise SystemExit(
                "UNIONML_TPU_BENCH_PRESET=serve_autoscale takes no CLI "
                f"flags or KV/PREFIX env legs (got {sys.argv[1:]}); its "
                "workload is hardcoded in autoscale_leg"
            )
        autoscale_leg()
    elif os.environ.get("UNIONML_TPU_BENCH_PRESET") == "serve_router":
        if len(sys.argv) > 1 or os.environ.get("UNIONML_TPU_BENCH_KV") or (
            os.environ.get("UNIONML_TPU_BENCH_PREFIX")
        ):
            # hardcoded workload, same rule as the other engine legs
            raise SystemExit(
                "UNIONML_TPU_BENCH_PRESET=serve_router takes no CLI "
                f"flags or KV/PREFIX env legs (got {sys.argv[1:]}); its "
                "workload is hardcoded in router_leg"
            )
        router_leg()
    elif os.environ.get("UNIONML_TPU_BENCH_PRESET") == "serve_preempt":
        if len(sys.argv) > 1 or os.environ.get("UNIONML_TPU_BENCH_KV") or (
            os.environ.get("UNIONML_TPU_BENCH_PREFIX")
        ):
            # hardcoded workload, same rule as the other engine legs
            raise SystemExit(
                "UNIONML_TPU_BENCH_PRESET=serve_preempt takes no CLI "
                f"flags or KV/PREFIX env legs (got {sys.argv[1:]}); its "
                "workload is hardcoded in preempt_leg"
            )
        preempt_leg()
    elif os.environ.get("UNIONML_TPU_BENCH_PRESET") == "serve_usage":
        if len(sys.argv) > 1 or os.environ.get("UNIONML_TPU_BENCH_KV") or (
            os.environ.get("UNIONML_TPU_BENCH_PREFIX")
        ):
            # hardcoded workload, same rule as the other engine legs
            raise SystemExit(
                "UNIONML_TPU_BENCH_PRESET=serve_usage takes no CLI "
                f"flags or KV/PREFIX env legs (got {sys.argv[1:]}); its "
                "workload is hardcoded in usage_leg"
            )
        usage_leg()
    elif os.environ.get("UNIONML_TPU_BENCH_PRESET") == "serve_overload":
        if len(sys.argv) > 1 or os.environ.get("UNIONML_TPU_BENCH_KV") or (
            os.environ.get("UNIONML_TPU_BENCH_PREFIX")
        ):
            # hardcoded workload, same rule as serve_prefix_cache below
            raise SystemExit(
                "UNIONML_TPU_BENCH_PRESET=serve_overload takes no CLI "
                f"flags or KV/PREFIX env legs (got {sys.argv[1:]}); its "
                "workload is hardcoded in overload_leg"
            )
        overload_leg()
    elif os.environ.get("UNIONML_TPU_BENCH_PRESET") == "serve_prefix_cache":
        if len(sys.argv) > 1 or os.environ.get("UNIONML_TPU_BENCH_KV") or (
            os.environ.get("UNIONML_TPU_BENCH_PREFIX")
        ):
            # this leg never parses argv and replaces the env-triggered
            # legs — accepting either here would record its hardcoded
            # workload under the wrong labels
            raise SystemExit(
                "UNIONML_TPU_BENCH_PRESET=serve_prefix_cache takes no CLI "
                f"flags or KV/PREFIX env legs (got {sys.argv[1:]}); its "
                "workload is hardcoded in prefix_cache_engine_leg"
            )
        prefix_cache_engine_leg()
    elif os.environ.get("UNIONML_TPU_BENCH_KV") or os.environ.get(
        "UNIONML_TPU_BENCH_PREFIX"
    ):
        if len(sys.argv) > 1:
            # these legs never parse argv — accepting flags here would
            # record hardcoded-config numbers under the flags' labels
            raise SystemExit(
                "UNIONML_TPU_BENCH_KV/UNIONML_TPU_BENCH_PREFIX legs take "
                f"no CLI flags (got {sys.argv[1:]}); their configs are "
                "hardcoded in kv_cache_legs/prefix_cache_legs"
            )
        if os.environ.get("UNIONML_TPU_BENCH_KV"):
            kv_cache_legs()
        else:
            prefix_cache_legs()
    else:
        main()
