#!/usr/bin/env python3
"""Chip smoke: the two main paths, end to end, on one TPU chip.

    python chip_smoke.py                      # one chip: train + serve
    python chip_smoke.py --chips 4            # four chips: sharded paths only
    python chip_smoke.py [--chips 4] --rehearse   # CPU, tiny sizes

One process, no children. Without ``--rehearse`` the script refuses to
run unless ``jax.devices()[0].platform == "tpu"``; any phase that raises
or fails a check ends the run non-zero. The last stdout line of a
passing chip run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

A rehearsal walks the same code at tiny sizes on the CPU (Pallas in
interpret mode), prefixes every line with ``[rehearsal]`` and never
prints that line. Timings printed on the way are smoke timings — one
cold run each, for orientation — not benchmark results.

Phases (one chip):

- **train** — a ``Dataset`` + ``Model`` app whose ``@model.train_step``
  is ``classification_step(ViT(ViTConfig.base16()))`` at batch 64,
  driven by ``model.train()``; step-1 loss against the same step under
  ``attn_impl="xla"``; the fused attention kernel against
  ``mha_reference`` directly; a short profiler trace.
- **serve** — Llama-3-8B widths with int8 weights behind ``DecodeEngine``
  + ``ServingApp`` over HTTP: contiguous KV (tokens == ``make_generator``
  solo greedy), paged KV under ``paged_impl="reference"`` (tokens ==
  contiguous) and under the default ``paged_impl`` (the Pallas kernel).
- **paged kernel** — ``paged_attention(impl="pallas")`` against
  ``paged_attention_reference`` at the 8B and 16/16-MHA geometries and
  at the benchmark's two serving shapes, rows of length 0 among the rest.

With ``--chips 4``: one ``compile_step(lm_step(Llama))`` step over a
dp2 x tensor2 mesh against the same step on one chip, and a
``DecodeEngine`` over tensor=4 sharded params against the one-chip
engine, with per-device ``bytes_in_use``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import statistics
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from pathlib import Path

# bf16 tolerance of the single-op comparisons below: kernel outputs
# (absolute, on O(1) values) and losses (relative)
BF16_TOL = 2e-2
SEED = 0
REHEARSAL = False


def say(msg: str) -> None:
    print(("[rehearsal] " if REHEARSAL else "") + msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")
    say(f"  ok: {what}")


def _gb(n: float) -> str:
    return f"{n / 1e9:.2f} GB"


def _mem(device) -> dict:
    return device.memory_stats() or {}


def _logit_tol(num_layers: int, scale: float) -> float:
    """How far two bf16 programs of the same network may disagree on a
    logit: the bf16 unit roundoff (2^-8) random-walked over the 2L
    residual additions, relative to the logit scale, with a safety
    factor of two. Fixed after the first chip run, where every path at
    Llama-3-8B widths sat 0.06-0.14 from the cache-free reference on
    logits of scale 5.6; the flat BF16_TOL is a single op's tolerance."""
    return 2.0 * 2.0 ** -8 * (2 * num_layers) ** 0.5 * max(1.0, scale)


# --------------------------------------------------------------- sizes


def _sizes(rehearse: bool) -> dict:
    from unionml_tpu.models import LlamaConfig, ViTConfig

    if rehearse:
        return dict(
            vit=dataclasses.replace(
                ViTConfig.tiny(image_size=32, num_classes=10), attn_impl="fused"
            ),
            batch=8, train_steps=9,
            llama=LlamaConfig.tiny(vocab_size=256),
            prompt_len=12, bucket=16, new_tokens=8,
            kernel_geoms=[(4, 2, 16, 8), (4, 4, 16, 8)],
            kernel_wide=[(4, 2, 16, 8, 12, 75), (4, 4, 16, 8, 12, 75)],
            lm_batch=4, lm_seq=32,
        )
    return dict(
        vit=ViTConfig.base16(num_classes=1000),
        batch=64, train_steps=12,
        llama=LlamaConfig.llama3_8b(),
        prompt_len=48, bucket=64, new_tokens=32,
        # (q heads, kv heads, head_dim, block): Llama-3-8B at the three
        # pool block sizes, and the OLMoE 16/16 MHA shape
        kernel_geoms=[
            (32, 8, 128, 16), (32, 8, 128, 32), (32, 8, 128, 64),
            (16, 16, 128, 16),
        ],
        # (..., rows, table width): the mixtral_chat_decode cell's shape
        # and the olmo_hybrid_longgen_decode cell's
        kernel_wide=[(32, 8, 128, 16, 32, 101), (32, 32, 128, 16, 32, 261)],
        lm_batch=4, lm_seq=256,
    )


# --------------------------------------------------------------- train


def phase_train(sz: dict, out_dir: Path) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu import Dataset, Model, telemetry
    from unionml_tpu.data.native import get_library
    from unionml_tpu.diagnostics import assert_finite, trace
    from unionml_tpu.models import ViT, classification_step, create_train_state
    from unionml_tpu.ops.attention import mha_reference
    from unionml_tpu.ops.fused_attention import fused_attention

    dev = jax.devices()[0]
    cfg, batch, steps = sz["vit"], sz["batch"], sz["train_steps"]
    module = ViT(cfg)
    image = (cfg.image_size, cfg.image_size, 3)
    say(
        f"train: ViT hidden {cfg.hidden_dim} x {cfg.num_layers} layers, "
        f"attn_impl={cfg.attn_impl}, batch {batch}, {steps} steps via "
        "Model.train(); host loader: "
        + ("native (libhostloader.so)" if get_library() is not None else "numpy fallback")
    )

    dataset = Dataset(name="smoke_images")
    made = {}

    @dataset.reader
    def reader(n: int, data_seed: int) -> dict:
        rng = np.random.default_rng(data_seed)
        images = rng.standard_normal((n,) + image, dtype=np.float32)
        made["data"] = {
            "features": images.astype(jnp.bfloat16),
            "targets": rng.integers(0, cfg.num_classes, n).astype(np.int32),
        }
        return made["data"]

    # the last batch is the test split; everything before it trains
    @dataset.splitter
    def splitter(data: dict, test_size: float, shuffle: bool, random_state: int):
        k = len(data["features"]) - batch
        return (
            {"features": data["features"][:k], "targets": data["targets"][:k]},
            {"features": data["features"][k:], "targets": data["targets"][k:]},
        )

    @dataset.parser
    def parser(data: dict, features, targets):
        return (data["features"], data["targets"])

    def init_state(learning_rate: float = 1e-3):
        return create_train_state(
            module, jnp.zeros((1,) + image, jnp.bfloat16),
            learning_rate=learning_rate, seed=SEED,
        )

    model = Model(name="smoke_vit", init=init_state, dataset=dataset)
    step_fn = classification_step(module)
    # measure_device_time: every step ends in block_until_ready, so the
    # unionml_trainer_step_ms samples are device step times
    model.train_step(measure_device_time=True)(step_fn)

    classify = jax.jit(
        lambda params, x: jnp.argmax(module.apply({"params": params}, x), axis=-1)
    )

    @model.predictor
    def predictor(state, features: np.ndarray) -> jnp.ndarray:
        return classify(state.params, features)

    # one batch per split: the labels are noise, the point is the forward
    @model.evaluator
    def evaluator(state, features: np.ndarray, targets: np.ndarray) -> float:
        preds = np.asarray(predictor(state, features[:batch]))
        return float((preds == np.asarray(targets[:batch])).mean())

    t0 = time.perf_counter()
    state, metrics = model.train(
        hyperparameters={"learning_rate": 1e-3},
        trainer_kwargs={"num_epochs": 1, "batch_size": batch, "seed": SEED},
        n=(steps + 1) * batch, data_seed=SEED,
    )
    wall = time.perf_counter() - t0

    registry = telemetry.get_registry()
    step_ms = registry.histogram("unionml_trainer_step_ms").samples()
    check(len(step_ms) == steps and steps - 1 >= 8,
          f"Model.train() took {steps} steps, >= 8 of them after the compile")
    steady = statistics.median(step_ms[1:])
    say(
        f"train smoke timings: first step (compile + run) {step_ms[0] / 1e3:.1f} s, "
        f"median step {steady:.2f} ms over {steps - 1} steps, each a host batch fed and "
        f"ended by block_until_ready ({batch / steady * 1e3:.0f} samples/s, one cold run), "
        f"Model.train() wall {wall:.1f} s, metrics {metrics}"
    )
    last_loss = registry.gauge("unionml_trainer_loss").value
    assert_finite(state.params, name="params after Model.train()")
    check(bool(np.isfinite(last_loss)),
          f"every param is finite after {steps} steps; last loss {last_loss:.4f}")
    leaves = jax.tree_util.tree_leaves(state.params)
    check(all(leaf.devices() == {dev} for leaf in leaves),
          f"all {len(leaves)} param leaves live on {dev}")
    say(f"train: peak_bytes_in_use {_gb(_mem(dev).get('peak_bytes_in_use', 0))}")

    data = made["data"]
    feed = [
        jax.device_put((data["features"][i * batch:(i + 1) * batch],
                        data["targets"][i * batch:(i + 1) * batch]))
        for i in range(2)
    ]
    lowered = jax.jit(step_fn).lower(jax.eval_shape(init_state), feed[0]).as_text()
    if REHEARSAL:
        say("  (rehearsal: interpret-mode Pallas lowers to no tpu_custom_call; not checked)")
    else:
        check("tpu_custom_call" in lowered, "lowered train step holds a tpu_custom_call")

    # the same two steps under attn_impl="fused" and "xla", from the same state
    def two_steps(name: str, fn):
        step = jax.jit(fn, donate_argnums=0)
        st, t0 = init_state(), time.perf_counter()
        st, m = step(st, feed[0])
        first = float(m["loss"])
        say(f"train: attn_impl={name} step compiled + ran in {time.perf_counter() - t0:.1f} s")
        st, m = step(st, feed[1])
        return step, st, (first, float(m["loss"]))

    fused_step, fused_state, fused = two_steps(cfg.attn_impl, step_fn)
    xla_cfg = dataclasses.replace(cfg, attn_impl="xla")
    xla_step, xla_state, xla = two_steps("xla", classification_step(ViT(xla_cfg)))
    say(f"train: step 1/2 loss {cfg.attn_impl} {fused[0]:.5f}/{fused[1]:.5f}, "
        f"xla {xla[0]:.5f}/{xla[1]:.5f}")
    check(np.isfinite(fused[0]) and abs(fused[0] - xla[0]) <= BF16_TOL * max(1.0, abs(xla[0])),
          "step-1 loss agrees with attn_impl=xla to bf16 tolerance")

    # does block_until_ready fence on this device? The same 8-step chain,
    # ended once by block_until_ready and once by reading a param back
    def read_param(st) -> float:
        return float(jax.tree_util.tree_leaves(st.params)[0].ravel()[0])

    def chain(step, st, fence):
        t0 = time.perf_counter()
        for i in range(8):
            st, _ = step(st, feed[i % 2])
        fence(st)
        return st, (time.perf_counter() - t0) / 8 * 1e3

    fused_state, _ = chain(fused_step, fused_state, jax.block_until_ready)
    shutil.rmtree(out_dir / "train_trace", ignore_errors=True)
    with trace(str(out_dir / "train_trace")):
        fused_state, t_traced = chain(fused_step, fused_state, jax.block_until_ready)
    fused_state, t_bur = chain(fused_step, fused_state, jax.block_until_ready)
    fused_state, t_read = chain(fused_step, fused_state, read_param)
    _, t_xla = chain(xla_step, xla_state, jax.block_until_ready)
    say(f"train smoke timings (8 chained steps, batches on device): attn_impl="
        f"{cfg.attn_impl} {t_bur:.2f} ms/step ended by block_until_ready, {t_read:.2f} "
        f"ended by a param readback, {t_traced:.2f} with the profiler on; "
        f"attn_impl=xla {t_xla:.2f} ms/step")
    traces = list((out_dir / "train_trace").rglob("*.xplane.pb"))
    check(bool(traces), f"profiler wrote {[str(p) for p in traces]}")

    # the fused kernel itself, forward and backward, at this model's shape
    heads, hd = cfg.num_heads, cfg.hidden_dim // cfg.num_heads
    seq = (cfg.image_size // cfg.patch_size) ** 2 + 1
    ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q, k, v = (jax.random.normal(kk, (batch, seq, heads, hd), jnp.bfloat16) for kk in ks)

    def fwd_bwd(attn):
        def run(q, k, v):
            out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v).astype(jnp.float32), q, k, v)
            return (out,) + vjp(jnp.ones_like(out))

        return jax.jit(run)(q, k, v)

    got, want = fwd_bwd(fused_attention), fwd_bwd(mha_reference)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        scale = float(jnp.max(jnp.abs(b.astype(jnp.float32))))
        check(err <= BF16_TOL * max(1.0, scale),
              f"fused_attention {name} [{batch},{seq},{heads},{hd}] vs mha_reference: "
              f"max err {err:.4f} (max |ref| {scale:.2f})")


# --------------------------------------------------------------- serve


def _http(method: str, url: str, body=None, timeout: float = 600.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


def _serve_over_http(label: str, module, params, prompts, sz: dict, *, paged: bool):
    """One DecodeEngine behind ServingApp: /health, two sequential and
    two concurrent /predict, one /predict/stream. Returns the five token
    lists and the engine's cache length."""
    from unionml_tpu import Dataset, Model
    from unionml_tpu.model import ModelArtifact
    from unionml_tpu.serving.engine import DecodeEngine
    from unionml_tpu.serving.http import ServingApp

    engine = DecodeEngine(
        module, slots=8, max_new_tokens=sz["new_tokens"],
        prompt_buckets=(sz["bucket"],), paged=paged,
    )
    app = None
    try:
        t0 = time.perf_counter()
        engine.warmup(params)
        compile_s = time.perf_counter() - t0
        engine.reset_stats()

        dataset = Dataset(name=f"smoke_prompts_{label}", targets=[])

        @dataset.reader
        def reader() -> list:
            return []

        lm = Model(name=f"smoke_llama_{label}", init=lambda: params, dataset=dataset)

        @lm.trainer
        def trainer(p: dict, features: list) -> dict:
            return p

        @lm.predictor
        def predictor(p: dict, prompts: list) -> list:
            return engine.generate(p, prompts)

        lm.artifact = ModelArtifact(params, {}, {})
        app = ServingApp(
            lm, batch=False, health=engine.health, stats=engine.stats,
            stream=lambda p, feats: engine.generate_stream(p, feats[0]),
        )
        host, port = app.serve(port=0, blocking=False)
        base = f"http://{host}:{port}"

        status, body = _http("GET", f"{base}/health")
        check(status == 200 and json.loads(body)["status"] == "ok",
              f"{label}: GET /health 200 ok")

        def predict(prompt) -> list:
            # urlopen raises on any status but 2xx
            _, body = _http("POST", f"{base}/predict", {"features": [prompt]})
            return json.loads(body)[0]

        tokens = [predict(prompts[0]), predict(prompts[1])]
        with ThreadPoolExecutor(2) as pool:  # map re-raises a worker's error here
            tokens += pool.map(predict, prompts[2:4])
        check(len(tokens) == 4, f"{label}: 4 POST /predict -> 200 (two of them concurrent)")

        status, body = _http("POST", f"{base}/predict/stream", {"features": prompts[4]})
        events = [json.loads(line[6:]) for line in body.splitlines() if line.startswith("data: ")]
        check(status == 200 and events and events[-1].get("done") is True,
              f"{label}: POST /predict/stream -> 200, {len(events)} events")
        tokens.append([t for e in events[:-1] for t in e["tokens"]])
        check(all(len(t) == sz["new_tokens"] for t in tokens),
              f"{label}: every request returned {sz['new_tokens']} tokens")

        if paged:
            deadline = time.monotonic() + 30
            while True:
                _, text = _http("GET", f"{base}/metrics")
                in_use = [
                    float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
                    if line.startswith("unionml_kv_pool_blocks_in_use")
                ]
                if (in_use and not any(in_use)) or time.monotonic() > deadline:
                    break
                time.sleep(0.05)
            check(bool(in_use) and not any(in_use),
                  f"{label}: unionml_kv_pool_blocks_in_use back to 0 ({in_use})")

        stats = engine.stats()
        say(
            f"{label} smoke timings: warmup (compile) {compile_s:.1f} s, "
            f"TTFT p50 {stats['ttft_ms']['p50']:.1f} ms, per-token gap p50 "
            f"{stats.get('itl_ms', {}).get('p50', float('nan')):.2f} ms over "
            f"{stats['completed_requests']} requests, cache_len {engine.cache_len}"
        )
        return tokens, engine.cache_len
    finally:
        if app is not None:
            app.shutdown()
        engine.close()


def _deficit_fn(module, params, width: int):
    """``deficits(prompt, generated) -> (per-token deficit, logit scale)``.

    One cache-free full forward over ``prompt + generated`` (right-padded
    to ``width``; causal attention keeps the padding out of reach) gives
    the reference logits at every position. A token's deficit is how far
    its reference logit lies below the best one GIVEN THE RUN'S OWN
    PREFIX — teacher forcing, so every emitted token is judged, also
    after two greedy runs have parted ways at a near-tie."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def forward(params, toks):
        logits = module.apply({"params": params}, toks)[0, :-1]     # [width - 1, vocab]
        picked = jnp.take_along_axis(logits, toks[0, 1:, None], axis=-1)[:, 0]
        return logits.max(-1) - picked, jnp.abs(logits).max()

    def deficits(prompt, generated):
        seq = np.zeros((1, width), np.int32)
        seq[0, :len(prompt) + len(generated)] = prompt + generated
        deficit, scale = forward(params, seq)
        first = len(prompt) - 1  # the row that predicts generated[0]
        return np.asarray(deficit)[first:first + len(generated)], float(scale)

    return deficits


def _check_greedy(runs: dict, prompts, deficits, num_layers: int) -> None:
    """On the chip two bf16 programs of different shape (a batch-8 slot
    decode, a batch-1 scan, a paged gather) round differently, and
    greedy decoding of seeded random weights meets a near-tie every few
    tokens: token-for-token equality between them is a CPU property.
    What must hold on the chip: every token a run emits is, to bf16
    tolerance, the reference's best choice for that run's prefix."""
    names = list(runs)
    for a, b in zip(names[1:], names):
        same = [x == y for x, y in zip(runs[a], runs[b])]
        parts = [
            next(i for i, (x, y) in enumerate(zip(ta, tb)) if x != y)
            for ta, tb, eq in zip(runs[a], runs[b], same) if not eq
        ]
        say(f"{a} vs {b}: {sum(same)}/{len(same)} requests token-identical"
            + (f", the others part at token {parts}" if parts else ""))
    for name, tokens in runs.items():
        worst, scale = 0.0, 0.0
        for prompt, toks in zip(prompts, tokens):
            deficit, s = deficits(prompt, toks)
            worst, scale = max(worst, float(deficit.max())), max(scale, s)
        tol = _logit_tol(num_layers, scale)
        check(worst <= tol,
              f"{name}: all {sum(map(len, tokens))} emitted tokens are within {tol:.3f} of "
              f"the reference's best logit (worst deficit {worst:.4f}, max |logit| {scale:.2f})")


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


def phase_serve(sz: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.models import Llama, make_generator

    dev = jax.devices()[0]
    cfg = dataclasses.replace(sz["llama"], quantized=True)
    module = Llama(cfg)
    say(
        f"serve: Llama hidden {cfg.hidden_dim}, {cfg.num_heads}/{cfg.num_kv_heads} heads, "
        f"mlp {cfg.mlp_dim}, vocab {cfg.vocab_size}, {cfg.num_layers} layers "
        f"(depth cut: none), int8 weights, paged_impl={cfg.paged_impl}"
    )
    t0 = time.perf_counter()
    params = jax.block_until_ready(random_quantized_params(module, seed=SEED))
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    say(f"serve: {_gb(nbytes)} of seeded weights built on device in "
        f"{time.perf_counter() - t0:.1f} s")
    rng = np.random.default_rng(SEED)
    prompts = [
        rng.integers(1, cfg.vocab_size, sz["prompt_len"]).tolist() for _ in range(5)
    ]

    contig, cache_len = _serve_over_http(
        "serve/contiguous", module, params, prompts, sz, paged=False
    )
    t0 = time.perf_counter()
    gen = make_generator(module, max_new_tokens=sz["new_tokens"], max_len=cache_len)
    solo = [
        np.asarray(gen(params, jnp.asarray([p], jnp.int32)))[0].tolist() for p in prompts
    ]
    say(f"serve: make_generator solo greedy x5 (compile + run) {time.perf_counter() - t0:.1f} s")

    ref_module = Llama(dataclasses.replace(cfg, paged_impl="reference"))
    paged_ref, _ = _serve_over_http(
        "serve/paged-reference", ref_module, params, prompts, sz, paged=True
    )
    paged, _ = _serve_over_http(
        "serve/paged-kernel", module, params, prompts, sz, paged=True
    )
    _check_greedy(
        {
            "make_generator solo": solo,
            "serve/contiguous": contig,
            "serve/paged-reference": paged_ref,
            "serve/paged-kernel": paged,
        },
        prompts, _deficit_fn(module, params, sz["bucket"] + sz["new_tokens"]),
        cfg.num_layers,
    )
    say(f"serve: peak_bytes_in_use {_gb(_mem(dev).get('peak_bytes_in_use', 0))}")


def phase_paged_kernel(sz: dict) -> None:
    import numpy as np

    batch, width = 8, 11
    rng = np.random.default_rng(SEED)
    for hq, hk, hd, block in sz["kernel_geoms"]:
        # ragged: a dead slot, one row, a block edge on either side, full
        lengths = np.array(
            [0, 1, block, block + 1, 3 * block - 1, 5 * block + 3,
             width * block - 1, width * block], np.int32,
        )
        _paged_kernel_case(rng, hq, hk, hd, block, lengths, width, n_blocks=96)
    # the benchmark's serving shapes: 32 slots over a table 101 or 261
    # blocks wide (a row walks several groups of pool blocks, the last
    # one partial), ragged lengths, retired slots the engine's way (length
    # 0: first, between live rows and last) and the old way (table all
    # trash block, length stale)
    for hq, hk, hd, block, batch, width in sz["kernel_wide"]:
        lengths = rng.integers(1, width * block + 1, batch).astype(np.int32)
        lengths[:6] = [0, 0, 1, width * block, 512, 513]
        lengths[8::5] = 0
        lengths[-2:] = 0
        dead = np.zeros(batch, bool)
        dead[5::6] = True
        _paged_kernel_case(rng, hq, hk, hd, block, lengths, width, n_blocks=3 * width, dead=dead)


def _paged_kernel_case(rng, hq, hk, hd, block, lengths, width, n_blocks, dead=None) -> None:
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.ops.paged_attention import paged_attention, paged_attention_reference

    batch = len(lengths)
    dead = np.zeros(batch, bool) if dead is None else dead
    table = rng.integers(1, n_blocks, (batch, width)).astype(np.int32)
    for b in range(batch):  # entries past coverage park on the trash block
        table[b, 0 if dead[b] else -(-int(lengths[b]) // block):] = 0
    q = jnp.asarray(rng.standard_normal((batch, hq, hd)), jnp.bfloat16)
    kv = rng.standard_normal((2, n_blocks, block, hk, hd)).astype(np.float32)
    kv[:, 0] = 100.0  # the trash block holds garbage
    scales = (rng.random((2, n_blocks, block, hk)) * 0.02 + 1e-3).astype(np.float32)
    for quant in (False, True):
        if quant:
            k, v = (jnp.asarray(np.clip(x * 40, -127, 127), jnp.int8) for x in kv)
            kw = dict(k_scale=jnp.asarray(scales[0]), v_scale=jnp.asarray(scales[1]))
        else:
            k, v = (jnp.asarray(x, jnp.bfloat16) for x in kv)
            kw = {}
        args = (q, k, v, jnp.asarray(table), jnp.asarray(lengths))
        got = paged_attention(*args, impl="pallas", **kw).astype(jnp.float32)
        want = paged_attention_reference(*args, **kw).astype(jnp.float32)
        # a dead slot's row is garbage by contract, but finite
        live = (lengths > 0) & ~dead
        err = float(jnp.max(jnp.abs(got - want)[live]))
        check(bool(jnp.all(jnp.isfinite(got))) and err <= BF16_TOL,
              f"paged kernel {hq}/{hk} heads, head_dim {hd}, block {block}, {batch} rows x "
              f"{width} blocks, {'int8' if quant else 'bf16'} pool vs reference: max err {err:.5f}")


# ---------------------------------------------------------- four chips


def _per_device_bytes(label: str, tree_bytes: int) -> None:
    import jax

    used = [_mem(d).get("bytes_in_use") for d in jax.devices()]
    say(f"{label}: bytes_in_use per device {used} (tree {tree_bytes} bytes)")
    if REHEARSAL:
        say("  (rehearsal: the CPU backend reports no memory_stats; not checked)")
        return
    share = tree_bytes / len(used)
    check(min(used) > 0.1 * share, f"{label}: no device is near empty")
    check(max(used) < 0.9 * tree_bytes, f"{label}: no device holds the whole tree")


def phase_four_chips(sz: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.models import LLAMA_PARTITION_RULES, Llama, lm_step
    from unionml_tpu.models.train import TrainState, adamw
    from unionml_tpu.parallel import ShardingConfig, compile_step, shard_pytree
    from unionml_tpu.serving.engine import DecodeEngine

    devices = jax.devices()
    # bf16 params + both adam moments is 6 bytes/param and embed +
    # lm_head alone are 1.05 B params: at four layers the one-chip step
    # is 11.5 GB of state + 1.6 GB of temporaries (AOT memory analysis)
    depth = min(4, sz["llama"].num_layers)
    cfg = dataclasses.replace(sz["llama"], num_layers=depth)
    module = Llama(cfg)
    say(
        f"chips4: Llama hidden {cfg.hidden_dim}, {cfg.num_heads}/{cfg.num_kv_heads} heads, "
        f"mlp {cfg.mlp_dim}, vocab {cfg.vocab_size}; depth cut "
        f"{sz['llama'].num_layers} -> {depth} layers (what one chip holds as "
        "bf16 params + adamw state + grads)"
    )
    rng = np.random.default_rng(SEED)
    tokens = rng.integers(1, cfg.vocab_size, (sz["lm_batch"], sz["lm_seq"])).astype(np.int32)

    @jax.jit
    def init():
        params = module.init(jax.random.PRNGKey(SEED), jnp.zeros((1, 8), jnp.int32))["params"]
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)

    # the seeded state goes to the host once, so each layout below is
    # placed from there and chip 0 never stages the whole tree
    state = jax.device_get(
        TrainState.create(apply_fn=module.apply, params=init(), tx=adamw(1e-3))
    )
    state_bytes = sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(state))
    param_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(state.params))
    step_fn = lm_step(module)

    # (a) one train step over dp2 x tensor2, against the same step on one chip
    sharding = ShardingConfig(data=2, tensor=2, rules=LLAMA_PARTITION_RULES)
    t0 = time.perf_counter()
    step4, placed = compile_step(step_fn, state, sharding=sharding)
    placed, m4 = step4(placed, tokens)
    loss4 = float(m4["loss"])
    say(f"chips4 smoke timings: dp2 x tensor2 step placed + compiled + ran in "
        f"{time.perf_counter() - t0:.1f} s, loss {loss4:.5f}")
    _per_device_bytes("chips4 train state over dp2 x tensor2", state_bytes)
    del placed, step4
    t0 = time.perf_counter()
    # [1]: the updated state must not outlive the step on chip 0
    m1 = jax.jit(step_fn, donate_argnums=0)(jax.device_put(state, devices[0]), tokens)[1]
    loss1 = float(m1["loss"])
    say(f"chips4 smoke timings: one-chip step placed + compiled + ran in "
        f"{time.perf_counter() - t0:.1f} s, loss {loss1:.5f}")
    check(np.isfinite(loss4) and abs(loss4 - loss1) <= BF16_TOL * max(1.0, abs(loss1)),
          "dp2 x tensor2 step loss == one-chip step loss within bf16 tolerance")

    # (b) DecodeEngine over tensor=4 params, against the one-chip engine
    prompts = [
        rng.integers(1, cfg.vocab_size, sz["prompt_len"]).tolist() for _ in range(4)
    ]

    def run_engine(params):
        engine = DecodeEngine(
            module, slots=8, max_new_tokens=sz["new_tokens"],
            prompt_buckets=(sz["bucket"],),
        )
        try:
            t0 = time.perf_counter()
            return engine.generate(params, prompts), time.perf_counter() - t0
        finally:
            engine.close()

    @jax.jit
    def prefill_logits(params):
        toks = jnp.asarray(prompts, jnp.int32)
        return module.apply(
            {"params": params}, toks, logit_index=jnp.full((len(prompts),), toks.shape[1] - 1)
        )[:, 0]

    one = jax.device_put(state.params, devices[0])
    out1, dt = run_engine(one)
    logits1 = np.asarray(prefill_logits(one))
    say(f"chips4 smoke timings: one-chip engine compiled + ran 4 requests in {dt:.1f} s")
    del one

    tp = shard_pytree(state.params, ShardingConfig(tensor=4, rules=LLAMA_PARTITION_RULES))
    specs = {str(tuple(x.sharding.spec)) for x in jax.tree_util.tree_leaves(tp)}
    check(any("tensor" in s for s in specs), f"params are tensor-sharded ({sorted(specs)})")
    out4, dt = run_engine(tp)
    logits4 = np.asarray(prefill_logits(tp))
    say(f"chips4 smoke timings: tensor=4 engine compiled + ran 4 requests in {dt:.1f} s")
    _per_device_bytes("chips4 params over tensor=4", param_bytes)
    err, scale = float(np.max(np.abs(logits4 - logits1))), float(np.max(np.abs(logits1)))
    check(err <= _logit_tol(depth, scale),
          f"tensor=4 prefill logits vs one chip: max err {err:.5f} "
          f"(tolerance {_logit_tol(depth, scale):.3f}, max |logit| {scale:.2f})")
    _check_greedy(
        {"chips4 one-chip engine": out1, "chips4 tensor=4 engine": out4},
        prompts, _deficit_fn(module, tp, sz["bucket"] + sz["new_tokens"]), depth,
    )


# ---------------------------------------------------------------- main


def main() -> int:
    global REHEARSAL
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument(
        "--rehearse", action="store_true",
        help="CPU rehearsal at tiny sizes; never prints the success line",
    )
    args = parser.parse_args()
    REHEARSAL = args.rehearse
    if REHEARSAL:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}"
            ).strip()

    import jax

    first = jax.devices()[0]
    if not REHEARSAL and first.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX found platform={first.platform!r}; "
            "for a CPU walk-through pass --rehearse",
            file=sys.stderr,
        )
        return 1
    if len(jax.devices()) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but JAX sees {len(jax.devices())} devices",
            file=sys.stderr,
        )
        return 1

    from unionml_tpu.compile_cache import enable_compile_cache
    from unionml_tpu.introspection import resolve_device_peaks
    from unionml_tpu.ops import flash_attention, paged_attention

    cache_dir = Path(enable_compile_cache())

    def entries() -> int:
        return len(list(cache_dir.glob("*"))) if cache_dir.is_dir() else 0

    say(f"jax {jax.__version__}, {len(jax.devices())} x {first.device_kind} "
        f"({first.platform}); compile cache {cache_dir} holds {entries()} entries")
    if not REHEARSAL:
        # nothing on this path may take a CPU or interpret fallback unnoticed
        check(not flash_attention._interpret() and not paged_attention._interpret(),
              "Pallas kernels compile for the device (no interpret mode)")
        peaks = resolve_device_peaks()
        check(peaks["source"] == "table", f"device peaks come from the table: {peaks}")

    sz = _sizes(REHEARSAL)
    out_dir = Path("chiprun_out") / ("chip_smoke_rehearsal" if REHEARSAL else "chip_smoke")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(sz)
    else:
        phase_train(sz, out_dir)
        phase_paged_kernel(sz)
        phase_serve(sz)
    say(f"all phases passed in {time.perf_counter() - t0:.0f} s; compile cache "
        f"{cache_dir} now holds {entries()} entries")
    if REHEARSAL:
        print("[rehearsal] CPU rehearsal only: no chip was used and nothing here "
              "is a device result", flush=True)
        return 0
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": first.platform,
            "kind": first.device_kind,
            "count": len(jax.devices()),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
