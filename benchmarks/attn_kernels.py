"""Attention-kernel selection benchmark.

Times each attention implementation (fwd+bwd, one jit program over a
12-layer chain) at the two regimes that drive the `attn_impl` defaults:

- short-seq ViT/BERT shape (64 x 197 x 12 x 64, non-causal) — where the
  one-program-per-batch `fused` kernel wins;
- long-seq LLM shape (4 x 4096 x 16 x 128, causal) — where the
  VMEM-tiled `flash` kernel wins.

Plus the PAGED DECODE leg (docs/performance.md "Paged KV attention"):
one decode step against a block-paged KV pool at block sizes 16/32/64
vs the contiguous cached-attention baseline — per-step latency and the
KV bytes each layout moves, so the engine's `kv_block_size` choice is
data-driven (smaller blocks waste fewer tail rows, larger blocks cut
per-block gather overhead).

Prints one JSON line per (regime, impl). On CPU backends Pallas kernels
run in interpret mode — use UNIONML_TPU_BENCH_PRESET=tiny for a smoke
run there.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.ops.attention import attention

    tiny = os.environ.get("UNIONML_TPU_BENCH_PRESET") == "tiny" or (
        jax.default_backend() == "cpu"
    )
    regimes = {
        "short_seq": dict(shape=(8, 64, 4, 16) if tiny else (64, 197, 12, 64),
                          causal=False, impls=("xla", "blockwise", "fused")),
        "long_seq": dict(shape=(1, 256, 4, 32) if tiny else (4, 4096, 16, 128),
                         causal=True, impls=("xla", "blockwise", "flash"),
                         layers=1),
    }
    steps, warmup = (3, 1) if tiny else (30, 5)

    for regime, spec in regimes.items():
        # chaining 12 layers of full 4096^2 score tensors through one bwd
        # program crashes the compiler; the long regime times one layer
        layers = spec.get("layers", 2 if tiny else 12)
        b, s, h, d = spec["shape"]
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, s, h, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, s, h, d), jnp.bfloat16)

        for impl in spec["impls"]:
            def loss(q, k, v, _impl=impl):
                x = q
                for _ in range(layers):
                    x = attention(x, k, v, impl=_impl, causal=spec["causal"])
                return jnp.sum(x.astype(jnp.float32) ** 2)

            grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            try:
                for _ in range(warmup):
                    out = grad(q, k, v)
                _ = float(np.asarray(out[0]).ravel()[0])
            except Exception as e:
                print(json.dumps({
                    "metric": f"attn_{regime}_{impl}_ms", "value": None,
                    "error": str(e)[:120],
                }))
                continue
            t0 = time.perf_counter()
            for _ in range(steps):
                out = grad(q, k, v)
            _ = float(np.asarray(out[0]).ravel()[0])
            ms = (time.perf_counter() - t0) / steps * 1e3
            print(json.dumps({
                "metric": f"attn_{regime}_{impl}_ms",
                "shape": [b, s, h, d],
                "layers": layers,
                "value": round(ms, 2),
                "unit": "ms (fwd+bwd)",
            }))

    paged_decode_leg(tiny, steps, warmup)


def paged_decode_leg(tiny: bool, steps: int, warmup: int) -> None:
    """Paged-vs-contiguous decode microbench at block sizes 16/32/64.

    One decode step: [slots] single-token queries against [slots]
    resident sequences at mixed fill depths (a long-tail mix — half the
    slots shallow, half deep, the workload paging exists for). The
    contiguous baseline reads the full [slots, max_len] cache; the
    paged kernel gathers only each slot's covered blocks. ``kv_bytes``
    is the per-step KV traffic each layout issues — the HBM-bound
    quantity that sets decode throughput."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.ops.attention import cached_attention
    from unionml_tpu.ops.paged_attention import paged_attention

    if tiny:
        slots, kvh, heads, d, max_len = 4, 2, 4, 16, 128
        block_sizes = (16, 32, 64)
    else:
        slots, kvh, heads, d, max_len = 8, 8, 32, 128, 4096
        block_sizes = (16, 32, 64)
    # long-tail fills: half the slots at 1/8 depth, half near max
    fills = np.where(
        np.arange(slots) % 2 == 0, max_len // 8, max_len - max_len // 8
    ).astype(np.int32)
    q = jax.random.normal(
        jax.random.PRNGKey(1), (slots, heads, d), jnp.bfloat16
    )
    itemsize = 2  # bf16

    # ---- contiguous baseline: full [slots, max_len] cache read ----
    ck = jax.random.normal(
        jax.random.PRNGKey(2), (slots, max_len, kvh, d), jnp.bfloat16
    )
    cv = jax.random.normal(
        jax.random.PRNGKey(3), (slots, max_len, kvh, d), jnp.bfloat16
    )
    kv_pos = jnp.arange(max_len)[None, :]
    bias = jnp.where(
        (kv_pos[None] <= (jnp.asarray(fills) - 1)[:, None, None]),
        0.0, -1e30,
    )[:, None]

    def contiguous_step(q, ck, cv, bias):
        return cached_attention(q[:, None], ck, cv, bias=bias)[:, 0]

    step = jax.jit(contiguous_step)
    out = step(q, ck, cv, bias)
    out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        out = step(q, ck, cv, bias)
    out.block_until_ready()
    ms = (time.perf_counter() - t0) / steps * 1e3
    contig_bytes = 2 * slots * max_len * kvh * d * itemsize
    print(json.dumps({
        "metric": "attn_paged_decode_contiguous_ms",
        "slots": slots, "max_len": max_len, "fills": fills.tolist(),
        "kv_bytes": contig_bytes,
        "value": round(ms, 3), "unit": "ms/step",
    }))

    # ---- paged: gather only the covered blocks, per block size ----
    impl = "reference" if jax.default_backend() == "cpu" else "pallas"
    for bs in block_sizes:
        w = max_len // bs
        covered = [int(-(-f // bs)) for f in fills]
        n_pool = 1 + sum(covered)
        pool_k = jax.random.normal(
            jax.random.PRNGKey(4), (n_pool, bs, kvh, d), jnp.bfloat16
        )
        pool_v = jax.random.normal(
            jax.random.PRNGKey(5), (n_pool, bs, kvh, d), jnp.bfloat16
        )
        table = np.zeros((slots, w), np.int32)
        nid = 1
        for s_i, c in enumerate(covered):
            for j in range(c):
                table[s_i, j] = nid
                nid += 1
        table = jnp.asarray(table)
        lengths = jnp.asarray(fills)

        pstep = jax.jit(
            lambda q, k, v, t, ln: paged_attention(q, k, v, t, ln, impl=impl)
        )
        out = pstep(q, pool_k, pool_v, table, lengths)
        out.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(steps):
            out = pstep(q, pool_k, pool_v, table, lengths)
        out.block_until_ready()
        ms = (time.perf_counter() - t0) / steps * 1e3
        paged_bytes = 2 * sum(covered) * bs * kvh * d * itemsize
        print(json.dumps({
            "metric": f"attn_paged_decode_bs{bs}_ms",
            "slots": slots, "max_len": max_len, "impl": impl,
            "kv_bytes": paged_bytes,
            "kv_bytes_vs_contiguous": round(paged_bytes / contig_bytes, 3),
            "value": round(ms, 3), "unit": "ms/step",
        }))


if __name__ == "__main__":
    main()
