"""Headline benchmark: ViT-B/16 trainer samples/sec/chip (BASELINE.json).

Full training step (fwd + bwd + adamw) on the flagship ViT-B/16 config,
bf16 compute, batch 64, one chip. Prints ONE JSON line that names the
device it ran on. It needs a TPU: on any other platform it refuses,
unless ``UNIONML_TPU_BENCH_PRESET=tiny`` asks for the CPU walk-through
by name (whose number is not a device metric).

Env knobs: UNIONML_TPU_BENCH_PRESET=tiny; UNIONML_TPU_BENCH_BATCH to
override the per-chip batch size.
"""

from __future__ import annotations

import json
import os
import sys
import time


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.compile_cache import enable_compile_cache
    from unionml_tpu.models import ViT, ViTConfig, classification_step, create_train_state

    device = jax.devices()[0]
    preset = os.environ.get("UNIONML_TPU_BENCH_PRESET", "vit_b16")
    if device.platform != "tpu" and preset != "tiny":
        print(
            f"bench.py: needs a TPU, JAX found platform={device.platform!r}; "
            "set UNIONML_TPU_BENCH_PRESET=tiny for the CPU walk-through",
            file=sys.stderr,
        )
        return 1
    enable_compile_cache()
    if preset == "tiny":
        cfg = ViTConfig.tiny(image_size=32, num_classes=10)
        batch = int(os.environ.get("UNIONML_TPU_BENCH_BATCH", 32))
        steps, warmup = 10, 3
    else:
        cfg = ViTConfig.base16(num_classes=1000)
        batch = int(os.environ.get("UNIONML_TPU_BENCH_BATCH", 64))
        steps, warmup = 100, 10

    module = ViT(cfg)
    rng = np.random.default_rng(0)
    images = jnp.asarray(
        rng.normal(size=(batch, cfg.image_size, cfg.image_size, 3)), jnp.bfloat16
    )
    labels = jnp.asarray(rng.integers(0, cfg.num_classes, size=(batch,)), jnp.int32)

    state = create_train_state(module, images[:1], learning_rate=1e-3)
    step = jax.jit(classification_step(module), donate_argnums=0)

    for _ in range(warmup):
        state, metrics = step(state, (images, labels))
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, (images, labels))
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0

    print(
        json.dumps(
            {
                "metric": f"{preset}_train_samples_per_sec_per_chip",
                "value": round(batch * steps / dt, 2),
                "unit": "samples/sec/chip",
                "platform": device.platform,
                "device_kind": device.device_kind,
                "device_count": len(jax.devices()),
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
