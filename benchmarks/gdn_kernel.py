"""The gated-delta decode step alone, at the hybrid cell's shape.

    chiprun -- env PYTHONPATH=. python3 benchmarks/gdn_kernel.py    # the tree's code
    PYTHONPATH=<other checkout> python3 benchmarks/gdn_kernel.py     # another tree's

One JSON line a case: microseconds a call, from a jitted loop of ``--reps``
passes over ``--layers`` layers, timed on the host's clock around
``block_until_ready`` (the launch is paid once a loop). The shape is
``chipbench``'s ``olmo_hybrid_longgen_decode``: 32 slots, 30 heads of 96 x
192, hidden 3840, int8 weights. Every layer has its own state (70 MB) and
its own weights (88 MB), as in the model: one state carried alone through a
loop stays in the chip's fast memory and reads 43 us a call at 12 live rows,
more than twice the HBM rate. Two things are timed, each with 12 and with 6
of the 32 rows live (the cell's loaded and calm traced windows):

- ``kernel``: ``ops.gated_delta.gated_delta_step``, the states carried from
  pass to pass. Keys, queries and values do not change, so what XLA does to
  hand them over is hoisted out of the loop: this is the Pallas kernel and
  its own fetches.
- ``layer_step``: a ``GatedDeltaNet`` decode step (``seq == 1``, vector
  ``cache_index``) a layer, the states and tails carried and each layer's
  input made from the last one's output, so nothing is hoisted: the six
  projections' weight read (108 us at the HBM rate), the kernel, and the
  glue between them.

``layer_step`` minus ``kernel`` minus the weight read is the glue. Fails
without a TPU unless ``--rehearse`` (tiny shapes, interpret mode: the numbers
then mean nothing). Not run by any cell or test.
"""

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from unionml_tpu.models.olmo_hybrid import GatedDeltaNet, OlmoHybridConfig
from unionml_tpu.ops import gated_delta as gd

SLOTS = 32


def _live_rows(rng, n_live):
    """``n_live`` rows among the low slots (an engine takes the lowest free)."""
    live = np.zeros(SLOTS, bool)
    live[rng.choice(min(SLOTS, 2 * n_live), size=n_live, replace=False)] = True
    return jnp.asarray(live)


def _params(layer, rng, x, cache, index):
    """Random weights by leaf kind: int8 values, small positive scales,
    standard-normal everything else."""
    shapes = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), x, cache=cache, cache_index=index, live=index > -1)
    )

    def leaf(path, s):
        if s.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-128, 128, s.shape, dtype=np.int8))
        if path[-1].key == "scale":
            return jnp.full(s.shape, 2e-4, s.dtype)
        fan_in = s.shape[0] if len(s.shape) == 2 else 1
        return jnp.asarray(rng.standard_normal(s.shape) / np.sqrt(fan_in), s.dtype)

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def _time(loop, carry, *fixed, calls):
    """Median and least microseconds a call over five runs of ``loop``,
    which takes its carry (donated) and gives the next one."""
    carry = jax.block_until_ready(loop(jax.tree_util.tree_map(jnp.copy, carry), *fixed))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        carry = jax.block_until_ready(loop(carry, *fixed))
        times.append((time.perf_counter() - t0) / calls)
    return round(1e6 * float(np.median(times)), 2), round(1e6 * min(times), 2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"needs a TPU, found {device.platform}")
    reps, layers = (2, 2) if args.rehearse else (args.reps, args.layers)
    cfg = OlmoHybridConfig(quantized=True)
    if args.rehearse:
        cfg = OlmoHybridConfig.tiny(quantized=True, linear_num_key_heads=6, linear_num_value_heads=6,
                                    linear_key_head_dim=96, linear_value_head_dim=192)
    heads, dk, dv = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    rng = np.random.default_rng(args.seed)

    def unit(shape):
        x = rng.standard_normal(shape)
        return jnp.asarray(x / np.linalg.norm(x, axis=-1, keepdims=True), jnp.float32)

    q, k = unit((SLOTS, heads, dk)), unit((SLOTS, heads, dk))
    v = jnp.asarray(rng.standard_normal((SLOTS, heads, dv)), jnp.float32)
    g = jnp.asarray(-0.3 * np.exp(rng.standard_normal((SLOTS, heads))), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, (SLOTS, heads)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((SLOTS, 1, cfg.hidden_size)), jnp.bfloat16)
    index = jnp.zeros((SLOTS,), jnp.int32)
    layer = GatedDeltaNet(cfg)
    tail_width = (cfg.linear_conv_kernel_dim - 1) * cfg.conv_channels
    caches = [(
        jnp.asarray(rng.standard_normal((SLOTS,) + gd.state_shape(heads, dk, dv)), jnp.float32),
        jnp.asarray(rng.standard_normal((SLOTS, tail_width)), jnp.bfloat16),
    ) for _ in range(layers)]
    params = [_params(layer, rng, x, caches[0], index) for _ in range(layers)]

    @functools.partial(jax.jit, donate_argnums=(0,))
    def kernel_loop(states, live):
        def body(_, states):
            return [gd.gated_delta_step(q, k, v, g, beta, s, live, impl="pallas")[1] for s in states]
        return jax.lax.fori_loop(0, reps, body, states)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def layer_loop(carry, params, live):
        def body(_, carry):
            x, caches = carry
            new = []
            for p, cache in zip(params, caches):
                out, cache = layer.apply(p, x, cache=cache, cache_index=index, live=live)
                x = jnp.tanh(out)
                new.append(cache)
            return x, new
        return jax.lax.fori_loop(0, reps, body, carry)

    operand_bytes = getattr(gd, "step_operand_bytes", None)
    for n_live in (12, 6):
        live = _live_rows(np.random.default_rng(args.seed + 1), n_live)
        for what, timed in (
            ("kernel", lambda: _time(kernel_loop, [c[0] for c in caches], live, calls=reps * layers)),
            ("layer_step", lambda: _time(layer_loop, (x, caches), params, live, calls=reps * layers)),
        ):
            us, us_min = timed()
            print(json.dumps({
                "what": what, "live_rows": n_live, "layers": layers, "us_per_call": us, "us_min": us_min,
                "state_mb": round(2 * n_live * heads * dk * dv * 4 / 1e6, 2),
                "operand_mb": operand_bytes and round(operand_bytes(SLOTS, heads, dk, dv) / 1e6, 2),
                "device": device.device_kind, "platform": device.platform,
            }), flush=True)


if __name__ == "__main__":
    main()
