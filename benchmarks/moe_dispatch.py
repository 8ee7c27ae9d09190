"""One expert layer alone: the dense dispatch beside the grouped one.

    chiprun -- env PYTHONPATH=. python3 benchmarks/moe_dispatch.py    # the tree's code, Mixtral's layer
    chiprun -- env PYTHONPATH=. python3 benchmarks/moe_dispatch.py --tokens 256 --chunk 64 --tiles 2048,1024,0
    chiprun -- env PYTHONPATH=. python3 benchmarks/moe_dispatch.py --experts 64 --top-k 4 --hidden 2048 \\
        --width 1536 --router sigmoid --layers 12 --tokens 32 512 4096 --chunk 0 16 32 --live 14

One JSON line a case: microseconds a layer, from a jitted loop of ``--reps``
passes over ``--layers`` layers, timed on the host's clock around
``block_until_ready``. The shape is ``chipbench``'s ``mixtral_chat_decode``:
hidden 4096, 8 experts of 14336, top-2, int8 weights, bfloat16 rows, unless
``--experts`` / ``--top-k`` / ``--hidden`` / ``--width`` / ``--router`` give
another layer (``glm_flash_code_context_decode``'s: 64 experts of 1536 on
2048, top-4, the sigmoid router). ``--live N``: only the first N rows are
distinct and the rest repeat one row, as a decode chunk's dead slots all hold
the pad token and route alike (the experts touched are then the live rows').
``--valid N``: the rows past the first N are handed to the grouped dispatch as
``valid=False`` (sent to no expert, as the dead rows of a block forward that
carries two blocks a slot: PR 46); the dense dispatch has no such argument
and computes every row. Every
layer has its own weights (1.41 GB; eight of them, as the cell holds): one
layer's weights carried through a loop are not what a model reads. A layer
is the router, the routing, and the experts' SwiGLU, its input made from the
last layer's output so that nothing is hoisted. ``T`` rows are 32 (the decode
chunk's slot rows) and the four prefill buckets' 128 to 1024; beside each
time stand how far one layer's output lies from the dense dispatch's
(``max_off_dense_in_sd``: bfloat16 rounding, a few hundredths) and the
layer's least times: its weights at the HBM rate (every
expert is touched from 32 rows on) and the routed rows' FLOPs at the MXU's
peak.

``--chunk`` and ``--tiles`` override the kernel's row tile
(``ops.moe._row_chunk``) and the tiles ``ops.moe.matmul_tiles`` gives it, to
re-derive both: ``k,n,b`` is the weight tile and the row tiles of a row block
(0: every row in one block) for both products, ``k,n,b/k,n,b`` for gate + up
and for down, ``rule`` the op's own beside them; each line says the tiles and
grid steps it ran. ``--ragged-dot`` adds ``jax.lax.ragged_dot`` (it
keeps a bfloat16 copy of the weights: 2.8 GB a layer beside the int8 ones).
Fails without a TPU unless ``--rehearse`` (tiny shapes, interpret mode: the
numbers then mean nothing). Not run by any cell or test.
"""

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from unionml_tpu.ops import moe

HBM_BYTES_PER_S, MXU_FLOPS = 819e9, 197e12  # TPU v5e (chipbench/peaks.json)


def _layer_weights(rng, d, h, experts):
    def q(shape):
        return jnp.asarray(rng.integers(-127, 128, shape, dtype=np.int8))

    def scale(k, n):  # the lecun standard deviation, as the cell's adapter makes it
        return jnp.full((experts, n), 1.0 / (73.0 * np.sqrt(k)), jnp.float32)

    return {
        "router": jnp.asarray(rng.standard_normal((d, experts)) / np.sqrt(d), jnp.bfloat16),
        "w": (q((experts, d, h)), q((experts, d, h)), q((experts, h, d))),
        "scales": (scale(d, h), scale(d, h), scale(h, d)),
    }


def _layer(mlp, k, x, p, router="softmax"):
    if router == "sigmoid":
        logits = jnp.matmul(x.astype(jnp.float32), p["router"].astype(jnp.float32))
        weights, indices = moe.sigmoid_top_k_routing(logits, jnp.zeros((logits.shape[-1],)), k, scaling=1.8)
    else:
        weights, indices, _ = moe.top_k_routing(x @ p["router"], k)
    out = mlp(x, weights, indices, *p["w"], scales=p["scales"]).astype(jnp.float32)
    # the next layer's rows: unit scale again, and a function of this output
    return (out * jax.lax.rsqrt(jnp.mean(out * out, axis=-1, keepdims=True) + 1e-6)).astype(x.dtype)


def _tiles_arg(text):
    """``k,n,b`` or ``k,n,b/k,n,b`` -> ((tk, tn, block) of gate + up, of down);
    ``rule`` -> None, the op's own."""
    if text == "rule":
        return None
    products = [tuple(int(n) for n in p.split(",")) for p in text.split("/")]
    return tuple(products * 2)[:2]


def _with_tiles(pallas, tiles, lhs, *args, chunk, gated, **kw):
    tk, tn, block = tiles[0 if gated else 1]
    return pallas(lhs, *args, chunk=chunk, gated=gated, tiles=(tk, tn, block or lhs.shape[0] // chunk), **kw)


def _kernel_plan(tokens, k, experts, d, h, chunk, tiles):
    """The tiles and grid steps the two calls of a layer run."""
    rows = moe._padded_rows(tokens * k, experts, chunk)
    plan = {}
    products = {"gate_up": (d, h, 2), "down": (h, d, 1)}
    for (name, (depth, width, n_rhs)), given in zip(products.items(), tiles or (None, None)):
        if given is None:
            plan[name] = moe.matmul_tiles(rows, depth, width, n_rhs, chunk)
        else:
            plan[name] = moe._tile_plan(rows, depth, width, chunk, *given[:2], given[2] or rows // chunk)
    return plan


def _time(loop, x, params, calls):
    jax.block_until_ready(loop(x, params))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(loop(x, params))
        times.append((time.perf_counter() - t0) / calls)
    return round(1e6 * float(np.median(times)), 1), round(1e6 * min(times), 1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, nargs="*", default=[32, 128, 256, 512, 1024])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=4096, help="the model's width")
    ap.add_argument("--width", type=int, default=14336, help="an expert's width")
    ap.add_argument("--router", choices=("softmax", "sigmoid"), default="softmax")
    ap.add_argument("--live", type=int, default=0, help="distinct rows; the rest repeat one (0: all)")
    ap.add_argument("--valid", type=int, default=0,
                    help="rows the grouped dispatch sends to experts; the rest valid=False (0: all)")
    ap.add_argument("--chunk", type=int, nargs="*", default=[], help="row tiles to try (0: the op's own)")
    ap.add_argument("--tiles", nargs="*", default=[], help="k,n,b[/k,n,b] to try, not the op's own")
    ap.add_argument("--ragged-dot", action="store_true")
    ap.add_argument("--skip-dense", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"needs a TPU, found {device.platform}")
    d, h, experts, k = (args.hidden, args.width, args.experts, args.top_k)
    if args.rehearse:
        d, h, experts, k = 256, 512, 8, 2
    reps, layers = (1, 2) if args.rehearse else (args.reps, args.layers)
    rng = np.random.default_rng(args.seed)
    params = [_layer_weights(rng, d, h, experts) for _ in range(layers)]
    layer_bytes = 3 * experts * d * h

    cases = [] if args.skip_dense else [("dense", moe.dense_expert_mlp, None, None)]
    grouped = functools.partial(moe.grouped_expert_mlp, impl="pallas")
    tiles = [_tiles_arg(t) for t in args.tiles] or [None]
    cases += [("grouped", grouped, c or None, t) for c in (args.chunk or [None]) for t in tiles]
    if args.ragged_dot:
        cases.append(("ragged_dot", functools.partial(moe.grouped_expert_mlp, impl="ragged_dot"), None, None))
    row_chunk, pallas = moe._row_chunk, moe._grouped_matmul_pallas
    for tokens in args.tokens:
        x = jnp.asarray(rng.standard_normal((tokens, d)), jnp.bfloat16)
        if args.live:
            x = jnp.where(jnp.arange(tokens)[:, None] < args.live, x, x[-1:])
        for what, mlp, chunk, tile in cases:
            if args.valid and what != "dense":
                mlp = functools.partial(mlp, valid=jnp.arange(tokens) < args.valid)
            moe._row_chunk = row_chunk if chunk is None else (lambda rows, e, chunk=chunk: chunk)
            moe._grouped_matmul_pallas = (
                pallas if tile is None else functools.partial(_with_tiles, pallas, tile)
            )

            @jax.jit
            def loop(x, params, mlp=mlp):
                def body(_, x):
                    for p in params:
                        x = _layer(mlp, k, x, p, args.router)
                    return x
                return jax.lax.fori_loop(0, reps, body, x)

            try:
                us, us_min = _time(loop, x, params, reps * layers)
                # one layer's rows against the dense dispatch's, in units of their spread
                got, want = (
                    jax.jit(functools.partial(_layer, m, k, router=args.router))(x, params[0])
                    .astype(jnp.float32)
                    for m in (mlp, moe.dense_expert_mlp)
                )
                sent = slice(0, args.valid or None)      # a row sent to no expert comes back zero
                off = round(float(jnp.max(jnp.abs(got - want)[sent]) / jnp.std(want[sent])), 4)
            except Exception as exc:  # a tile the compiler refuses: say so and go on
                us, us_min, off = None, str(exc)[:300], None
            chunk = chunk or (moe._row_chunk(tokens * k, experts) if what == "grouped" else None)
            print(json.dumps({
                "what": what, "tokens": tokens, "us_per_layer": us, "us_min": us_min,
                "chunk": chunk, "max_off_dense_in_sd": off,
                "kernel": _kernel_plan(tokens, k, experts, d, h, chunk, tile) if what == "grouped" else None,
                "rows_computed_over_routed": {
                    "dense": experts / k, "ragged_dot": 1.0,
                }.get(what) or round(moe._padded_rows(tokens * k, experts, chunk) / (tokens * k), 3),
                "weights_us_at_hbm_rate": round(1e6 * layer_bytes / HBM_BYTES_PER_S, 1),
                "experts_touched_expected": round(
                    moe.expected_experts_touched(
                        args.live or (args.valid if what != "dense" else 0) or tokens, experts, k), 1
                ),
                "experts": experts, "top_k": k, "hidden": d, "width": h, "router": args.router,
                "live": args.live, "valid": (args.valid or tokens) if what != "dense" else tokens,
                "routed_flops_us_at_peak": round(1e6 * 2 * tokens * k * 3 * d * h / MXU_FLOPS, 1),
                "layers": layers, "device": device.device_kind, "platform": device.platform,
            }), flush=True)
    moe._row_chunk, moe._grouped_matmul_pallas = row_chunk, pallas


if __name__ == "__main__":
    main()
