"""The fused attention kernel pair alone, at the ViT cell's shape and BERT-base's.

    chiprun -- env PYTHONPATH=. python3 benchmarks/fused_attention_kernel.py
    chiprun -- env PYTHONPATH=. python3 benchmarks/fused_attention_kernel.py \\
        --layout new --shape 8x256x8x128 --causal

One JSON line a shape and layout: device microseconds a forward and a backward
kernel call, and the GB/s of the bytes they move *as the chip stores them*
(``ops.fused_attention.attention_layout``: a 64-wide head laid out alone fills
half of every 128-lane tile). ``--layout new`` takes the blocks the
rule gives a shape (``[B, S, H*D]`` rows, two 64-wide heads a lane tile);
``old`` hands the same kernels ``[B, H, S, D]`` blocks, a head a block row,
which is what every shape took before PR 39 and what a width that fits no
tile (80, 96) still takes. Both go through ``ops.fused_attention._fused``, the
custom-VJP entry under ``fused_attention``, with tensors already in block
form: no projection, no transpose, nothing but the two ``tpu_custom_call``s.
With ``both`` the line of ``new`` also says how far its output and gradients
lie from ``old``'s.

``--layers`` independent sets of ``q, k, v, do`` go through one jitted
program a dispatch, each with its own buffers, as a step's twelve layers do:
one set alone would sit in the chip's fast memory from call to call. The
kernels' times are the median durations of their ``tpu_custom_call`` events in
a profiler trace of ``--reps`` dispatches: XLA lays a jitted program's
arguments and results out as it likes and copies them to and from what the
kernels take, so the host's clock round a dispatch (``program_us`` a layer)
holds those copies too, twice the bytes in the old layout. Fails without a
TPU unless ``--rehearse`` (tiny shapes, interpret mode: no device trace, and
the host's numbers mean nothing). Not run by any cell or test. A shape whose
whole ``[S, H*D]`` blocks pass the kernels' 16 MiB of VMEM (``8x512x8x128``,
either layout) fails to compile here as it does in a model.
"""

import argparse
import glob
import json
import os
import re
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import ProfileData

from unionml_tpu.ops import fused_attention as fa

SHAPES = {"vit": (64, 197, 12, 64), "bert": (128, 128, 12, 64)}


def _blocks(layout, seq, heads, head_dim, dtype):
    rule = fa.attention_layout if layout == "new" else fa._heads_layout
    taken = rule(seq, heads, head_dim, dtype)
    return taken, fa._Blocks.of(taken, head_dim)


def _time(fn, sets, reps):
    """Host microseconds a layer over ``reps`` dispatches of ``fn`` over
    every set, and the median device microseconds of the forward and of the
    backward kernel's calls in a trace of them (None off the TPU)."""
    jax.block_until_ready(fn(sets))
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn(sets)
            jax.block_until_ready(out)
            host_us = 1e6 * (time.perf_counter() - t0) / (reps * len(sets))
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        planes = ProfileData.from_file(files[0]).planes if files else []
    kernels = {"fwd": [], "bwd": []}
    for plane in planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        if "tpu_custom_call" in ev.name:
                            # the backward kernel's result is a tuple (dq, dk, dv)
                            which = "bwd" if re.search(r"= \(", ev.name) else "fwd"
                            kernels[which].append(ev.duration_ns / 1e3)
    fwd, bwd = (round(float(np.median(v)), 2) if v else None for v in kernels.values())
    return round(host_us, 2), fwd, bwd


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layout", choices=("old", "new", "both"), default="both")
    ap.add_argument("--shape", action="append", help="vit, bert or BxSxHxD; may repeat (default: both)")
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"needs a TPU, found {device.platform}")
    shapes = [SHAPES.get(s) or tuple(int(n) for n in s.split("x")) for s in args.shape or SHAPES]
    layers, reps = (2, 2) if args.rehearse else (args.layers, args.reps)
    dtype = jnp.bfloat16
    rng = np.random.default_rng(args.seed)
    for batch, seq, heads, head_dim in shapes:
        if args.rehearse:
            batch, seq = 2, min(seq, 40)
        shape = (batch, seq, heads, head_dim)
        sets4 = [
            tuple(jnp.asarray(rng.standard_normal(shape) * scale, dtype)
                  for scale in (head_dim ** -0.5 * fa.LOG2E, 1.0, 1.0, 1.0))
            for _ in range(layers)
        ]
        results = {}
        for layout in ("old", "new") if args.layout == "both" else (args.layout,):
            taken, blocks = _blocks(layout, seq, heads, head_dim, dtype)
            sets = [tuple(blocks.pack(x) for x in s) for s in sets4]

            def attend(q, k, v, blocks=blocks):
                return fa._fused(q, k, v, args.causal, heads, blocks)

            @jax.jit
            def forward(sets):
                return [attend(q, k, v) for q, k, v, _ in sets]

            @jax.jit
            def pair(sets):
                outs = []
                for q, k, v, do in sets:
                    out, vjp = jax.vjp(attend, q, k, v)
                    outs.append((out,) + vjp(do))
                return outs

            _, fwd_us, _ = _time(forward, sets, reps)
            program_us, _, bwd_us = _time(pair, sets, reps)
            stored = batch * taken.stored_bytes
            line = {
                "shape": "x".join(map(str, shape)), "causal": args.causal, "layout": layout,
                "blocks": taken.layout, "heads_per_tile": taken.heads_per_tile,
                "stored_over_values": round(taken.stored_bytes / taken.value_bytes, 3),
                "fwd_kernel_us": fwd_us, "bwd_kernel_us": bwd_us, "program_us": program_us,
                # forward: q, k, v in, out back; backward: q, k, v, do, out in, dq, dk, dv back
                "fwd_stored_gb_s": fwd_us and round(4 * stored / fwd_us / 1e3, 1),
                "bwd_stored_gb_s": bwd_us and round(8 * stored / bwd_us / 1e3, 1),
                "layers": layers, "device": device.device_kind, "platform": device.platform,
            }
            results[layout] = [blocks.unpack(x, shape).astype(jnp.float32) for x in pair(sets[:1])[0]]
            if len(results) == 2:
                line["max_abs_from_old"] = [
                    round(float(jnp.max(jnp.abs(a - b))), 5) for a, b in zip(results["new"], results["old"])
                ]
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
