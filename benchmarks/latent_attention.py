"""The absorbed latent-attention kernel alone, at a served cell's shapes.

    chiprun -- env PYTHONPATH=. python3 benchmarks/latent_attention.py
    chiprun -- env PYTHONPATH=. python3 benchmarks/latent_attention.py --live 32 --positions 4600 \\
        --rows-per-step 256 512 1024

One JSON line a case: microseconds a call (one layer's attention of one
decode step) from a jitted loop of ``--reps`` passes over ``--layers``
pools, timed on the host's clock around ``block_until_ready``. The shape is
``chipbench``'s ``glm_flash_code_context_decode``: 32 rows of 20 heads in the
latent space (576 values held in 640 lanes), a pool of 15,024 blocks of 16
positions a layer (308 MB), a table 293 wide. Thirteen layers' pools are read
in turn, as the cell's decode step reads them: one pool carried through a
loop stays in fast memory and reads faster than a model's does (the lesson
of ``benchmarks/gdn_kernel.py``). ``--live`` rows hold ``--positions`` cached
positions each (in scattered blocks) and the others none, as the engine hands
the kernel a length 0 for a slot that is not live. Beside each time: the live
rows' bytes as stored and as the values they hold, their time at the HBM
rate, and how far the kernel's output lies from the plain gather's.
``--rows-per-step`` re-derives ``ops.paged_attention._LATENT_ROWS_PER_STEP``.
Fails without a TPU unless ``--rehearse`` (tiny shapes, interpret mode: the
numbers then mean nothing). Not run by any cell or test.
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from unionml_tpu.ops import paged_attention as pa

HBM_BYTES_PER_S = 819e9  # TPU v5e (chipbench/peaks.json)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", type=int, nargs="*", default=[14, 32])
    ap.add_argument("--positions", type=int, nargs="*", default=[2500])
    ap.add_argument("--rows-per-step", type=int, nargs="*", default=[0], help="0: the op's own")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--layers", type=int, default=13)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"needs a TPU, found {device.platform}")
    rows, heads, rank, rope, block, blocks, width = (
        (4, 4, 128, 64, 8, 64, 12) if args.rehearse else (32, 20, 512, 64, 16, 15_024, 293)
    )
    stored = -(-(rank + rope) // 128) * 128
    layers, reps = (2, 1) if args.rehearse else (args.layers, args.reps)
    rng = np.random.default_rng(args.seed)
    pools = [
        jnp.asarray(rng.standard_normal((blocks, block, stored)), jnp.bfloat16).at[..., rank + rope:].set(0)
        for _ in range(layers)
    ]
    q = jnp.asarray(rng.standard_normal((rows, heads, stored)), jnp.bfloat16).at[..., rank + rope:].set(0)
    own = pa._LATENT_ROWS_PER_STEP
    for live in args.live:
        for positions in args.positions:
            positions = min(positions, width * block)
            need = -(-positions // block)
            # every live row's blocks scattered over the pool, the rest of its table the trash block
            table = np.zeros((rows, width), np.int32)
            ids = rng.permutation(blocks - 1)[: live * need].reshape(live, need) + 1
            table[:live, :need] = ids
            lengths = np.zeros((rows,), np.int32)
            lengths[:live] = positions
            table, lengths = jnp.asarray(table), jnp.asarray(lengths)
            for per_step in args.rows_per_step:
                pa._LATENT_ROWS_PER_STEP = per_step or own

                def attend(q, pool, impl):
                    return pa.paged_latent_attention(
                        q, pool, table, lengths, value_dim=rank, scale=(rank // 2) ** -0.5, impl=impl,
                    )

                @jax.jit
                def loop(q, pools):
                    def body(_, q):
                        for pool in pools:
                            o = attend(q, pool, "pallas")
                            # the next layer's query: a function of this output
                            q = q.at[..., :rank].add((o * 1e-3).astype(q.dtype))
                        return q
                    return jax.lax.fori_loop(0, reps, body, q)

                try:
                    jax.block_until_ready(loop(q, pools))
                    times = []
                    for _ in range(5):
                        t0 = time.perf_counter()
                        jax.block_until_ready(loop(q, pools))
                        times.append((time.perf_counter() - t0) / (reps * layers))
                    got = jax.jit(lambda q, p: attend(q, p, "pallas"))(q, pools[0]).astype(jnp.float32)
                    want = jax.jit(lambda q, p: attend(q, p, "reference"))(q, pools[0]).astype(jnp.float32)
                    off = float(jnp.max(jnp.abs(got[:live] - want[:live])) / jnp.std(want[:live]))
                    us, us_min = 1e6 * float(np.median(times)), 1e6 * min(times)
                except Exception as exc:  # a shape the compiler refuses: say so and go on
                    us, us_min, off = None, str(exc)[:300], None
                stored_bytes = live * positions * stored * 2
                print(json.dumps({
                    "live_rows": live, "positions_each": positions, "rows_per_step": pa._LATENT_ROWS_PER_STEP,
                    "us_per_call": None if us is None else round(us, 1),
                    "us_min": us_min if us is None else round(us_min, 1),
                    "bytes_as_stored": stored_bytes, "bytes_of_values": live * positions * (rank + rope) * 2,
                    "stored_us_at_hbm_rate": round(1e6 * stored_bytes / HBM_BYTES_PER_S, 1),
                    "share_of_hbm_rate_as_stored": (
                        None if us is None else round(stored_bytes / HBM_BYTES_PER_S / (us * 1e-6), 3)
                    ),
                    "max_off_gather_in_sd": None if off is None else round(off, 4),
                    "layers": layers, "rows": rows, "heads": heads, "device": device.device_kind,
                    "platform": device.platform,
                }), flush=True)
    pa._LATENT_ROWS_PER_STEP = own


if __name__ == "__main__":
    main()
