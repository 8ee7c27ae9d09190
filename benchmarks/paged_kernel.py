"""The paged decode attention kernel alone, at the benchmark's two serving shapes.

    chiprun -- python3 benchmarks/paged_kernel.py            # the tree's kernel
    PYTHONPATH=<other checkout> python3 benchmarks/paged_kernel.py

One JSON line a case: microseconds a call, from a jitted loop of ``--reps``
dependent calls timed on the host's clock around ``block_until_ready`` (the
launch is paid once a loop). A case is a shape (``chipbench``'s
``mixtral_chat_decode``: 32 slots, GQA 32/8, a table 101 blocks wide;
``olmo_hybrid_longgen_decode``: 32 slots, MHA 32/32, a table 261 wide) and
what the rows hold:

- ``live+zero``: the live rows at their lengths, every other row length 0
  (what an engine hands the kernel when it says which rows are live);
- ``live+stale``: the other rows keep a retired sequence's length over a
  table of trash-block entries (what it handed before);
- ``dead``: every row length 0 — what walking the batch costs by itself.

``--queries N`` times the kernel with N queries a row instead (a decoder that
generates by blocks: a block's N positions share the row's length and attend
one another) at ``sdar_chat_fixed_length_decode``'s shape: 32 slots, GQA 32/4
over a fused pool (a position's 4 key and 4 value heads in one row), a table
163 blocks wide, 20 live rows of 300-2,300 positions; beside it the same
rows with one query, so that what N queries cost over one is read off two
lines. ``--two-limits`` (with an even ``--queries N``; PR 46) times what a
forward that carries two blocks a row hands the kernel: the row's first
``N / 2`` queries see ``length - N / 2`` positions and the others ``length``
(``paged_attention(limits=)``), beside ``N / 2`` queries that share the
length (the forward of one block) and one query; ``--live R --positions LO
HI`` set the live rows and the range their lengths are drawn from (the cell
after PR 45: ~27 rows of ~300-700). Table, lengths and limits are arguments
of every timed program. A line also
says the ``[query rows, columns]`` of the score tile one group of the kernel
works on (``score_tile``), what the live rows' keys and values take at the
HBM's rate (``bytes_us``) and the call's time over that (``over_bytes``), and
how far the kernel's output lies from the plain gather's on the live rows
(``gap``).

``--rows N`` overrides the KV rows a kernel step takes (the module's
``_ROWS_PER_STEP`` and a VMEM budget to match), to re-derive them. Fails
without a TPU unless ``--rehearse`` (tiny shapes, interpret mode: the
numbers then mean nothing).
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from unionml_tpu.ops import paged_attention as pa

BLOCK, HEAD_DIM, SLOTS = 16, 128, 32
HBM_BYTES_PER_S = 819e9  # a v5e's (chipbench/peaks.json)
# (name, q heads, kv heads, table width, pool blocks, live rows, mean live length)
SHAPES = [
    ("mixtral_chat_decode", 32, 8, 101, 2861, 13, 260),
    ("olmo_hybrid_longgen_decode", 32, 32, 261, 1621, 12, 850),
]
# --queries: the cell whose rows take several queries, over a fused pool
BLOCK_SHAPE = ("sdar_chat_fixed_length_decode", 32, 4, 163, 3814, 20, 1300)


def _case(rng, width, n_blocks, live, mean_len, stale, spread=None):
    """Table, lengths and the live positions: ``live`` rows among the low
    slots (an engine takes the lowest free slot) at lengths around
    ``mean_len``; the rest over trash-block entries, at length 0 or, if
    ``stale``, at ``mean_len``."""
    lengths = np.full(SLOTS, mean_len if stale else 0, np.int32)
    table = np.zeros((SLOTS, width), np.int32)
    blocks = iter(rng.permutation(np.arange(1, n_blocks)))
    rows = rng.choice(min(SLOTS, 2 * live), size=live, replace=False)
    for b in rows:
        if spread is None:
            lengths[b] = np.clip(rng.normal(mean_len, 0.4 * mean_len), 16, width * BLOCK)
        else:
            lengths[b] = rng.integers(*spread)
        need = -(-int(lengths[b]) // BLOCK)
        table[b, :need] = [next(blocks) for _ in range(need)]
    return jnp.asarray(table), jnp.asarray(lengths), int(lengths[rows].sum())


def score_tile(block, q_heads, kv_heads, head_dim, itemsize, width, *, queries, fused):
    """The tree's own count; an older tree's kernel (``PYTHONPATH``) scored
    every query row against every stored row of the group."""
    if hasattr(pa, "score_tile"):
        return pa.score_tile(
            block, q_heads, kv_heads, head_dim, itemsize, width, queries=queries, fused=fused,
        )
    positions = pa._pages_per_step(block, kv_heads, head_dim, itemsize, width) * block
    return [queries * q_heads, positions * kv_heads * (2 if fused else 1)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--queries", type=int, default=0)
    ap.add_argument("--two-limits", action="store_true",
                    help="the first half of a row's queries sees length - queries / 2 positions")
    ap.add_argument("--live", type=int, default=0, help="live rows of the --queries shape")
    ap.add_argument("--positions", type=int, nargs=2, default=(300, 2300),
                    help="the range a live row's length is drawn from (--queries)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"needs a TPU, found {device.platform}")
    if args.rows:  # with room for four buffers of that many 32-head bf16 rows
        pa._ROWS_PER_STEP = args.rows
        pa._KV_BUFFER_BYTES = max(pa._KV_BUFFER_BYTES, 4 * args.rows * 32 * HEAD_DIM * 2)
    reps = 2 if args.rehearse else args.reps

    shapes = [shape + (0, False) for shape in SHAPES]
    if args.queries:
        shape = BLOCK_SHAPE[:5] + (args.live or BLOCK_SHAPE[5],) + BLOCK_SHAPE[6:]
        shapes = [shape + (args.queries, False), shape + (1, False)]
        if args.two_limits:
            shapes = [shape + (args.queries, True), shape + (args.queries // 2, False), shape + (1, False)]
    for name, q_heads, kv_heads, width, n_blocks, live, mean_len, queries, limited in shapes:
        spread = tuple(args.positions) if queries else None
        if args.rehearse:
            width, n_blocks, mean_len, spread = 40, 200, 60, (30, 90) if queries else None
        rng = np.random.default_rng(args.seed)
        pool_shape = (n_blocks, BLOCK, kv_heads, HEAD_DIM)
        if queries:
            # one pool of fused rows: the key heads, the value heads behind them
            fused_shape = (n_blocks, BLOCK, 2 * kv_heads, HEAD_DIM)
            k = jnp.asarray(rng.standard_normal(fused_shape), jnp.bfloat16)
            v = None
            q = jnp.asarray(rng.standard_normal((SLOTS, queries, q_heads, HEAD_DIM)), jnp.bfloat16)
        else:
            k = jnp.asarray(rng.standard_normal(pool_shape), jnp.bfloat16)
            v = jnp.asarray(rng.standard_normal(pool_shape), jnp.bfloat16)
            q = jnp.asarray(rng.standard_normal((SLOTS, q_heads, HEAD_DIM)), jnp.bfloat16)

        def limits_of(lengths):
            """[rows, queries]: the first half of a row's queries sees the
            positions before the second half's own."""
            if not limited:
                return None
            back = jnp.repeat(jnp.asarray([queries // 2, 0], jnp.int32), queries // 2)
            return jnp.maximum(lengths[:, None] - back[None, :], 0)

        @jax.jit
        def loop(q, k, v, table, lengths, limits):
            kw = {} if limits is None else {"limits": limits}

            def body(_, x):
                return pa.paged_attention(x, k, v, table, lengths, impl="pallas", **kw)
            return jax.lax.fori_loop(0, reps, body, q)

        cases = (("live+zero", live, False), ("live+stale", live, True), ("dead", 0, False))
        for what, n_live, stale in cases:
            table, lengths, positions = _case(
                np.random.default_rng(args.seed + 1), width, n_blocks, n_live, mean_len, stale, spread,
            )
            limits = limits_of(lengths)
            kw = {} if limits is None else {"limits": limits}
            loop(q, k, v, table, lengths, limits).block_until_ready()
            gap = None
            if n_live and not stale:  # a stale row's output is garbage by contract
                got, want = (
                    pa.paged_attention(q, k, v, table, lengths, impl=impl, **kw).astype(jnp.float32)
                    for impl in ("pallas", "reference")
                )
                gap = float(jnp.max(jnp.abs(got - want)[np.asarray(lengths) > 0]))
            kv_bytes = positions * 2 * kv_heads * HEAD_DIM * 2
            times = []
            for _ in range(5):
                t0 = time.perf_counter()
                loop(q, k, v, table, lengths, limits).block_until_ready()
                times.append((time.perf_counter() - t0) / reps)
            print(json.dumps({
                "shape": name, "rows": what, "queries": queries or 1, "limits": 2 if limited else 1,
                "us_per_call": round(1e6 * float(np.median(times)), 2),
                "us_min": round(1e6 * min(times), 2), "live_rows": n_live, "live_positions": positions,
                "kv_mb": round(kv_bytes / 1e6, 2), "bytes_us": round(1e6 * kv_bytes / HBM_BYTES_PER_S, 2),
                "over_bytes": round(float(np.median(times)) * HBM_BYTES_PER_S / kv_bytes, 2)
                if positions else None,
                "score_tile": score_tile(
                    BLOCK, q_heads, kv_heads, HEAD_DIM, 2, width, queries=queries or 1, fused=v is None,
                ),
                "gap": gap,
                "pages_per_step": pa._pages_per_step(BLOCK, kv_heads, HEAD_DIM, 2, width),
                "device": device.device_kind, "platform": device.platform,
            }), flush=True)


if __name__ == "__main__":
    main()
