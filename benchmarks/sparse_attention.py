"""The learned sparse attention's decode operations alone, at a served
cell's shapes.

    chiprun -- env PYTHONPATH=. python3 benchmarks/sparse_attention.py
    chiprun -- env PYTHONPATH=. python3 benchmarks/sparse_attention.py --live 6 16 --positions 8192 16384 \\
        --prefill 8192
    ... --live 16 --positions 17536 --blocks 4400 --layers 6 --legs mask walk      (the table's worst case)
    ... --live 6 --positions 8192 --legs walk --group-rows 512                     (another group size)

One JSON line a case: microseconds a layer of one decode step for each
stage, from a jitted loop of ``--reps`` passes over ``--layers`` pools,
timed on the host's clock around ``block_until_ready``. The shape is
``chipbench``'s ``keye_vl2_long_context_decode``: 16 rows of 32 query heads
over 4 key / value heads of 128, an indexer of 16 heads of 64 (keys stored
128 wide), ``topk`` 2,048, pools of 2,260 blocks of 64 positions a layer
(a position's keys and values in one tile of 8 x 128: 296 MB; indexer keys
37 MB; ``--blocks`` sizes another pool), a table 274 wide (``--block`` gives
other block sizes over the same bytes).
Twelve layers' pools are read in turn, as the cell's decode step reads them
(one pool carried through a loop stays in fast memory and reads faster than
a model's does). ``--live`` rows hold ``--positions`` cached positions each
(in scattered blocks) and the others none: dead rows are part of every
batch. **Every loop takes the table, the lengths and the selection as
arguments of its program**, as a decode chunk does: closed over, they are
constants the compiler folds in, and a stage then reads up to three times
faster than in the cell (PERF.md, PR 40). The stages (``--legs`` times some):

- ``scores``: ``paged_index_scores`` (the Pallas kernel);
- ``mask``: ``sparse_attention.top_k_mask`` over those scores (the exact
  top-2,048 as a mask: the threshold descent, no sort);
- ``walk``: ``paged_sparse_attention`` (the Pallas kernel: the row's live
  blocks walked with the mask handed in; its output is also compared with
  the plain form's, dead rows with zeros);
- ``chain``: scores, mask and walk a layer, each feeding the next, as the
  chunk's loop runs them;
- ``dense``: ``paged_attention`` (the kernel every other decoder's step
  runs) over all of the same rows, in ``[blocks, block, 4, 128]`` pools.

Read on a TPU v5e (my chip runs, PR 42), us a layer at 16 live rows x 8,192
positions / 8 x 16,384 / 6 x 8,192 / 16 x 17,536 (the last with ``--blocks
4400 --layers 6``: more than the cell's pool can hold):

- ``walk``, groups of 1,024 positions: **368.7 / 369.6 / 146.9 / 815.2**
  (groups of 512: 401.8 / 403.0 / 158.3 / 870.8; of 256: 505.2 / - / 196.3 / -);
- the gather it replaced (one XLA gather of 2,048 tiles a row, live or
  not; deleted in PR 42): 413.7 / 626.6 / 681.6 / 451.6;
- ``mask`` 98.3 / 98.9 / 98.2 / 106.3 beside ``jax.lax.top_k`` (the sort it
  replaced) 271.8 / 270.6 / 269.2 / 280.0;
- ``scores`` 160.5 / 160.5 / 93.3; ``dense`` 377.3 / 379.2 / 149.9;
- ``chain``: **571.2 / 571.8 / 283.7** (groups of 512: 604.1 / 605.0 / 294.2;
  with the sort and the gather, PR 40: 1,124.7 / 1,317.4 / 1,307.1).

Beside each stage: the bytes it must read as stored and their time at the
HBM rate. ``--prefill N`` also times one layer of the plain form over a
whole prompt of ``N`` tokens (``sparse_attention.sparse_attention``: index
scores and the selection as a mask in blocks of queries, the softmax over
the selected set by the kernel ``sparse_prefill_attention``). Fails without
a TPU unless ``--rehearse`` (tiny shapes, interpret mode: the numbers then
mean nothing). Not run by any cell or test.
"""

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from unionml_tpu.ops import paged_attention as pa
from unionml_tpu.ops import sparse_attention as sa

HBM_BYTES_PER_S = 819e9  # TPU v5e (chipbench/peaks.json)


def _timed(fn, *args, calls: int):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) / calls)
    return round(1e6 * float(np.median(times)), 1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", type=int, nargs="*", default=[6, 16])
    ap.add_argument("--positions", type=int, nargs="*", default=[8192, 16384])
    ap.add_argument("--prefill", type=int, nargs="*", default=[])
    ap.add_argument("--block", type=int, default=64, help="positions a pool block holds")
    ap.add_argument("--blocks", type=int, default=2_260, help="blocks of 64 positions a layer's pool holds")
    ap.add_argument("--legs", nargs="*", default=None, help="the stages to time (default: all)")
    ap.add_argument("--group-rows", type=int, default=None, help="positions a group of the walk holds")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"needs a TPU, found {device.platform}")
    rows, q_heads, kv_heads, hd, ih, idim, topk, block, blocks, width = (
        (4, 4, 2, 16, 4, 8, 8, 8, 64, 12) if args.rehearse
        else (16, 32, 4, 128, 16, 64, 2048, args.block, args.blocks * 64 // args.block,
              -(-17_536 // args.block))
    )
    stored = -(-idim // 128) * 128
    if args.group_rows:
        pa._SPARSE_ROWS_PER_STEP = args.group_rows
    layers, reps = (2, 1) if args.rehearse else (args.layers, args.reps)
    calls = layers * reps
    key = jax.random.PRNGKey(args.seed)
    rng = np.random.default_rng(args.seed)

    def normal(i, shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape, jnp.bfloat16)

    kv_pools = [normal(2 * i, (blocks, block, 2 * kv_heads, hd)) for i in range(layers)]
    i_pools = [normal(2 * i + 1, (blocks, block, stored)).at[..., idim:].set(0) for i in range(layers)]
    legs = set(args.legs or ("scores", "mask", "walk", "chain", "dense"))
    dense_pools = (
        [(kv[:, :, :kv_heads] + 0, kv[:, :, kv_heads:] + 0) for kv in kv_pools] if "dense" in legs else []
    )
    q = normal(1000, (rows, q_heads, hd))
    iq = normal(1001, (rows, ih, stored)).at[..., idim:].set(0)
    iw = jax.random.normal(jax.random.fold_in(key, 1002), (rows, ih), jnp.float32)

    for live in args.live:
        for positions in args.positions:
            positions = min(positions, width * block)
            need = -(-positions // block)
            live = min(live, rows, (blocks - 1) // need)     # what the pool holds
            table = np.zeros((rows, width), np.int32)
            table[:live, :need] = rng.permutation(blocks - 1)[: live * need].reshape(live, need) + 1
            lengths = np.zeros((rows,), np.int32)
            lengths[:live] = positions
            table, lengths = jnp.asarray(table), jnp.asarray(lengths)

            def scores_of(iq, pool, table, lengths):
                return pa.paged_index_scores(iq, iw, pool, table, lengths, impl="pallas")

            @jax.jit
            def scores_loop(iq, pools, table, lengths):
                def body(_, iq):
                    for pool in pools:
                        s = scores_of(iq, pool, table, lengths)
                        iq = iq.at[:, :, 0].add((jnp.max(s, axis=-1)[:, None] * 1e-6).astype(iq.dtype))
                    return iq
                return jax.lax.fori_loop(0, reps, body, iq)

            scores = jax.jit(scores_of)(iq, i_pools[0], table, lengths)
            ref = np.asarray(jax.jit(
                lambda iq, pool: pa.paged_index_scores(iq, iw, pool, table, lengths, impl="reference")
            )(iq, i_pools[0]))
            finite = np.isfinite(ref)
            off = float(np.abs(np.asarray(scores)[finite] - ref[finite]).max() / ref[finite].std())
            same_inf = bool((np.isfinite(np.asarray(scores)) == finite).all())

            @jax.jit
            def mask_loop(scores):
                def body(_, carry):
                    scores, acc = carry
                    for _ in range(layers):
                        n = jnp.sum(sa.top_k_mask(scores, topk), axis=-1)
                        scores = scores + (n[:, None] % 2).astype(scores.dtype) * 1e-6
                        acc = acc + n
                    return scores, acc
                return jax.lax.fori_loop(0, reps, body, (scores, jnp.zeros((rows,), jnp.int32)))

            # the mask against the sort it replaced: the same set, ties and all
            in_mask = np.asarray(jax.jit(lambda s: sa.top_k_mask(s, topk))(scores))
            values, picks = (np.asarray(x) for x in jax.jit(lambda s: jax.lax.top_k(s, topk))(scores))
            same_set = bool(all(
                set(row[np.isfinite(v)].tolist()) == set(np.flatnonzero(in_mask[r]).tolist())
                for r, (row, v) in enumerate(zip(picks, values))
            ))

            @jax.jit
            def walk_loop(q, kv_pools, table, lengths, selected):
                def body(_, q):
                    for kv in kv_pools:
                        o = pa.paged_sparse_attention(q, kv, table, lengths, selected, impl="pallas")
                        q = q + (o * 1e-3).astype(q.dtype)
                    return q
                return jax.lax.fori_loop(0, reps, body, q)

            @jax.jit
            def chain_loop(q, iq, i_pools, kv_pools, table, lengths):
                def body(_, carry):
                    q, iq = carry
                    for pool, kv in zip(i_pools, kv_pools):
                        selected = sa.top_k_mask(scores_of(iq, pool, table, lengths), topk)
                        o = pa.paged_sparse_attention(q, kv, table, lengths, selected, impl="pallas")
                        q = q + (o * 1e-3).astype(q.dtype)
                        iq = iq.at[:, :, 0].add((q[:, :ih, 0] * 1e-3).astype(iq.dtype))
                    return q, iq
                return jax.lax.fori_loop(0, reps, body, (q, iq))

            # the walk's output against the plain form's over the same selection
            selected = jnp.asarray(in_mask)
            walked, plain = (
                jax.jit(functools.partial(pa.paged_sparse_attention, impl=impl))(
                    q, kv_pools[0], table, lengths, selected).astype(jnp.float32)
                for impl in ("pallas", "reference")
            )
            walk_off = float(jnp.abs(walked - plain).max())
            dead_zero = bool(not np.asarray(walked)[live:].any())

            @jax.jit
            def dense_loop(q, pools, table, lengths):
                def body(_, q):
                    for k, v in pools:
                        o = pa.paged_attention(q, k, v, table, lengths, impl="pallas")
                        q = q + (o * 1e-3).astype(q.dtype)
                    return q
                return jax.lax.fori_loop(0, reps, body, q)

            out = {"live_rows": live, "positions_each": positions, "topk": topk, "block": block}
            for name, fn, fn_args in (
                ("scores", scores_loop, (iq, i_pools, table, lengths)), ("mask", mask_loop, (scores,)),
                ("walk", walk_loop, (q, kv_pools, table, lengths, selected)),
                ("chain", chain_loop, (q, iq, i_pools, kv_pools, table, lengths)),
                ("dense", dense_loop, (q, dense_pools, table, lengths)),
            ):
                if name not in legs:
                    continue
                try:
                    out[f"{name}_us"] = _timed(fn, *fn_args, calls=calls)
                except Exception as exc:  # a shape the compiler refuses: say so and go on
                    out[f"{name}_us"], out[f"{name}_error"] = None, str(exc)[:300]
            index_bytes = live * positions * stored * 2
            selected_bytes = live * min(positions, topk) * 2 * kv_heads * hd * 2
            dense_bytes = live * positions * 2 * kv_heads * hd * 2
            out.update(
                scores_bytes_as_stored=index_bytes,
                scores_us_at_hbm_rate=round(1e6 * index_bytes / HBM_BYTES_PER_S, 1),
                selected_bytes=selected_bytes,
                selected_us_at_hbm_rate=round(1e6 * selected_bytes / HBM_BYTES_PER_S, 1),
                dense_bytes=dense_bytes, dense_us_at_hbm_rate=round(1e6 * dense_bytes / HBM_BYTES_PER_S, 1),
                scores_max_off_gather_in_sd=round(off, 5), scores_same_visible=same_inf,
                mask_is_top_k_set=same_set, walk_max_off_plain=round(walk_off, 5),
                walk_dead_rows_zero=dead_zero, walk_group_rows=pa._SPARSE_ROWS_PER_STEP,
                layers=layers, rows=rows, device=device.device_kind, platform=device.platform,
            )
            print(json.dumps(out), flush=True)

    for seq in args.prefill:
        seq = 64 if args.rehearse else seq
        qs = normal(2000, (1, seq, q_heads, hd))
        ks, vs = normal(2001, (1, seq, kv_heads, hd)), normal(2002, (1, seq, kv_heads, hd))
        iqs, iks = normal(2003, (1, seq, ih, idim)), normal(2004, (1, seq, idim))
        iws = jax.random.normal(jax.random.fold_in(key, 2005), (1, seq, ih), jnp.float32)
        pos = jnp.arange(seq)[None, :]
        whole = jnp.ones((1, seq), bool)
        fn = jax.jit(lambda q, k, v, iq, ik, iw, valid: sa.sparse_attention(
            q, k, v, iq, ik, iw, pos, valid, topk=topk, scale=hd ** -0.5))
        line = {
            "prefill_tokens": seq, "softmax": "kernel" if sa._use_kernel(seq, seq, hd) else "plain",
            "select": ("kernel" if sa._use_kernel(seq, seq, hd) and sa._select_fits(seq, seq, idim)
                       else "plain"),
            "query_block": sa._query_block(seq, seq, q_heads), "device": device.device_kind,
        }
        outs = {}
        # a whole prompt, then one that fills five eighths of its bucket
        for name, valid in (("whole", whole), ("five_eighths", pos < seq * 5 // 8)):
            try:
                line[f"sparse_attention_us_a_layer_{name}"] = _timed(
                    fn, qs, ks, vs, iqs, iks, iws, valid, calls=1)
                outs[name] = fn(qs, ks, vs, iqs, iks, iws, valid)
            except Exception as exc:
                line[f"error_{name}"] = str(exc)[:300]
        # the same prompt with scores and selection in plain JAX (the form
        # before the select kernel): its time, and how far the two lie apart
        fits = sa._select_fits
        sa._select_fits = lambda *a: False
        try:
            plain = jax.jit(lambda q, k, v, iq, ik, iw, valid: sa.sparse_attention(
                q, k, v, iq, ik, iw, pos, valid, topk=topk, scale=hd ** -0.5))
            line["plain_select_us_a_layer_whole"] = _timed(plain, qs, ks, vs, iqs, iks, iws, whole, calls=1)
            for name, valid in (("whole", whole), ("five_eighths", pos < seq * 5 // 8)):
                if name in outs:
                    n = seq if name == "whole" else seq * 5 // 8
                    want = plain(qs, ks, vs, iqs, iks, iws, valid)[:, :n].astype(jnp.float32)
                    line[f"max_off_plain_select_{name}"] = float(
                        jnp.max(jnp.abs(outs[name][:, :n].astype(jnp.float32) - want)))
        except Exception as exc:
            line["error_plain"] = str(exc)[:300]
        finally:
            sa._select_fits = fits
        if line["softmax"] == "kernel" and seq <= 8192 and "whole" in outs:
            # both kernels against the plain form of everything (blocks of queries in plain JAX)
            use = sa._use_kernel
            sa._use_kernel = lambda *a: False
            try:
                form = jax.jit(lambda q, k, v, iq, ik, iw, valid: sa.sparse_attention(
                    q, k, v, iq, ik, iw, pos, valid, topk=topk, scale=hd ** -0.5))
                want = form(qs, ks, vs, iqs, iks, iws, whole)
                off = jnp.abs(outs["whole"].astype(jnp.float32) - want.astype(jnp.float32))
                line["max_off_plain_form_whole"] = float(off.max())
                line["mean_off_plain_form_whole"] = float(off.mean())
            except Exception as exc:
                line["error_plain_form"] = str(exc)[:300]
            finally:
                sa._use_kernel = use
        if line["select"] == "kernel":
            # the selected sets themselves: the kernel's against top_k_mask's
            last = jnp.max(pos.reshape(-1, 128), axis=1)
            tiles = sa.select_mask_tiles(iqs, iks, iws, pos, None, last, topk=topk)
            got = jnp.swapaxes(tiles, 2, 3).reshape(1, seq, seq) > 0
            picked, other = [], 0
            for lo in range(0, seq, 512):
                rows_pos = pos[:, lo:lo + 512]
                vis = jnp.arange(seq)[None, None, :] <= rows_pos[..., None]
                sc = jnp.where(vis, sa.index_scores(iqs[:, lo:lo + 512], iks, iws[:, lo:lo + 512]), -jnp.inf)
                want = sa.top_k_mask(sc, topk)
                g = got[:, lo:lo + 512] & vis           # tiles past a query's last key are unwritten
                picked.append(jnp.sum(g, axis=-1))
                other += int(jnp.sum(g != want))
            picked = jnp.concatenate(picked, axis=1)
            # the two kernels apart
            soft = jax.jit(lambda q, k, v, t: sa.masked_attention(q, k, v, t, last, scale=hd ** -0.5))
            line["softmax_kernel_us"] = _timed(soft, qs, ks, vs, tiles, calls=1)
            sel = jax.jit(lambda iq, ik, iw: sa.select_mask_tiles(iq, ik, iw, pos, None, last, topk=topk))
            line["select_kernel_us"] = _timed(sel, iqs, iks, iws, calls=1)
            line["selected_a_query_ok"] = bool(jnp.all(picked == jnp.minimum(pos + 1, topk)))
            line["picks_other_than_plain"] = other      # bfloat16 products summed in another order: near-ties
            line["picks_in_all"] = int(jnp.sum(picked))
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
