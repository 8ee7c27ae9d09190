"""The learned sparse attention's decode operations alone, at a served
cell's shapes.

    chiprun -- env PYTHONPATH=. python3 benchmarks/sparse_attention.py
    chiprun -- env PYTHONPATH=. python3 benchmarks/sparse_attention.py --live 6 16 --positions 8192 16384 \\
        --prefill 8192

One JSON line a case: microseconds a layer of one decode step for each
stage, from a jitted loop of ``--reps`` passes over ``--layers`` pools,
timed on the host's clock around ``block_until_ready``. The shape is
``chipbench``'s ``keye_vl2_long_context_decode``: 16 rows of 32 query heads
over 4 key / value heads of 128, an indexer of 16 heads of 64 (keys stored
128 wide), ``topk`` 2,048, pools of 2,260 blocks of 64 positions a layer
(a position's keys and values in one tile of 8 x 128: 296 MB; indexer keys
37 MB), a table 274 wide (``--block`` gives other block sizes over the same
bytes).
Twelve layers' pools are read in turn, as the cell's decode step reads them
(one pool carried through a loop stays in fast memory and reads faster than
a model's does). ``--live`` rows hold ``--positions`` cached positions each
(in scattered blocks) and the others none. **Every loop takes the table, the
lengths and the picks as arguments of its program**, as a decode chunk does:
closed over, they are constants the compiler folds into the gather, and the
picked rows then read three times faster than in the cell (PERF.md, PR 40).
The stages:

- ``scores``: ``paged_index_scores`` (the Pallas kernel);
- ``select``: ``sparse_attention.select_top_k`` (``jax.lax.top_k``: a sort);
- ``mask``: ``sparse_attention.top_k_mask`` over the same scores (the
  threshold descent a prefill uses; it yields no positions);
- ``attention``: ``paged_sparse_attention`` over picks handed in;
- ``chain``: scores, select and attention a layer, each feeding the next, as
  the chunk's loop runs them;
- ``dense``: ``paged_attention`` (the kernel every other decoder's step
  runs) over all of the same rows, in ``[blocks, block, 4, 128]`` pools.

Beside each: the bytes the stage must read as stored and their time at the
HBM rate. ``--prefill N`` also times one layer of the plain form over a
whole prompt of ``N`` tokens (``sparse_attention.sparse_attention``: index
scores and the selection as a mask in blocks of queries, the softmax over
the selected set by the kernel ``sparse_prefill_attention``). Fails without
a TPU unless ``--rehearse`` (tiny shapes, interpret mode: the numbers then
mean nothing). Not run by any cell or test.
"""

import argparse
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
        else (16, 32, 4, 128, 16, 64, 2048, args.block, 2_260 * 64 // args.block, -(-17_536 // args.block))
    )
    stored = -(-idim // 128) * 128
    layers, reps = (2, 1) if args.rehearse else (args.layers, args.reps)
    calls = layers * reps
    key = jax.random.PRNGKey(args.seed)
    rng = np.random.default_rng(args.seed)

    def normal(i, shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape, jnp.bfloat16)

    kv_pools = [normal(2 * i, (blocks, block, 2 * kv_heads, hd)) for i in range(layers)]
    i_pools = [normal(2 * i + 1, (blocks, block, stored)).at[..., idim:].set(0) for i in range(layers)]
    dense_pools = [(kv[:, :, :kv_heads] + 0, kv[:, :, kv_heads:] + 0) for kv in kv_pools]
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
            def select_loop(scores):
                def body(_, carry):
                    scores, acc = carry
                    for _ in range(layers):
                        picked, valid = sa.select_top_k(scores, topk)
                        scores = scores + (picked[:, :1] % 2).astype(scores.dtype) * 1e-6
                        acc = acc + picked[:, 0]
                    return scores, acc
                return jax.lax.fori_loop(0, reps, body, (scores, jnp.zeros((rows,), jnp.int32)))

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

            picked, valid = jax.jit(lambda s: sa.select_top_k(s, topk))(scores)
            in_mask = np.asarray(jax.jit(lambda s: sa.top_k_mask(s, topk))(scores))
            same_set = bool(all(
                set(row[ok].tolist()) == set(np.flatnonzero(in_mask[r]).tolist())
                for r, (row, ok) in enumerate(zip(np.asarray(picked), np.asarray(valid)))
            ))

            @jax.jit
            def attention_loop(q, kv_pools, table, picked, valid):
                def body(_, q):
                    for kv in kv_pools:
                        o = pa.paged_sparse_attention(q, kv, table, picked, valid)
                        q = q + (o * 1e-3).astype(q.dtype)
                    return q
                return jax.lax.fori_loop(0, reps, body, q)

            @jax.jit
            def chain_loop(q, iq, i_pools, kv_pools, table, lengths):
                def body(_, carry):
                    q, iq = carry
                    for pool, kv in zip(i_pools, kv_pools):
                        picked, valid = sa.select_top_k(scores_of(iq, pool, table, lengths), topk)
                        o = pa.paged_sparse_attention(q, kv, table, picked, valid)
                        q = q + (o * 1e-3).astype(q.dtype)
                        iq = iq.at[:, :, 0].add((q[:, :ih, 0] * 1e-3).astype(iq.dtype))
                    return q, iq
                return jax.lax.fori_loop(0, reps, body, (q, iq))

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
                ("scores", scores_loop, (iq, i_pools, table, lengths)), ("select", select_loop, (scores,)),
                ("mask", mask_loop, (scores,)),
                ("attention", attention_loop, (q, kv_pools, table, picked, valid)),
                ("chain", chain_loop, (q, iq, i_pools, kv_pools, table, lengths)),
                ("dense", dense_loop, (q, dense_pools, table, lengths)),
            ):
                try:
                    out[f"{name}_us"] = _timed(fn, *fn_args, calls=calls)
                except Exception as exc:  # a shape the compiler refuses: say so and go on
                    out[f"{name}_us"], out[f"{name}_error"] = None, str(exc)[:300]
            index_bytes = live * positions * stored * 2
            picked_bytes = live * min(positions, topk) * 2 * kv_heads * hd * 2
            dense_bytes = live * positions * 2 * kv_heads * hd * 2
            out.update(
                scores_bytes_as_stored=index_bytes,
                scores_us_at_hbm_rate=round(1e6 * index_bytes / HBM_BYTES_PER_S, 1),
                attention_bytes=picked_bytes,
                attention_us_at_hbm_rate=round(1e6 * picked_bytes / HBM_BYTES_PER_S, 1),
                dense_bytes=dense_bytes, dense_us_at_hbm_rate=round(1e6 * dense_bytes / HBM_BYTES_PER_S, 1),
                scores_max_off_gather_in_sd=round(off, 5), scores_same_visible=same_inf,
                select_and_mask_same_set=same_set,
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
