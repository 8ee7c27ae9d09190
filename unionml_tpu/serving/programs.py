"""The decode engine's device side: every program it traces, once.

:mod:`unionml_tpu.serving.engine` is the host (queue, admission,
dispatcher, harvester, recovery, stats); it calls this module, and this
module imports nothing of the host. :func:`build_programs` returns the
jitted programs of one engine. They share one state dict — what a
*residency* keeps, plus the per-slot ``fill`` / ``last_tok`` / ``done`` —
and one call signature: where a finished prefill goes, and where a
decode step finds a slot's history, is the ``place`` argument.

A residency owns the entries of the state dict that hold caches, and
four things about them: their ``init``, the ``commit`` of a filled fresh
cache into a slot, what a decode step hands ``module.apply`` and takes
back (``step_args`` / ``step_result``), and the ``extract`` that feeds
the prefix cache. There are two:

- :class:`SlotRows`: every slot owns ``rows`` contiguous cache rows of
  each served model and a row of the visibility mask ``kv_mask``;
  ``place`` is ``None``. A speculative engine keeps two caches this way
  (``cache`` for the target, ``d_cache`` for the draft) under one mask.
- :class:`BlockPool`: the layers that cache a row a token (keys and
  values, or the latent they are projections of: the layout entries
  that say ``owns_rows``) share a pool of blocks, ``place`` is the
  host's block ids (a prefill's) or block table (a decode chunk's), and
  the layers with a state of fixed size keep one per slot (``rec``).
  Visibility is ``fill + 1``.

A module that generates by blocks (``generation_scheme()`` returns a
:class:`~unionml_tpu.models.layers.BlockDiffusion`) is served from the
block pool by a chunk of its own, :func:`build_programs`'s
``block_chunk``: a scan step is one forward over ``[slots, 2 Bk]`` rows (a
slot's open block, or the block it closes and the next one behind it), the
state also holds every slot's open block, and a prefill commits whole
blocks and samples nothing.

A cache with another lifetime (window layers' ring) is a third residency;
a change to how prompts are prefilled is a change to the one prefill
family below (``init_fresh``, ``prefill_step``, ``finish_prefill``,
``prefill``), which runs over every served model and knows no residency.
Every program has static shapes: XLA compiles one executable per bucket
and program. The names of the traced functions are what the trace
readers match (``jit_prefill``, ``jit_decode_chunk``): keep them. So are
the ``jax.named_scope`` names round the work that no module of the model
owns (``sample``, ``commit``, ``step_io``; ``draft`` / ``verify`` /
``accept`` in a speculative round): a device trace carries them as each
operation's ``tf_op`` (docs/observability.md "Device time by model part").
"""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from unionml_tpu.models.speculative import greedy_acceptance

__all__ = ["BlockPool", "SlotRows", "build_programs", "cache_layout", "generation_scheme"]


def cache_layout(module):
    """What each layer of ``module`` caches (``models/layers.py``: a
    ``KVRows``, a ``LatentRows`` or a ``SlotState`` per layer): the module
    says, the engine does not assume."""
    layout = getattr(module, "cache_layout", None)
    if layout is None:
        raise TypeError(
            f"{type(module).__name__} has no cache_layout(): a decoder the "
            "engine can serve says what each of its layers caches "
            "(unionml_tpu.models.layers.KVRows / LatentRows / SlotState)"
        )
    return tuple(layout())


def generation_scheme(module):
    """How ``module`` generates: its :class:`~unionml_tpu.models.layers
    .BlockDiffusion` declaration, or ``None`` for a decoder that emits one
    token a forward (it declares nothing)."""
    scheme = getattr(module, "generation_scheme", None)
    return None if scheme is None else scheme()


def _splice_rows(dst_tree, src_tree, b_start, r_start):
    """Write ``src_tree``'s rows into ``dst_tree`` at (batch, row) offset
    ``(b_start, r_start)`` — per layer, per buffer, rank-generic (covers
    the bf16 [B, L, H, D] KV buffers, the int8-cache [B, L, H] scale
    planes and a state layer's arrays alike). The single home for the
    engine's cache splices (a finished prefill into its slot, a cached
    prefix block into a fresh cache)."""
    return tuple(
        tuple(
            jax.lax.dynamic_update_slice(
                dst, src.astype(dst.dtype),
                (b_start, r_start) + (0,) * (dst.ndim - 2),
            )
            for dst, src in zip(dst_layer, src_layer)
        )
        for dst_layer, src_layer in zip(dst_tree, src_tree)
    )


def _init_layers(layout, batch: int, rows: int, owns_rows=None):
    """Zeroed caches of the layers (all, or those that own cache rows or
    do not), ``batch`` sequences of ``rows`` positions."""
    return tuple(
        l.init(batch, rows) for l in layout
        if owns_rows is None or l.owns_rows == owns_rows
    )


def _join_layers(layout, rows, states):
    """The module's per-layer cache from the row layers' entries and the
    state layers', in layer order."""
    rows, states = iter(rows), iter(states)
    return tuple(
        next(rows) if l.owns_rows else next(states) for l in layout
    )


def _split_layers(layout, cache):
    """``(row layers' entries, state layers')`` of a per-layer cache."""
    return (
        tuple(c for c, l in zip(cache, layout) if l.owns_rows),
        tuple(c for c, l in zip(cache, layout) if not l.owns_rows),
    )


class SlotRows:
    """Residency: slot ``i`` owns rows ``[i, 0:rows]`` of every cache in
    ``layouts`` (state key -> the layout of the model it serves) and row
    ``i`` of ``kv_mask``. Empty slots idle at row 0: dead slots still run
    the decode apply and write garbage k/v at their fill row — row 0
    stays masked False and is overwritten by the next admission's
    full-bucket splice. ``place`` is ``None``."""

    def __init__(self, layouts: dict, slots: int, rows: int):
        self.layouts, self.slots, self.rows = layouts, slots, rows

    def init(self) -> dict:
        state = {
            key: _init_layers(layout, self.slots, self.rows)
            for key, layout in self.layouts.items()
        }
        state["kv_mask"] = jnp.zeros((self.slots, self.rows), bool)
        return state

    def commit(self, state, filled, slot, place, true_len) -> dict:
        """Splice each model's whole fresh cache into ``slot`` —
        cached-prefix rows spliced before the chunks ran are carried
        along; garbage rows above ``true_len`` stay masked False."""
        out = {
            key: _splice_rows(state[key], new, slot, 0)
            for key, new in zip(self.layouts, filled)
        }
        row_mask = jnp.arange(self.rows) < true_len
        out["kv_mask"] = state["kv_mask"].at[slot].set(row_mask)
        return out

    def step_args(self, state, live, place) -> dict:
        # this step writes its k/v at row `fill`; the new token must see
        # ITSELF, so expose the row before the apply — for live slots
        # only (dead slots' writes land on masked-False rows and stay
        # invisible)
        kv_mask = state["kv_mask"] | (
            (jnp.arange(self.rows)[None, :] == state["fill"][:, None])
            & live[:, None]
        )
        return {"cache": state["cache"], "kv_mask": kv_mask}

    def step_result(self, args, cache) -> dict:
        return {"cache": cache, "kv_mask": args["kv_mask"]}

    def extract_rows(self, state, slot, place, *, n):
        """A slot's leading ``n`` resident rows in ONE dispatch — the
        harvester splits the contiguous copy into blocks host-side."""
        return tuple(
            tuple(
                jax.lax.dynamic_slice(
                    buf, (slot, 0) + (0,) * (buf.ndim - 2),
                    (1, n) + buf.shape[2:],
                )
                for buf in layer
            )
            for layer in state["cache"]
        )

    extract = extract_rows


class BlockPool:
    """Residency: the layers that own rows share ``pool``, per buffer
    ``[num_blocks, block, ...]`` with the row's shape behind (``kv_heads,
    head_dim`` for keys and values, the latent's width for a latent
    layer: the copies below are rank-generic), addressed
    through the host-owned block table; the state layers keep one state
    per slot in ``rec`` (empty for a module without such layers),
    written whole when a prefill ends and updated in place by decode.
    Block 0 is the trash block: padding entries of a prefill's ids and
    the table rows of slots that are not live point at it, so an
    in-flight chunk can never write a block the allocator has recycled.
    There is no resident ``kv_mask``: visibility is ``fill + 1``."""

    def __init__(self, layout, slots: int, num_blocks: int, block: int):
        self.layout, self.slots = layout, slots
        self.num_blocks, self.block = num_blocks, block

    def init(self) -> dict:
        return {
            "pool": _init_layers(self.layout, self.num_blocks, self.block, True),
            "rec": _init_layers(self.layout, self.slots, 0, False),
        }

    def commit(self, state, filled, slot, place, true_len) -> dict:
        """Table-directed block scatter: the fresh ``[1, bucket]`` rows
        into pool blocks ``place`` ([bucket / block] int32; duplicate
        trash writes race benignly, it is garbage by definition), and
        the slot's states whole: whatever its last occupant left is
        overwritten (dst [slots, ...] <- src [1, ...])."""
        (filled,) = filled
        rows, states = _split_layers(self.layout, filled)
        nb = place.shape[0]
        pool = tuple(
            tuple(
                pbuf.at[place].set(
                    fbuf.reshape((nb, self.block) + fbuf.shape[2:])
                    .astype(pbuf.dtype)
                )
                for pbuf, fbuf in zip(p_layer, f_layer)
            )
            for p_layer, f_layer in zip(state["pool"], rows)
        )
        return {"pool": pool, "rec": _splice_rows(state["rec"], states, slot, 0)}

    def step_args(self, state, live, place) -> dict:
        # the table is a per-chunk INPUT (the host grows it between
        # chunks); rows of slots that are not live are re-masked to the
        # trash block EVERY step
        return {
            "block_table": jnp.where(live[:, None], place, 0),
            "cache": _join_layers(self.layout, state["pool"], state["rec"]),
        }

    def step_result(self, args, cache) -> dict:
        pool, rec = _split_layers(self.layout, cache)
        return {"pool": pool, "rec": rec}

    def extract_blocks(self, state, slot, place, *, n):
        """Gather pool blocks ``place`` ([n_blocks, block, ...] per
        buffer) for the async device→host prefix-cache insert —
        per-block copies addressed by table entries."""
        return tuple(
            tuple(jnp.take(buf, place, axis=0) for buf in layer)
            for layer in state["pool"]
        )

    extract = extract_blocks


def build_programs(
    module, *, draft=None, speculate_k: int = 0, slots: int, rows: int,
    pool_blocks=None, block=None, chunk_steps: int, sample, eos_id, pad_id,
    stale_commit: bool = False,
) -> SimpleNamespace:
    """The jitted programs of one engine: ``init_state``, ``init_fresh``,
    ``prefill``, ``prefill_step``, ``prefill_final``, ``decode_chunk``,
    ``splice_block``, ``extract``. ``pool_blocks`` selects the block-pool
    residency; a ``draft`` makes the chunk a scan of speculative rounds
    and ``params`` the bound ``{"target", "draft"}`` mapping; a module that
    generates by blocks makes it a scan of forwards over every slot's open
    block (``stale_commit`` builds that chunk wrongly on purpose: the
    broken path that the tests and the benchmark's control hold up)."""
    # the served models, and where each finds its parameters in what
    # bind() was given; the first one's logits are the ones sampled
    if draft is None:
        models = ((module, lambda p: p),)
    else:
        models = ((module, lambda p: p["target"]), (draft, lambda p: p["draft"]))
    layouts = tuple(cache_layout(m) for m, _ in models)
    if pool_blocks is not None:
        residency = BlockPool(layouts[0], slots, pool_blocks, block)
    else:
        residency = SlotRows(dict(zip(("cache", "d_cache"), layouts)), slots, rows)
    L, B = rows, slots
    first_rows = next(
        i for i, l in enumerate(layouts[0]) if l.owns_rows
    )

    scheme = generation_scheme(module)
    if scheme is not None and (draft is not None or pool_blocks is None):
        raise ValueError(
            f"{type(module).__name__} generates by blocks: its chunk runs over a block "
            "pool and takes no draft"
        )
    Bk = 1 if scheme is None else scheme.block_length

    def open_block():
        """A slot's open block with every entry undecided (``blk_gen``:
        the entries this sequence generates, the others being prompt)."""
        return {
            "blk_tok": jnp.full((Bk,), pad_id, jnp.int32),
            "blk_und": jnp.ones((Bk,), bool),
            "blk_gen": jnp.ones((Bk,), bool),
            "blk_at": jnp.zeros((Bk,), jnp.int32),
            "blk_fwd": jnp.zeros((), jnp.int32),
        }

    def init_state():
        state = {
            **residency.init(),
            "fill": jnp.zeros((B,), jnp.int32),
            "last_tok": jnp.zeros((B,), jnp.int32),
            "done": jnp.ones((B,), bool),
        }
        if scheme is not None:
            # every slot's open block, and the position its request ends at
            state.update(jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (B,) + x.shape), open_block(),
            ))
            state["stop"] = jnp.zeros((B,), jnp.int32)
        return state

    # ---- the prefill family. A prompt is computed against a transient
    # contiguous [1, bucket] fresh cache per model — one admission's
    # workspace, whatever the residency. Short buckets run `prefill`
    # (fresh build + finish in ONE program); long ones and prefix-cached
    # ones fill the fresh cache WITHOUT touching the resident state
    # (`prefill_step` lead chunks, `splice_block` cached blocks), so
    # decode chunks interleave between them, and only the final chunk
    # (`finish_prefill`) commits into the slot and samples token 0. ----

    def fresh_caches(bucket):
        with jax.named_scope("step_io"):
            return tuple(_init_layers(layout, 1, bucket) for layout in layouts)

    @functools.partial(jax.jit, static_argnames=("bucket",))
    def init_fresh(*, bucket):
        return fresh_caches(bucket)

    def prefill_step(params, fresh, toks, start):
        """One lead chunk: tokens are fully real (the host only runs
        chunks covering the true length; the final, possibly padded,
        chunk goes through ``finish_prefill``)."""
        lf = fresh[0][first_rows][0].shape[1]  # bucket (static)
        c = toks.shape[1]
        with jax.named_scope("step_io"):
            kv_mask = (jnp.arange(lf) < start + c)[None, :]
            positions = start + jnp.arange(c)[None, :]
        return tuple(
            model.apply(
                {"params": pick(params)}, toks, positions=positions,
                cache=cache, cache_index=start, kv_mask=kv_mask,
                # head output unused → DCE'd; the chunk only fills cache
                logit_index=jnp.zeros((1,), jnp.int32),
            )[1]
            for (model, pick), cache in zip(models, fresh)
        )

    def finish_prefill(params, state, fresh, slot, place, toks, start,
                       true_len, key, asked=None, *, full=False):
        """The SINGLE home for the prefill tail (monolithic, chunked,
        and prefix-cached admissions of every residency trace it — a
        desynced invariant here would corrupt one path silently): run
        ``toks`` (the whole right-padded bucket at ``start=0``, or the
        final chunk at its offset) against ``fresh``, sample the first
        token at the last REAL position, commit the fresh caches into
        ``slot``. ``full``: this call covers the whole visible history,
        so a model whose ``prefill_impl`` is ``"flash"`` may run it
        through the flash kernel (right-padded buckets need no pad mask:
        causal alone hides the trailing garbage).

        A module that generates by blocks: the ``true_len // Bk`` whole
        blocks of the prompt are committed (the rows of a trailing partial
        block lie past ``fill`` and are overwritten), **no token is
        sampled**, the ``true_len % Bk`` tokens held back open the slot's
        first block as decided entries, and the request ends at position
        ``true_len + asked``; what is returned in the first token's place
        is the number of entries held back."""
        bucket = fresh[0][first_rows][0].shape[1]
        c = toks.shape[1]
        with jax.named_scope("step_io"):
            kv_mask = (jnp.arange(bucket) < true_len)[None, :]
            positions = start + jnp.arange(c)[None, :]
            # head on the last REAL position only — the full-bucket head
            # would materialize [1, bucket, vocab] fp32
            last = jnp.reshape(true_len - 1 - start, (1,))
        outs = [
            model.apply(
                {"params": pick(params)}, toks, positions=positions,
                cache=cache, cache_index=start, kv_mask=kv_mask,
                logit_index=last,
                **(
                    {"full_prefill": True}
                    if full and model.config.prefill_impl == "flash" else {}
                ),
            )
            for (model, pick), cache in zip(models, fresh)
        ]
        if scheme is not None:
            with jax.named_scope("commit"):
                resident = residency.commit(
                    state, tuple(filled for _, filled in outs), slot, place, true_len
                )
                committed = true_len // Bk * Bk
                held = true_len - committed
                mine = jnp.arange(Bk) < held     # the prompt's entries of the first block
                block = {
                    **open_block(),
                    # (a prompt that ends on a block's end holds nothing back,
                    # and what a clamped slice reads is masked)
                    "blk_tok": jax.lax.dynamic_slice(toks[0], (committed - start,), (Bk,)),
                    "blk_und": ~mine, "blk_gen": ~mine,
                }
                return {
                    **state,
                    **resident,
                    **{k: state[k].at[slot].set(v) for k, v in block.items()},
                    "fill": state["fill"].at[slot].set(committed),
                    "done": state["done"].at[slot].set(False),
                    "stop": state["stop"].at[slot].set(true_len + asked),
                }, held
        with jax.named_scope("sample"):
            first = sample(outs[0][0][:, 0], key)[0]
        with jax.named_scope("commit"):
            resident = residency.commit(
                state, tuple(filled for _, filled in outs), slot, place, true_len
            )
            return {
                **resident,
                "fill": state["fill"].at[slot].set(true_len),
                "last_tok": state["last_tok"].at[slot].set(first),
                "done": state["done"].at[slot].set(False),
            }, first

    def prefill(params, state, slot, place, tokens, true_len, key, asked=None):
        """Monolithic admission: fresh build + full-bucket finish in
        ONE program (short buckets; one dispatch per admission)."""
        return finish_prefill(
            params, state, fresh_caches(tokens.shape[0]), slot, place,
            tokens[None], jnp.int32(0), true_len, key, asked, full=True,
        )

    def splice_block(fresh, rows, start):
        """One cached splice unit's host rows into a fresh cache at a
        dynamic row offset (compiled once per (bucket, unit) shape)."""
        (cache,) = fresh
        with jax.named_scope("commit"):
            return (_splice_rows(cache, rows, 0, start),)

    # ---- the decode chunk ----

    def decode_chunk(params, state, active, place, keys):
        """``chunk_steps`` decode steps for every slot in one scan."""
        ((model, pick),) = models

        def step(state, key):
            with jax.named_scope("step_io"):
                live = active & ~state["done"]
                fill = state["fill"]
                args = residency.step_args(state, live, place)
            logits, cache = model.apply(
                {"params": pick(params)}, state["last_tok"][:, None],
                cache_index=fill, live=live, **args,
            )
            with jax.named_scope("sample"):
                nxt = sample(logits[:, -1], key)
            with jax.named_scope("step_io"):
                resident = residency.step_result(args, cache)
                nxt = jnp.where(live, nxt, pad_id)
                done = state["done"]
                if eos_id is not None:
                    done = done | (live & (nxt == eos_id))
                advance = live & (fill + 1 < L)
                # belt: a live slot at the cache end freezes its fill on a
                # visible row — mark done so it stops writing there
                done = done | (live & ~advance)
                return {
                    **resident,
                    "fill": fill + advance.astype(jnp.int32),
                    "last_tok": jnp.where(live, nxt, state["last_tok"]),
                    "done": done,
                }, nxt

        state, toks = jax.lax.scan(step, state, keys)
        return state, toks  # toks: [chunk_steps, slots]

    def block_chunk(params, state, active, place, keys):
        """``chunk_steps`` forwards over every slot's open block in one
        scan. A forward runs ``2 Bk`` rows a slot at positions ``fill ..
        fill + 2 Bk - 1``, and every live slot *denoises* in it: the scheme
        picks the entries of the open block that it decides from their
        candidates' confidences, and the forward that decides a block's
        last asked entry emits the block's generated entries. A slot in the
        middle of a block runs it in the forward's first ``Bk`` rows (the
        mask token where undecided; their keys and values are provisional:
        the next forward overwrites them) and its second ``Bk`` rows are
        dead: written to the trash block, sent to no expert, seen by
        nobody. A slot whose block came in with none left to decide
        *closes* it in the same forward: the first ``Bk`` rows are the
        block's final tokens, whose keys and values are the block's for
        good (the *commit*), ``fill`` moves on by ``Bk``, and the next block
        opens in the second ``Bk`` rows, every entry the mask token, for
        its first denoising pass; a query of the closing half sees ``fill +
        Bk`` rows and one of the opening half ``fill + 2 Bk``. The head runs
        over the open block's ``Bk`` rows of each slot only. Slots denoise
        and close side by side: one program, per-slot flags. A request ends
        with its last asked entry decided (``stop``; an ``eos_id`` among a
        block's emitted tokens ends it there): its last block is never
        closed, and the entries past the asked length are never decided.

        Returns per forward ``(tokens [R, B, Bk], decided_at [R, B, Bk],
        info [R, B, 4])``: the open block after this forward's decisions,
        the forward of the block (0, 1, ...) that decided each entry, and
        ``(n_emit, first, n_decided, kind)``: the entries ``first ..
        first + n_emit - 1`` of ``tokens`` are emitted (0 unless this
        forward completed the block), ``n_decided`` entries were decided,
        and ``kind`` is 0 for a slot that ran nothing, 1 for a forward
        that denoised and 3 for one that also closed the block before
        (bit 1: a commit)."""
        ((model, pick),) = models

        def per_slot(flag, ndim):
            return flag.reshape((B,) + (1,) * ndim)

        def step(state, key):
            with jax.named_scope("step_io"):
                offs = jnp.arange(Bk)[None, :]
                live = active & ~state["done"]
                stop = state["stop"][:, None]
                fresh = open_block()
                came = {k: state[k] for k in fresh}
                # the forward's first half: the block as it came in
                first = jnp.where(came["blk_und"], scheme.mask_token_id, came["blk_tok"])
                closing = live & ~(came["blk_und"] & (state["fill"][:, None] + offs < stop)).any(-1)
                if stale_commit:
                    closing = jnp.zeros_like(live)      # this chunk's blocks move on below
                # the open block: where one closes, the next, in the second half
                blk = {
                    k: jnp.where(per_slot(closing, fresh[k].ndim), fresh[k], v) for k, v in came.items()
                }
                fill = state["fill"] + Bk * closing.astype(jnp.int32)
                und = blk["blk_und"]
                asked = fill[:, None] + offs < stop
                cand = und & asked
                ids = jnp.concatenate([first, jnp.full((B, Bk), scheme.mask_token_id, jnp.int32)], axis=1)
                runs = live[:, None] & jnp.concatenate(
                    [jnp.ones((B, Bk), bool), jnp.broadcast_to(closing[:, None], (B, Bk))], axis=1,
                )
                # the head runs over the open block's rows of each slot
                heads = Bk * closing.astype(jnp.int32)[:, None] + offs
                args = residency.step_args(state, live, place)
            logits, cache = model.apply(
                {"params": pick(params)}, ids, cache_index=state["fill"], live=runs, logit_index=heads,
                **args,
            )
            with jax.named_scope("sample"):
                flat = logits.reshape(B * Bk, -1)
                choice = sample(flat, key)
                with jax.named_scope("unmask"):
                    # a candidate's confidence is its softmax probability
                    picked = jnp.take_along_axis(flat, choice[:, None], axis=-1)[:, 0]
                    conf = jnp.exp(picked - jax.nn.logsumexp(flat, axis=-1)).reshape(B, Bk)
                    now = scheme.choose(conf, cand) & live[:, None]
                choice = choice.reshape(B, Bk).astype(jnp.int32)
            with jax.named_scope("step_io"):
                resident = residency.step_result(args, cache)
                tok = jnp.where(now, choice, blk["blk_tok"])
                und = und & ~now
                at = jnp.where(now, blk["blk_fwd"][:, None], blk["blk_at"])
                # the forward that decides a block's last asked entry emits it
                complete = live & ~(und & asked).any(-1)
                made = blk["blk_gen"] & asked
                done = state["done"]
                if eos_id is not None:
                    hit = made & (tok == eos_id)
                    ends = complete & hit.any(-1)
                    made = made & jnp.where(
                        ends[:, None], offs <= jnp.argmax(hit, axis=-1)[:, None], True,
                    )
                    done = done | ends
                n_emit = jnp.where(complete, made.sum(-1), 0)
                # a request ends with its last block, and a slot at the cache's
                # end (belt: the host's budget stops it first) with this one
                done = done | (complete & (
                    (fill + Bk >= state["stop"]) | (fill + 2 * Bk > L)
                ))
                info = jnp.stack([
                    n_emit, jnp.argmax(made, axis=-1), now.sum(-1),
                    live.astype(jnp.int32) + 2 * closing.astype(jnp.int32),
                ], axis=-1).astype(jnp.int32)
                out = (jnp.where(live[:, None], tok, pad_id), at, info)
                opened = {
                    "blk_tok": tok, "blk_und": und, "blk_gen": blk["blk_gen"], "blk_at": at,
                    "blk_fwd": blk["blk_fwd"] + live.astype(jnp.int32),
                }
                if stale_commit:
                    # WRONG on purpose: the rows this forward wrote, with the
                    # mask token where it decided, are kept as the block's
                    opened = {
                        k: jnp.where(per_slot(complete, fresh[k].ndim), fresh[k], v)
                        for k, v in opened.items()
                    }
                    fill = fill + Bk * complete.astype(jnp.int32)
                return {**state, **resident, **opened, "fill": fill, "done": done}, out

        state, outs = jax.lax.scan(step, state, keys)
        return state, outs

    def spec_chunk(params, state, active, place, keys):
        """``chunk_steps`` speculative rounds in one scan over the two
        :class:`SlotRows` caches: per-slot draft proposals (vector
        ``cache_index``), ONE shared [slots, k+1] verify forward, greedy
        acceptance advancing per-slot fills — the
        ``make_speculative_generator`` round body (same acceptance /
        emission / eos invariants; a desync there breaks token identity)
        restructured for the resident slot batch. Returns per-round
        ``(emit [R, B, k+1], n_emit [R, B], accepted [R, B])`` — the
        host credits each slot ``n_emit`` tokens per round
        (eos-truncated device-side, budget-truncated host-side like the
        plain path)."""
        k = speculate_k
        arange_l = jnp.arange(L)[None, :]

        def round_body(state, _):
            with jax.named_scope("step_io"):
                live = active & ~state["done"]
                fill0 = state["fill"]

            # draft proposes k tokens over k+1 steps (the extra step
            # consumes proposal k so a fully-accepted round leaves no
            # draft-cache hole — the make_speculative_generator rule)
            def dstep(c, _):
                d_cache, tok, f = c
                vis = state["kv_mask"] | (
                    (arange_l >= fill0[:, None])
                    & (arange_l <= f[:, None])
                    & live[:, None]
                )
                logits, d_cache = draft.apply(
                    {"params": params["draft"]}, tok[:, None],
                    cache=d_cache, cache_index=f, kv_mask=vis,
                )
                nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
                return (d_cache, nxt, f + 1), nxt

            with jax.named_scope("draft"):
                (d_cache, _, _), props = jax.lax.scan(
                    dstep, (state["d_cache"], state["last_tok"], fill0),
                    None, length=k + 1,
                )
                props = props.transpose(1, 0)[:, :k]          # [B, k]

            # ONE shared multi-token verify forward for every slot
            with jax.named_scope("verify"):
                verify_in = jnp.concatenate(
                    [state["last_tok"][:, None], props], axis=1
                )
                vis_v = state["kv_mask"] | (
                    (arange_l >= fill0[:, None])
                    & (arange_l <= (fill0 + k)[:, None])
                    & live[:, None]
                )
                v_logits, cache = module.apply(
                    {"params": params["target"]}, verify_in,
                    cache=state["cache"], cache_index=fill0, kv_mask=vis_v,
                )
            with jax.named_scope("accept"):
                greedy = jnp.argmax(v_logits, -1).astype(jnp.int32)
                accepted, correction, emit = greedy_acceptance(props, greedy)
                n_emit = jnp.where(live, accepted + 1, 0)
                done = state["done"]
                if eos_id is not None:
                    pos_idx = jnp.arange(k + 1)[None, :]
                    eos_hit = (emit == eos_id) & (pos_idx < n_emit[:, None])
                    any_eos = eos_hit.any(axis=1)
                    first_eos = jnp.argmax(eos_hit, axis=1)
                    n_emit = jnp.where(
                        any_eos, jnp.minimum(n_emit, first_eos + 1), n_emit
                    )
                    done = done | (live & any_eos)
                # rows consumed = accepted + 1 (eos shrinks EMISSION, not
                # the cache rows written — done stops later rounds)
                advance = jnp.where(live, accepted + 1, 0)
                new_fill = fill0 + advance
                # freeze before the end: the next round writes k+1 rows
                done = done | (live & (new_fill + k + 1 >= L))
                new_kv = state["kv_mask"] | (
                    (arange_l >= fill0[:, None])
                    & (arange_l < new_fill[:, None])
                )
                new_last = jnp.where(live, correction, state["last_tok"])
                out = (
                    jnp.where(live[:, None], emit, pad_id),
                    n_emit.astype(jnp.int32),
                    jnp.where(live, accepted, 0).astype(jnp.int32),
                )
                return {
                    "cache": cache,
                    "d_cache": d_cache,
                    "kv_mask": new_kv,
                    "fill": new_fill,
                    "last_tok": new_last,
                    "done": done,
                }, out

        state, outs = jax.lax.scan(
            round_body, state, None, length=chunk_steps
        )
        return state, outs

    chunk = decode_chunk if draft is None else spec_chunk
    if scheme is not None:
        # the trace readers find a served cell's chunk by the name
        # jit_decode_chunk, whatever a step of it is
        chunk = block_chunk
        chunk.__name__ = chunk.__qualname__ = "decode_chunk"
    # the resident state is donated through every program that returns
    # it, so the multi-GB cache never copies. prefill_final donates the
    # state only: no output matches the fresh cache's [1, bucket] shape,
    # so donating it would just warn
    return SimpleNamespace(
        init_state=jax.jit(init_state),
        init_fresh=init_fresh,
        prefill=jax.jit(prefill, donate_argnums=(1,)),
        prefill_step=jax.jit(prefill_step, donate_argnums=(1,)),
        prefill_final=jax.jit(finish_prefill, donate_argnums=(1,)),
        decode_chunk=jax.jit(chunk, donate_argnums=(1,)),
        splice_block=jax.jit(splice_block, donate_argnums=(0,)),
        extract=jax.jit(residency.extract, static_argnames=("n",)),
    )
