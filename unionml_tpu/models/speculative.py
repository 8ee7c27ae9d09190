"""Speculative decoding: draft proposes, target verifies in one forward.

No reference counterpart (the reference has no generation loop at all);
this is the standard latency optimization for autoregressive serving: a
small DRAFT model greedily proposes ``k`` tokens, the TARGET model
scores all ``k + 1`` positions in ONE forward, and the longest prefix
of draft tokens matching the target's own greedy choices is accepted —
plus the target's next token as a free correction/extension. With the
greedy acceptance rule the output is **token-identical to plain greedy
decoding of the target** (tested), so speculation is purely a latency
knob: each accepted draft token replaces one full target decode step
with its share of one batched verify forward.

TPU-first design:

- the whole generation is ONE jitted ``lax.while_loop`` — no host round
  trips per round;
- per-row acceptance counts differ, so both caches advance by per-row
  amounts — the vector ``cache_index`` path of
  :class:`~unionml_tpu.models.layers.Attention` (built for the
  continuous-batching engine) makes the ``[B, k+1]`` verify forward a
  single program with per-row write offsets;
- rejected draft rows become stale cache entries ABOVE each row's fill;
  visibility follows ``kv_pos <= q_pos`` from the per-row index, and
  every stale row is rewritten by the next round's forward (which
  always covers ``fill .. fill+k``) before it could become visible;
- static shapes throughout: the draft scan is ``k`` fixed steps, the
  verify is ``k + 1`` tokens, and the while_loop trip count is
  data-dependent (fine for inference — no reverse-mode through it),
  bounded by ``max_new_tokens`` rounds since every live row emits at
  least one token per round.

Greedy only: sampled speculative decoding needs the rejection-sampling
correction to keep the target distribution; the greedy rule is exact
and is what the equality tests pin down.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from unionml_tpu.models.llama import Llama, init_cache
from unionml_tpu.models.train import resolve_params

__all__ = [
    "greedy_acceptance",
    "make_speculative_generator",
    "make_speculative_predictor",
]


def greedy_acceptance(proposals: jnp.ndarray, greedy: jnp.ndarray):
    """The greedy acceptance rule — ONE home (this generator's round body
    and the DecodeEngine's speculative round both trace it; a desync
    breaks their shared token-identity-with-plain-greedy contract).

    ``proposals`` [B, k] (draft tokens), ``greedy`` [B, k+1] (the
    target's argmax at each verify position). Draft token i is accepted
    iff it equals the target's choice after position i-1 AND every
    earlier proposal was accepted. Returns ``(accepted [B], correction
    [B], emit [B, k+1])`` — the count of accepted draft tokens, the
    target's next token after the accepted prefix (free
    correction/extension), and the emission buffer holding the accepted
    prefix with the correction at position ``accepted``.
    """
    batch, k = proposals.shape
    rows = jnp.arange(batch)
    match = proposals == greedy[:, :k]
    accepted = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
    correction = jnp.take_along_axis(greedy, accepted[:, None], axis=1)[:, 0]
    emit = jnp.concatenate(
        [proposals, jnp.zeros((batch, 1), jnp.int32)], axis=1
    )
    emit = emit.at[rows, accepted].set(correction)
    return accepted, correction, emit


def make_speculative_generator(
    target: Llama,
    draft: Llama,
    *,
    max_new_tokens: int,
    speculate_k: int = 4,
    max_len: Optional[int] = None,
    eos_id: Optional[int] = None,
    pad_id: int = 0,
    with_stats: bool = False,
) -> Callable:
    """Build ``generate(target_params, draft_params, tokens) ->
    tokens [B, max_new_tokens]`` (greedy, == plain target decoding).

    ``tokens``: int32 [B, prompt_len], equal lengths (bucket upstream —
    the :func:`~unionml_tpu.models.generate.make_lm_predictor` pattern).
    ``target`` and ``draft`` must share the vocabulary; the draft is
    typically 4-10x smaller, and a round costs ``k + 1`` draft steps
    (the extra step consumes the last proposal so the draft cache stays
    hole-free across fully-accepted rounds) plus one (k+1)-token target
    forward, for ``accepted + 1`` emitted tokens — profitable when the
    draft is much cheaper than the target and acceptance is high.

    ``with_stats=True``: returns ``(tokens, {"rounds": [..],
    "accepted": [..]})`` per batch row — rounds taken and total draft
    tokens accepted (the acceptance-rate observability the equality
    tests can't see).

    ``generate`` also takes an optional ``true_lens`` int vector [B] for
    RIGHT-padded prompt batches (the serving-bucket form): each row's
    caches fill only to its true length, the first token reads that
    row's last REAL position, and the pad-garbage cache rows sit above
    the fill where visibility (``kv_pos <= q_pos``) cannot reach them
    before a later round overwrites them (fill advances ≤ k+1 per round
    while rounds write ``fill..fill+k`` — no row can be skipped).
    """
    t_cfg, d_cfg = target.config, draft.config
    if t_cfg.vocab_size != d_cfg.vocab_size:
        raise ValueError(
            f"target/draft vocabularies differ: {t_cfg.vocab_size} vs "
            f"{d_cfg.vocab_size}"
        )
    k = int(speculate_k)
    if k < 1:
        raise ValueError(f"speculate_k must be >= 1, got {k}")

    def generate(
        target_params, draft_params, tokens: jnp.ndarray, true_lens=None
    ) -> jnp.ndarray:
        batch, prompt_len = tokens.shape
        # + k + 1 slack: a round writes up to k+1 rows past a row's fill
        # before acceptance truncates it
        total = (max_len or (prompt_len + max_new_tokens)) + k + 1
        rows = jnp.arange(batch)

        # prefill BOTH models on the full prompt; each row's fill counts
        # cache rows written, and the last emitted token is consumed by
        # the NEXT forward (standard KV bookkeeping)
        t_cache = init_cache(t_cfg, batch, total)
        d_cache = init_cache(d_cfg, batch, total)
        if true_lens is None:
            true_lens = jnp.full((batch,), prompt_len, jnp.int32)
        else:
            true_lens = jnp.asarray(true_lens, jnp.int32)
        # head on each row's last REAL position only (logit_index): the
        # full-sequence head would materialize [B, S, vocab] fp32 — the
        # same last-position trick the plain generator uses (causal
        # prefill: positions < true_len never attend the right-padding)
        t_logits, t_cache = target.apply(
            {"params": target_params}, tokens, cache=t_cache,
            cache_index=jnp.int32(0), logit_index=true_lens - 1,
        )
        # the draft's prefill logits are never read: logit_index=0 makes
        # the head a [B, 1, V] stub that XLA dead-code-eliminates
        _, d_cache = draft.apply(
            {"params": draft_params}, tokens, cache=d_cache,
            cache_index=jnp.int32(0),
            logit_index=jnp.zeros((batch,), jnp.int32),
        )
        first = jnp.argmax(t_logits[:, 0], -1).astype(jnp.int32)  # [B]

        out = jnp.full((batch, max_new_tokens + k + 1), pad_id, jnp.int32)
        out = out.at[:, 0].set(first)
        fill0 = true_lens
        done0 = jnp.full((batch,), max_new_tokens <= 1)
        if eos_id is not None:
            done0 = done0 | (first == eos_id)
        emitted0 = jnp.ones((batch,), jnp.int32)

        def body(carry):
            t_cache, d_cache, out, fill, last, done, emitted, rounds, acc_total = carry

            # ---- draft proposes k greedy tokens (k+1 tiny scan steps:
            # the extra step consumes proposal k, writing its KV so a
            # fully-accepted round leaves NO hole at row fill+k — the
            # next round's draft queries would otherwise attend a
            # zero-filled slot and acceptance would collapse) ----
            def draft_step(c, _):
                cache, tok, f = c
                logits, cache = draft.apply(
                    {"params": draft_params}, tok[:, None], cache=cache,
                    cache_index=f,
                )
                nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
                return (cache, nxt, f + 1), nxt

            (d_cache, _, _), proposals = jax.lax.scan(
                draft_step, (d_cache, last, fill), None, length=k + 1
            )
            proposals = proposals.T[:, :k]                     # [B, k]

            # ---- target verifies [last, d_1..d_k] in one forward ----
            verify_in = jnp.concatenate([last[:, None], proposals], axis=1)
            v_logits, t_cache = target.apply(
                {"params": target_params}, verify_in, cache=t_cache,
                cache_index=fill,
            )
            greedy = jnp.argmax(v_logits, -1).astype(jnp.int32)  # [B, k+1]
            accepted, correction, emit_toks = greedy_acceptance(
                proposals, greedy
            )
            emit_len = jnp.where(done, 0, accepted + 1)        # [B]

            # write this round's tokens at each row's emitted offset
            pos = emitted[:, None] + jnp.arange(k + 1)[None, :]  # [B, k+1]
            valid = jnp.arange(k + 1)[None, :] < emit_len[:, None]
            if eos_id is not None:
                # nothing after the first eos of the round is emitted
                is_eos = emit_toks == eos_id
                after_eos = jnp.cumsum(
                    jnp.pad(is_eos, ((0, 0), (1, 0)))[:, :-1], axis=1
                ) > 0
                valid = valid & ~after_eos
            emit_count = valid.sum(axis=1).astype(jnp.int32)
            safe_pos = jnp.where(valid, pos, out.shape[1] - 1)
            out = out.at[rows[:, None], safe_pos].set(
                jnp.where(valid, emit_toks, out[rows[:, None], safe_pos])
            )

            new_fill = jnp.where(done, fill, fill + accepted + 1)
            new_last = jnp.where(done, last, correction)
            new_emitted = emitted + emit_count
            new_done = done | (new_emitted >= max_new_tokens)
            if eos_id is not None:
                new_done = new_done | (valid & (emit_toks == eos_id)).any(axis=1)
            new_rounds = rounds + jnp.where(done, 0, 1)
            new_acc = acc_total + jnp.where(done, 0, accepted)
            return (
                t_cache, d_cache, out, new_fill, new_last, new_done,
                new_emitted, new_rounds, new_acc,
            )

        def cond(carry):
            done = carry[5]
            return ~done.all()

        zeros = jnp.zeros((batch,), jnp.int32)
        carry = (t_cache, d_cache, out, fill0, first, done0, emitted0, zeros, zeros)
        carry = jax.lax.while_loop(cond, body, carry)
        toks = carry[2][:, :max_new_tokens]
        if with_stats:
            return toks, {"rounds": carry[7], "accepted": carry[8]}
        return toks

    return jax.jit(generate)


def make_speculative_predictor(
    target: Llama,
    draft: Llama,
    *,
    max_new_tokens: int = 32,
    bucket_lens: tuple = (16, 32, 64, 128),
    speculate_k: int = 4,
    eos_id: Optional[int] = None,
    pad_id: int = 0,
) -> Callable:
    """An ``@model.predictor``-compatible fn with speculative decoding.

    The serving-side wrapper, matching ``make_lm_predictor``'s shape
    discipline: ragged token-id prompts are RIGHT-padded to the smallest
    covering prompt bucket and the batch to the next power of two, so
    XLA compiles a bounded executable set — one generator call per
    request, per-row ``true_lens`` keeping padded rows exact (the
    generator's vector-fill prefill). ``state`` must carry the paired
    trees as a mapping ``{"target": ..., "draft": ...}`` (plain dict or
    ``flax.core.FrozenDict``; or an object with ``.params`` holding it)
    — the artifact a speculative serving app saves. Output trims at
    ``eos_id`` when set.

    ``.warmup(state, max_batch=...)`` pre-compiles every (bucket,
    power-of-two batch) executable, like the LM predictor's.
    """
    from collections.abc import Mapping

    import numpy as np

    buckets = tuple(sorted(set(int(b) for b in bucket_lens)))
    gens = {
        b: make_speculative_generator(
            target, draft, max_new_tokens=max_new_tokens, speculate_k=speculate_k,
            max_len=b + max_new_tokens, eos_id=eos_id, pad_id=pad_id,
        )
        for b in buckets
    }

    def predictor(state, prompts) -> list:
        params = resolve_params(state)
        if (
            not isinstance(params, Mapping)
            or "target" not in params
            or "draft" not in params
        ):
            raise ValueError(
                'speculative predictor state must be a mapping '
                '{"target": params, "draft": params}'
            )
        rows = [np.asarray(p, dtype=np.int32).ravel() for p in prompts]
        if any(len(r) == 0 for r in rows):
            raise ValueError("empty prompt")
        longest = max(len(r) for r in rows)
        bucket = next((b for b in buckets if b >= longest), None)
        if bucket is None:
            raise ValueError(
                f"prompt length {longest} exceeds the largest bucket "
                f"{buckets[-1]}; add a larger bucket to bucket_lens"
            )
        n = len(rows)
        n_padded = 1 << (n - 1).bit_length()
        batch = np.full((n_padded, bucket), pad_id, np.int32)
        true_lens = np.ones((n_padded,), np.int32)
        for i in range(n_padded):
            r = rows[min(i, n - 1)]               # pad rows replicate last
            batch[i, : len(r)] = r
            true_lens[i] = len(r)
        out = np.asarray(
            gens[bucket](
                params["target"], params["draft"], jnp.asarray(batch),
                jnp.asarray(true_lens),
            )
        )
        results = []
        for row in out[:n]:
            toks = row.tolist()
            if eos_id is not None and eos_id in toks:
                toks = toks[: toks.index(eos_id) + 1]
            results.append(toks)
        return results

    def warmup(state, *, max_batch: int = 8, buckets: Optional[tuple] = None,
               _all=buckets) -> int:
        if buckets is not None and not buckets:
            raise ValueError(
                "warmup got an empty bucket tuple — pass buckets=None to "
                "warm every configured bucket"
            )
        use = _all if buckets is None else tuple(buckets)
        unknown = sorted(set(use) - set(_all))
        if unknown:
            raise ValueError(
                f"warmup buckets {unknown} are not configured ({_all})"
            )
        compiled = 0
        top = 1 << (max(1, max_batch) - 1).bit_length()
        for b in use:
            size = 1
            while size <= top:
                predictor(state, np.ones((size, b), np.int32))
                compiled += 1
                size *= 2
        return compiled

    predictor.warmup = warmup
    return predictor
