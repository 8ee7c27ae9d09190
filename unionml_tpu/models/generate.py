"""Autoregressive generation: jitted prefill + ``lax.scan`` decode.

No reference counterpart — the reference's predictors are single
sklearn/torch calls (reference: unionml/model.py:498-499); LLM serving
(BASELINE.json config #5, "Llama-3-8B FastAPI predictor serving") needs a
generation loop, and on TPU that loop must live inside ONE compiled
program: Python-driven token-at-a-time decoding pays a dispatch round
trip per token.

Design:

- **prefill** runs the prompt through the model once, filling the KV
  cache (one big MXU-friendly matmul pass);
- **decode** is a ``lax.scan`` over ``max_new_tokens`` steps: each step
  feeds one token per sequence with ``cache_index`` advancing, so the
  whole generation is a single XLA program with static shapes —
  recompiles happen per (batch, prompt_len, max_new_tokens) bucket only;
- **sampling** is greedy at ``temperature=0`` else temperature softmax
  with optional top-k and/or nucleus top-p filters, driven by a threaded
  PRNG key;
- **eos** handling keeps shapes static: once a sequence emits
  ``eos_id`` every later token becomes ``pad_id`` and generation simply
  runs out the scan (correct, just not early-exiting — the standard
  static-shape trade).

Prompts in one call must share a length (serving buckets by prompt
length — see :mod:`unionml_tpu.serving.batcher`): the per-batch scalar
``cache_index`` is what keeps the decode step a cheap dynamic-slice.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from unionml_tpu.models.llama import Llama, LlamaConfig, init_cache
from unionml_tpu.models.train import resolve_params


def make_sampler(
    *,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
) -> Callable:
    """Build ``sample(logits[B, V], key) -> token[B]``.

    Greedy at ``temperature == 0``; otherwise categorical over
    temperature-scaled logits, optionally filtered by ``top_k`` and/or
    nucleus ``top_p`` (keep the smallest prefix of probability-descending
    tokens whose mass reaches ``top_p``; the filters compose — top_k
    first, then top_p over the survivors). Shared by the scan generator
    and the continuous-batching decode engine so both sample identically.
    """
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")

    def sample(logits: jnp.ndarray, key) -> jnp.ndarray:
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / temperature
        if top_k is not None:
            top_vals, _ = jax.lax.top_k(scaled, top_k)
            cutoff = top_vals[:, -1:]
            scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)
        if top_p is not None and top_p < 1.0:
            probs = jax.nn.softmax(scaled, axis=-1)
            sort_idx = jnp.argsort(probs, axis=-1)[:, ::-1]        # descending
            sorted_probs = jnp.take_along_axis(probs, sort_idx, axis=-1)
            cum = jnp.cumsum(sorted_probs, axis=-1)
            # keep the smallest prefix whose mass reaches top_p: a sorted
            # position survives iff the mass BEFORE it is < top_p. Masking
            # by position (not probability value) keeps the nucleus
            # bounded even when many tokens tie at the cutoff.
            keep_sorted = (cum - sorted_probs) < top_p
            inv = jnp.argsort(sort_idx, axis=-1)
            keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
            scaled = jnp.where(keep, scaled, -jnp.inf)
        return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)

    return sample


def make_generator(
    module: Llama,
    *,
    max_new_tokens: int,
    max_len: Optional[int] = None,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_id: Optional[int] = None,
    pad_id: int = 0,
    prefill_chunk: Optional[int] = None,
    prefix_len: int = 0,
) -> Callable:
    """Build ``generate(params, tokens, key) -> tokens[B, max_new_tokens]``.

    ``tokens``: int32 [B, prompt_len] (equal lengths per call). The
    returned function is jit-compiled; XLA caches one executable per
    (batch, prompt_len) shape.

    Sampling: greedy at ``temperature == 0``; otherwise categorical over
    temperature-scaled logits, optionally filtered by ``top_k`` and/or
    nucleus ``top_p`` (keep the smallest prefix of
    probability-descending tokens whose mass reaches ``top_p``; the
    filters compose — top_k first, then top_p over the survivors).

    ``prefix_len > 0`` enables SHARED-PREFIX serving (system prompts):
    ``generate`` then takes a ``prefix_cache`` built once per weights by
    :func:`make_prefix_cache` holding the prefix's KV rows at
    ``[0, prefix_len)``; each request prefills only its own suffix, so
    the shared prefix's prefill cost is paid once per weights instead of
    once per request (~0.4 s per batch for a 512-token prefix at 8B).
    """
    cfg: LlamaConfig = module.config
    total_len = max_len or cfg.max_len
    sample = make_sampler(temperature=temperature, top_k=top_k, top_p=top_p)

    def generate(
        params, tokens: jnp.ndarray, key=None, prompt_mask=None,
        prefix_cache=None,
    ) -> jnp.ndarray:
        """``prompt_mask``: bool [B, prompt_len], False marks left-padding
        (padded slots are never attended to; RoPE positions are logical,
        i.e. counted over real tokens only)."""
        batch, prompt_len = tokens.shape
        if prefix_len + prompt_len + max_new_tokens > total_len:
            # dynamic_update_slice would clamp writes past the cache end
            # onto the last slot — silent corruption, so reject at trace
            raise ValueError(
                f"prefix_len {prefix_len} + prompt_len {prompt_len} + "
                f"max_new_tokens {max_new_tokens} exceeds the KV cache "
                f"length {total_len}; raise max_len"
            )
        if (prefix_cache is None) != (prefix_len == 0):
            raise ValueError(
                "prefix_cache must be passed exactly when the generator "
                f"was built with prefix_len > 0 (prefix_len={prefix_len})"
            )
        if key is None:
            if temperature != 0.0:
                # a silent fixed-key default would return byte-identical
                # "samples" on every call
                raise ValueError(
                    "temperature sampling needs an explicit PRNG key: "
                    "generate(params, tokens, key)"
                )
            key = jax.random.PRNGKey(0)  # greedy: key is never consumed
        if prompt_mask is None:
            prompt_mask = jnp.ones((batch, prompt_len), bool)
        pad_counts = prompt_len - prompt_mask.sum(axis=1).astype(jnp.int32)  # [B]
        # logical (RoPE) positions continue from the prefix's real tokens
        positions = prefix_len + jnp.maximum(
            jnp.arange(prompt_len, dtype=jnp.int32)[None, :] - pad_counts[:, None], 0
        )
        # padded prompt slots stay invisible forever; decode slots become
        # visible through the causal q_pos >= kv_pos rule as they fill;
        # prefix slots are always visible
        kv_mask = jnp.concatenate(
            [
                jnp.ones((batch, prefix_len), bool),
                prompt_mask,
                jnp.ones(
                    (batch, total_len - prefix_len - prompt_len), bool
                ),
            ],
            axis=1,
        )

        if prefix_cache is not None:
            # the prefix KV rows were prefilled ONCE (make_prefix_cache);
            # broadcast the [1, ...] buffers across this batch
            cache = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (batch,) + x.shape[1:]),
                prefix_cache,
            )
        else:
            cache = init_cache(cfg, batch, total_len)
        # prefill. The head runs on the LAST position only (prompts are
        # left-padded, so the last slot is the last real token): a
        # full-sequence head materializes [B, S, vocab] fp32 — 33 GB at
        # 8B x batch 8 x 8k. ``prefill_chunk`` additionally bounds the
        # cached-attention score buffer ([B, H, chunk, total] fp32
        # instead of [B, H, S, total]) — the knob that makes 8k-context
        # prefill fit at all. The chunk loop is a lax.scan (ONE compiled
        # chunk body), not a Python unroll of 63 8B chunk applies.
        step_size = prefill_chunk or prompt_len
        n_chunks = max(0, (prompt_len - 1) // step_size)  # before the tail
        tail_start = n_chunks * step_size
        if n_chunks > 0:
            lead = tokens[:, :tail_start].reshape(batch, n_chunks, step_size)
            lead_pos = positions[:, :tail_start].reshape(
                batch, n_chunks, step_size
            )
            starts = prefix_len + jnp.arange(n_chunks, dtype=jnp.int32) * step_size

            def chunk_body(carry, xs):
                toks_c, pos_c, start = xs
                # logit_index=0: the head output is unused and DCE'd; the
                # chunk exists only to fill its cache rows
                _, carry = module.apply(
                    {"params": params}, toks_c, positions=pos_c,
                    cache=carry, cache_index=start, kv_mask=kv_mask,
                    logit_index=jnp.zeros((batch,), jnp.int32),
                )
                return carry, None

            cache, _ = jax.lax.scan(
                chunk_body, cache,
                (lead.transpose(1, 0, 2), lead_pos.transpose(1, 0, 2), starts),
            )
        tail_len = prompt_len - tail_start
        # static promise for cfg.prefill_impl == "flash": the tail call IS
        # the whole prefill exactly when nothing precedes it (no shared
        # prefix, no lead chunks) — both are Python ints at trace time.
        # The kwarg is only passed when the config opts in, so module
        # families without the parameter are untouched.
        full_kwargs = (
            {"full_prefill": True}
            if getattr(cfg, "prefill_impl", "cached") == "flash"
            and prefix_len + tail_start == 0
            else {}
        )
        logits, cache = module.apply(
            {"params": params}, tokens[:, tail_start:],
            positions=positions[:, tail_start:],
            cache=cache, cache_index=jnp.int32(prefix_len + tail_start),
            kv_mask=kv_mask,
            logit_index=jnp.full((batch,), tail_len - 1, jnp.int32),
            **full_kwargs,
        )
        key, sub = jax.random.split(key)
        first = sample(logits[:, -1], sub)
        done = (first == eos_id) if eos_id is not None else jnp.zeros(batch, bool)

        def step(carry, key_step):
            cache, tok, index, done = carry
            pos = (index - pad_counts)[:, None]   # logical positions [B, 1]
            logits, cache = module.apply(
                {"params": params}, tok[:, None], positions=pos,
                cache=cache, cache_index=index, kv_mask=kv_mask,
            )
            nxt = sample(logits[:, -1], key_step)
            if eos_id is not None:
                nxt = jnp.where(done, pad_id, nxt)
                done = done | (nxt == eos_id)
            return (cache, nxt, index + 1, done), nxt

        if max_new_tokens == 1:
            return first[:, None]
        keys = jax.random.split(key, max_new_tokens - 1)
        (_, _, _, _), rest = jax.lax.scan(
            step, (cache, first, jnp.int32(prefix_len + prompt_len), done), keys
        )
        return jnp.concatenate([first[:, None], rest.T], axis=1)

    jitted = jax.jit(generate)
    if prefix_len == 0:
        def plain(params, tokens, key=None, prompt_mask=None, prefix_cache=None):
            if prefix_cache is not None:
                # raise here, not inside jit: an unregistered PrefixCache
                # dataclass would die in pytree flattening with an opaque
                # "not a valid JAX type" error
                raise ValueError(
                    "prefix_cache must be passed exactly when the "
                    "generator was built with prefix_len > 0 "
                    "(prefix_len=0)"
                )
            return jitted(params, tokens, key, prompt_mask)

        return plain

    def prefixed(params, tokens, key=None, prompt_mask=None, prefix_cache=None):
        # validate the wrapper OUTSIDE the jit boundary (an unregistered
        # dataclass would die in pytree flattening with an opaque error):
        # a cache built for a different prefix or max_len would be
        # silently overwritten/misread otherwise
        if prefix_cache is None:
            raise ValueError(
                "prefix_cache must be passed exactly when the generator "
                f"was built with prefix_len > 0 (prefix_len={prefix_len})"
            )
        if not isinstance(prefix_cache, PrefixCache):
            raise TypeError(
                "prefix_cache must come from make_prefix_cache "
                f"(got {type(prefix_cache).__name__})"
            )
        if prefix_cache.length != prefix_len or prefix_cache.total_len != total_len:
            raise ValueError(
                f"prefix_cache was built for prefix_len={prefix_cache.length}, "
                f"max_len={prefix_cache.total_len}; this generator needs "
                f"prefix_len={prefix_len}, max_len={total_len}"
            )
        return jitted(params, tokens, key, prompt_mask, prefix_cache.cache)

    return prefixed


@dataclass(frozen=True)
class PrefixCache:
    """A prefilled shared-prefix KV cache plus the geometry it was built
    for — :func:`make_generator`'s prefixed form validates ``length`` /
    ``total_len`` against its own configuration, so a cache built for a
    different prefix or cache size is rejected instead of silently
    conditioning generation on the wrong rows."""

    cache: Any
    length: int
    total_len: int


def make_prefix_cache(
    module: Llama,
    params,
    prefix_tokens,
    *,
    max_len: Optional[int] = None,
    prefill_chunk: Optional[int] = None,
) -> PrefixCache:
    """Prefill a shared prefix (system prompt) ONCE into a [1, max_len]
    KV cache for :func:`make_generator`'s ``prefix_len`` mode.

    Returns a :class:`PrefixCache` whose pytree (bf16 or int8 per
    ``config.kv_quant``) has rows ``[0, len(prefix_tokens))`` filled;
    ``generate`` broadcasts it across each request batch and prefills
    only the per-request suffix. Rebuild whenever ``params`` change (the
    predictor's ``system_prefix`` mode memoizes per state identity).
    """
    cfg: LlamaConfig = module.config
    total_len = max_len or cfg.max_len
    toks = jnp.asarray(prefix_tokens, jnp.int32)[None]
    prefix_len = toks.shape[1]
    if prefix_len == 0:
        raise ValueError("prefix_tokens must be non-empty")
    if prefix_len >= total_len:
        raise ValueError(
            f"prefix of {prefix_len} tokens leaves no cache room within "
            f"max_len {total_len}"
        )

    def build(params, toks):
        cache = init_cache(cfg, 1, total_len)
        step_size = prefill_chunk or prefix_len
        n_chunks = max(0, (prefix_len - 1) // step_size)
        tail_start = n_chunks * step_size
        positions = jnp.arange(prefix_len, dtype=jnp.int32)[None, :]
        if n_chunks > 0:
            lead = toks[:, :tail_start].reshape(1, n_chunks, step_size)
            lead_pos = positions[:, :tail_start].reshape(1, n_chunks, step_size)
            starts = jnp.arange(n_chunks, dtype=jnp.int32) * step_size

            def chunk_body(carry, xs):
                toks_c, pos_c, start = xs
                _, carry = module.apply(
                    {"params": params}, toks_c, positions=pos_c,
                    cache=carry, cache_index=start,
                    logit_index=jnp.zeros((1,), jnp.int32),
                )
                return carry, None

            cache, _ = jax.lax.scan(
                chunk_body, cache,
                (lead.transpose(1, 0, 2), lead_pos.transpose(1, 0, 2), starts),
            )
        # same static full-prefill promise as generate()'s tail: when the
        # tail covers the whole (unpadded) prefix, cfg.prefill_impl ==
        # "flash" may run it through the flash kernel
        full_kwargs = (
            {"full_prefill": True}
            if getattr(cfg, "prefill_impl", "cached") == "flash"
            and tail_start == 0
            else {}
        )
        _, cache = module.apply(
            {"params": params}, toks[:, tail_start:],
            positions=positions[:, tail_start:],
            cache=cache, cache_index=jnp.int32(tail_start),
            logit_index=jnp.zeros((1,), jnp.int32),
            **full_kwargs,
        )
        return cache

    return PrefixCache(
        cache=jax.jit(build)(params, toks),
        length=prefix_len,
        total_len=total_len,
    )


def make_lm_predictor(
    module: Llama,
    *,
    max_new_tokens: int = 32,
    max_len: Optional[int] = None,
    bucket_lens: tuple = (16, 32, 64, 128, 256, 512),
    pad_id: int = 0,
    seed: int = 0,
    system_prefix=None,
    **gen_kwargs,
) -> Callable:
    """An ``@model.predictor``-compatible fn over token-id prompts.

    Accepts a list of token-id lists (or an int array); left-truncates/
    right-pads each prompt to the smallest bucket length so XLA sees a
    bounded set of shapes, generates, and returns one token list per
    prompt. Padding tokens are masked out of attention and RoPE positions
    are logical, so a padded prompt generates exactly what its unpadded
    version would.

    With ``temperature > 0`` the PRNG key advances per call (seeded by
    ``seed``), so repeated identical requests draw fresh samples; greedy
    decoding ignores the key.

    ``system_prefix`` (a token-id list): a shared prefix every request is
    conditioned on. Its KV rows are prefilled ONCE per weights
    (:func:`make_prefix_cache`, one cache per bucket, memoized on params
    identity) and broadcast into each request batch, so per-request
    prefill covers only the user prompt — outputs are exactly those of
    prepending the prefix to every prompt.

    **Identity contract**: the prefix memo keys on the STATE OBJECT —
    serving must hold one state object for the lifetime of the weights.
    A caller that re-wraps the same buffers per call (``device_put`` per
    request, a fresh dict from a checkpoint-reload loop) silently
    re-prefills the shared prefix every request, degrading the ~-42%
    p50 win back to naive; the predictor logs a warning when it detects
    a rebuild over leaves it has already seen.
    """
    import numpy as np

    prefix = (
        None
        if system_prefix is None
        else np.asarray(system_prefix, np.int32).ravel()
    )
    if prefix is not None and prefix.size == 0:
        # an empty array would thread prefix_len=0 into make_prefix_cache
        # and die in a ZeroDivisionError at the first request
        raise ValueError("system_prefix must be non-empty when given")
    prefix_len = 0 if prefix is None else len(prefix)
    total_len = max_len or module.config.max_len
    # only buckets that leave room for generation (and the prefix) in the
    # KV cache
    usable = tuple(sorted(
        b for b in bucket_lens
        if prefix_len + b + max_new_tokens <= total_len
    ))
    if not usable:
        raise ValueError(
            f"no bucket in {bucket_lens} leaves room for {max_new_tokens} new "
            f"tokens{f' + a {prefix_len}-token system_prefix' if prefix_len else ''} "
            f"within max_len {total_len}"
        )
    # one generator per bucket, each with a cache sized to the bucket:
    # decode attention reads the whole cache every step, so a full-length
    # (cfg.max_len) cache costs up to ~4x p50 at batch 8 on short prompts
    # (measured, 1.5B on v5e). XLA compiles per shape either way — the
    # per-bucket generators don't add executables.
    generators = {
        b: make_generator(
            module, max_new_tokens=max_new_tokens,
            max_len=prefix_len + b + max_new_tokens,
            pad_id=pad_id, prefix_len=prefix_len, **gen_kwargs,
        )
        for b in usable
    }
    key_state = {"key": jax.random.PRNGKey(seed)}
    # single-slot memo keyed on the STATE object (pre-resolution), with a
    # strong reference held: LoRA states resolve to a FRESH merged tree
    # every call (id(params) would miss forever and re-prefill per
    # request), and holding the referent prevents the
    # freed-then-id-reused hazard of a raw id() key. Serving holds one
    # weight set at a time; passing a new state object rebuilds.
    prefix_state = {"ref": None, "caches": {}}

    def _prefix_cache(state, params, bucket):
        if prefix is None:
            return None
        if prefix_state["ref"] is not state:
            # same underlying buffers under a new wrapper object → the
            # caller is violating the identity contract (see docstring):
            # every request now pays a full prefix prefill. Warn rather
            # than guess — keying on buffer ids would wrongly SHARE the
            # memo across genuinely different states that alias a leaf.
            leaves = jax.tree_util.tree_leaves(params)
            leaf_id = id(leaves[0]) if leaves else None
            if (
                prefix_state["ref"] is not None
                and leaf_id is not None
                and leaf_id == prefix_state.get("leaf_id")
            ):
                from unionml_tpu._logging import logger

                logger.info(
                    "system_prefix cache rebuilt for a state wrapping the "
                    "SAME weight buffers — hold one state object per "
                    "weight set or every request re-prefills the prefix"
                )
            prefix_state.update(ref=state, caches={}, leaf_id=leaf_id)
        caches = prefix_state["caches"]
        if bucket not in caches:
            caches[bucket] = make_prefix_cache(
                module, params, prefix,
                max_len=prefix_len + bucket + max_new_tokens,
                prefill_chunk=gen_kwargs.get("prefill_chunk"),
            )
        return caches[bucket]

    def predictor(state, prompts) -> list:
        params = resolve_params(state)
        if isinstance(prompts, (list, tuple)):
            rows = [np.asarray(p, dtype=np.int32).ravel() for p in prompts]
        else:
            arr = np.asarray(prompts, dtype=np.int32)
            rows = [arr] if arr.ndim == 1 else list(arr)
        longest = max(len(r) for r in rows)
        bucket = next((b for b in usable if b >= longest), usable[-1])
        # bucket the BATCH dimension too (next power of two): otherwise
        # every distinct batch size compiles a fresh executable
        n = len(rows)
        n_padded = 1 << (n - 1).bit_length()
        batch = np.full((n_padded, bucket), pad_id, np.int32)
        mask = np.zeros((n_padded, bucket), bool)
        for i in range(n_padded):
            r = rows[min(i, n - 1)]               # pad rows replicate the last
            r = r[-bucket:]                       # left-truncate long prompts
            batch[i, bucket - len(r):] = r        # right-align (left-pad)
            mask[i, bucket - len(r):] = True
        key_state["key"], sub = jax.random.split(key_state["key"])
        out = generators[bucket](
            params, jnp.asarray(batch), sub, jnp.asarray(mask),
            prefix_cache=_prefix_cache(state, params, bucket),
        )
        return np.asarray(out)[:n].tolist()

    def warmup(state, *, max_batch: int = 8, buckets: Optional[tuple] = None) -> int:
        """Pre-compile every (bucket, power-of-two batch) executable.

        XLA compiles lazily per shape; in a live server the first request
        hitting a fresh (bucket, padded-batch) combination stalls behind a
        multi-second compile (measured: 17.9 s p95 under 8 concurrent
        clients on the 1.5B config — vs ~0.4 s once warm). Call this at
        startup (pass it to ``ServingApp(warmup=...)``). Returns the
        number of executables compiled.
        """
        compiled = 0
        if buckets is not None:
            # a bucket outside `usable` (filtered out for leaving no KV-cache
            # room, or never configured) would silently warm the covering
            # bucket instead — callers would believe shapes were compiled
            # that weren't; an empty tuple would silently warm nothing
            if not buckets:
                raise ValueError(
                    "warmup got an empty bucket tuple — pass buckets=None "
                    "to warm every usable bucket"
                )
            unknown = sorted(set(buckets) - set(usable))
            if unknown:
                raise ValueError(
                    f"warmup buckets {unknown} are not in the usable bucket "
                    f"set {usable} (bucket_lens filtered to those leaving "
                    f"room for max_new_tokens={max_new_tokens} within "
                    f"max_len {total_len})"
                )
        # the predictor pads batches to the next power of two, so warm up
        # through max_batch ROUNDED UP — warmup(max_batch=6) must compile
        # batch 8, the shape a 5- or 6-row request actually runs
        top = 1 << (max(1, max_batch) - 1).bit_length()
        for b in usable if buckets is None else buckets:
            n = 1
            while n <= top:
                predictor(state, np.zeros((n, b), np.int32))
                compiled += 1
                n *= 2
        return compiled

    predictor.warmup = warmup
    return predictor


def serving_params(params, dtype=jnp.bfloat16):
    """Cast float params once for serving residency.

    Training artifacts carry fp32 master weights; decoding straight from
    them re-reads (and casts) the fp32 tree every step. A one-time cast
    to ``dtype`` halves decode weight traffic (~12% p50 on the 1.5B
    serving config, one v5e chip). Integer leaves (e.g. int8 ``kernel_q``)
    pass through unchanged, and so does quantization metadata that is
    fp32 *by contract*: per-channel ``scale`` / ``*_scale`` leaves (the
    dequant contract is "apply the fp32 scale, then one cast down") and
    the MoE ``router_kernel`` (kept fp32 so tiny routing updates don't
    round to zero) — so quantize-then-cast and cast-then-quantize agree.
    """

    from collections.abc import Mapping

    def cast_leaf(x):
        return x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x

    def walk(node):
        if isinstance(node, Mapping):
            out = {}
            for k, v in node.items():
                if isinstance(v, Mapping) or not hasattr(v, "dtype"):
                    out[k] = walk(v)
                    continue
                # a scale is quant metadata only next to its int8/int4
                # sibling (QuantizedDenseGeneral: kernel_q+scale;
                # Int4DenseGeneral: kernel_p+scale or group-wise
                # scale_g; MoE experts: w_*_q + w_*_scale) — norm params
                # also named "scale" cast
                is_quant_scale = (
                    k in ("scale", "scale_g")
                    and ("kernel_q" in node or "kernel_p" in node)
                ) or (
                    k.endswith("_scale") and f"{k[: -len('_scale')]}_q" in node
                )
                if k == "router_kernel" or is_quant_scale:
                    out[k] = v
                else:
                    out[k] = cast_leaf(v)
            return out
        if hasattr(node, "dtype"):
            return cast_leaf(node)
        return jax.tree_util.tree_map(cast_leaf, node)

    return walk(params)
