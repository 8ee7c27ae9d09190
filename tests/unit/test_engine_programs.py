"""The engine's device programs (``serving/programs.py``): one call
signature whatever the residency, and a state that keeps its shape.

Nothing runs here: every program is traced with ``jax.eval_shape``, on
the four kinds of engine the host side serves."""

import jax
import jax.numpy as jnp
import pytest

from unionml_tpu import telemetry
from unionml_tpu.models.llama import Llama, LlamaConfig
from unionml_tpu.serving.engine import DecodeEngine

BUCKET, BLOCK, CHUNK = 32, 8, 8

KINDS = {
    "rows": dict(),
    "rows+prefix_cache": dict(prefix_cache=True),
    "paged": dict(paged=True, kv_pool_bytes=1 << 20, kv_block_size=BLOCK),
    "speculative": dict(draft_module=Llama(LlamaConfig.tiny(vocab_size=97, num_layers=1)), speculate_k=3),
}


def _params(module):
    return jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    )


def _shapes(tree):
    return jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), tree)


@pytest.mark.parametrize("kind", list(KINDS))
def test_programs_take_one_signature_and_keep_the_state(kind):
    module = Llama(LlamaConfig.tiny(vocab_size=97))
    kwargs = KINDS[kind]
    engine = DecodeEngine(
        module, slots=3, max_new_tokens=8, prompt_buckets=(BUCKET,), prefill_chunk=CHUNK,
        chunk_steps=2, registry=telemetry.MetricsRegistry(), tracer=telemetry.TraceRecorder(),
        **kwargs,
    )
    try:
        assert engine.buckets == (BUCKET,)
        params = _params(module)
        if engine.draft is not None:
            params = {"target": params, "draft": _params(engine.draft)}
        # where a prefill goes and where a chunk finds a slot's history: the
        # pool's block ids and table, or nothing at all for slot rows
        ids = jnp.zeros((BUCKET // BLOCK,), jnp.int32) if engine.paged else None
        table = jnp.asarray(engine._table) if engine.paged else None
        slot, key = jnp.int32(1), jax.random.PRNGKey(0)

        state = jax.eval_shape(engine._init_state)
        want = _shapes(state)
        assert {"fill", "last_tok", "done"} < set(state)

        new_state, first = jax.eval_shape(
            engine._prefill, params, state, slot, ids, jnp.zeros((BUCKET,), jnp.int32), jnp.int32(5), key
        )
        assert _shapes(new_state) == want and first.shape == ()

        fresh = jax.eval_shape(lambda: engine._init_fresh(bucket=BUCKET))
        toks = jnp.zeros((1, CHUNK), jnp.int32)
        stepped = jax.eval_shape(engine._prefill_step, params, fresh, toks, jnp.int32(0))
        assert _shapes(stepped) == _shapes(fresh)
        new_state, first = jax.eval_shape(
            engine._prefill_final, params, state, fresh, slot, ids, toks,
            jnp.int32(BUCKET - CHUNK), jnp.int32(BUCKET - 3), key,
        )
        assert _shapes(new_state) == want and first.shape == ()

        keys = jnp.stack([key] * engine.chunk_steps)
        new_state, out = jax.eval_shape(
            engine._decode_chunk, params, state, jnp.ones((3,), bool), table, keys
        )
        assert _shapes(new_state) == want
        lead = {leaf.shape[:2] for leaf in jax.tree_util.tree_leaves(out)}
        assert lead == {(engine.chunk_steps, 3)}  # per step (or round), per slot

        if engine.draft is None:
            # what the prefix cache stores: the bucket's rows of one slot
            rows = jax.eval_shape(lambda: engine._extract(state, slot, ids, n=BUCKET))
            n_rows = {leaf.shape[0] * leaf.shape[1] for leaf in jax.tree_util.tree_leaves(rows)}
            assert n_rows == {BUCKET}
    finally:
        engine.close()


def test_a_module_that_generates_by_blocks_keeps_the_signature_and_adds_what_it_asks():
    """The block pool's third chunk: the state also holds every slot's open
    block, a prefill takes the tokens asked behind its key and returns the
    entries held back where the others return a token, and a step returns a
    block's tokens, the forward that decided each and four counts a slot."""
    from unionml_tpu.models.sdar_moe import SdarMoe, SdarMoeConfig

    module = SdarMoe(SdarMoeConfig.tiny(vocab_size=97))
    engine = DecodeEngine(
        module, slots=3, max_new_tokens=8, prompt_buckets=(BUCKET,), chunk_steps=2,
        registry=telemetry.MetricsRegistry(), tracer=telemetry.TraceRecorder(),
        paged=True, kv_pool_bytes=1 << 20, kv_block_size=BLOCK,
    )
    try:
        params = _params(module)
        ids, table = jnp.zeros((BUCKET // BLOCK,), jnp.int32), jnp.asarray(engine._table)
        slot, key = jnp.int32(1), jax.random.PRNGKey(0)
        state = jax.eval_shape(engine._init_state)
        want = _shapes(state)
        assert {"fill", "done", "stop", "blk_tok", "blk_und", "blk_gen", "blk_at", "blk_fwd"} < set(state)
        assert state["blk_tok"].shape == state["blk_und"].shape == (3, 4)
        new_state, held = jax.eval_shape(
            engine._prefill, params, state, slot, ids, jnp.zeros((BUCKET,), jnp.int32), jnp.int32(5), key,
            jnp.int32(8),
        )
        assert _shapes(new_state) == want and held.shape == ()
        keys = jnp.stack([key] * engine.chunk_steps)
        new_state, (tokens, decided_at, info) = jax.eval_shape(
            engine._decode_chunk, params, state, jnp.ones((3,), bool), table, keys
        )
        assert _shapes(new_state) == want
        assert tokens.shape == decided_at.shape == (2, 3, 4) and info.shape == (2, 3, 4)
        # the trace readers find a served cell's chunk by this name
        lowered = getattr(engine._decode_chunk, "__wrapped__", engine._decode_chunk).lower(
            params, state, jnp.ones((3,), bool), table, keys
        )
        assert "jit_decode_chunk" in lowered.as_text()[:400] or "decode_chunk" in lowered.as_text()[:400]
    finally:
        engine.close()
