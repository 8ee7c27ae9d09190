"""Mistral / Mixtral sizes through the program's decoder: ``models.Llama``.

Training goes through ``lm_step``; serving through ``DecodeEngine`` behind
``ServingApp`` with int8 weight-only matmuls and a paged KV pool. Engine
settings the configuration does not name (``chunk_steps``,
``pipeline_depth``) are left at the program's defaults on purpose: they are
what users get.
"""

from __future__ import annotations


def llama_config(cfg: dict, **over):
    from unionml_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_dim=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], mlp_dim=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        max_len=cfg["max_position_embeddings"],
        num_experts=cfg.get("num_local_experts", 0),
        num_selected=cfg.get("num_experts_per_tok", 2),
        **over,
    )


def build(cfg: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from unionml_tpu.models import (
        LLAMA_MOE_PARTITION_RULES, LLAMA_PARTITION_RULES, Llama, lm_step,
    )
    from unionml_tpu.models.train import TrainState, adamw

    heads = cfg["num_attention_heads"]
    if cfg.get("head_dim", cfg["hidden_size"] // heads) != cfg["hidden_size"] // heads:
        raise SystemExit("chipbench: models.Llama derives head_dim as hidden_size / heads")

    def abstract(module):
        return jax.eval_shape(
            module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]

    out = dict(adam_b1=0.9)
    if "training" in cfg:
        train = cfg["training"]
        module = Llama(llama_config(cfg, attn_impl=train["attn_impl"], remat=train["remat"]))
        tx = adamw(train["learning_rate"])
        seq = train["sequence_length"]

        def make_batches(key, n: int, batch: int):
            return jax.random.randint(key, (n, batch, seq), 1, cfg["vocab_size"], jnp.int32)

        out.update(
            module=module, step_fn=lm_step(module),
            abstract_params=lambda: abstract(module),
            make_state=lambda params: TrainState.create(apply_fn=module.apply, params=params, tx=tx),
            make_batches=make_batches, take_batch=lambda pool, i: pool[i],
            partition_rules=(
                LLAMA_MOE_PARTITION_RULES if cfg.get("num_local_experts") else LLAMA_PARTITION_RULES
            ),
        )
    if "serving" in cfg:
        serve_module = Llama(llama_config(cfg, quantized=True))
        out.update(serve_module=serve_module, abstract_serve_params=lambda: abstract(serve_module))
    return out


def start_service(built: dict, cfg: dict, params):
    """The program's serving stack around ``params``: a ``DecodeEngine``
    behind a ``ServingApp`` on a free local port. Returns
    ``(engine, app, host, port)``; the caller shuts both down."""
    from unionml_tpu import Dataset, Model
    from unionml_tpu.model import ModelArtifact
    from unionml_tpu.serving.engine import DecodeEngine
    from unionml_tpu.serving.http import ServingApp

    s = cfg["serving"]
    engine = DecodeEngine(
        built["serve_module"], slots=s["slots"], max_new_tokens=s["max_new_tokens"],
        prompt_buckets=tuple(s["prompt_buckets"]), paged=True,
        kv_pool_bytes=int(s["kv_pool_bytes"]), kv_block_size=s["kv_block_size"],
    )
    try:
        engine.warmup(params)
        engine.reset_stats()
        dataset = Dataset(name="chipbench_prompts", targets=[])

        @dataset.reader
        def reader() -> list:
            return []

        lm = Model(name="chipbench_lm", init=lambda: params, dataset=dataset)

        @lm.trainer
        def trainer(p: dict, features: list) -> dict:
            return p

        @lm.predictor
        def predictor(p: dict, prompts: list) -> list:
            return engine.generate(p, prompts)

        lm.artifact = ModelArtifact(params, {}, {})
        app = ServingApp(
            lm, batch=False, health=engine.health, stats=engine.stats,
            stream=lambda p, feats: engine.generate_stream(p, feats[0]),
        )
        host, port = app.serve(port=0, blocking=False)
    except BaseException:
        engine.close()
        raise
    return engine, app, host, port


def rebind(engine, app, params) -> None:
    """Serve ``params`` from now on (the engine must be idle)."""
    from unionml_tpu.model import ModelArtifact

    app.model.artifact = ModelArtifact(params, {}, {})
    engine.bind(params)
