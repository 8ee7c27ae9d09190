"""ViT through the program's normal path: ``models.ViT`` + ``classification_step``."""

from __future__ import annotations


def build(cfg: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from unionml_tpu.models import ViT, ViTConfig, classification_step
    from unionml_tpu.models.train import TrainState, adamw
    from unionml_tpu.models.vit import VIT_PARTITION_RULES

    train = cfg["training"]
    vcfg = ViTConfig(
        image_size=cfg["image_size"], patch_size=cfg["patch_size"],
        num_classes=cfg["num_labels"], hidden_dim=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        mlp_dim=cfg["intermediate_size"], attn_impl=train["attn_impl"],
    )
    module = ViT(vcfg)
    image = (cfg["image_size"], cfg["image_size"], cfg["num_channels"])
    tx = adamw(train["learning_rate"])

    def abstract_params():
        return jax.eval_shape(
            module.init, jax.random.PRNGKey(0), jnp.zeros((1,) + image, jnp.bfloat16)
        )["params"]

    def make_batches(key, n: int, batch: int):
        k1, k2 = jax.random.split(key)
        images = jax.random.normal(k1, (n, batch) + image, jnp.bfloat16)
        labels = jax.random.randint(k2, (n, batch), 0, cfg["num_labels"], jnp.int32)
        return images, labels

    return dict(
        module=module,
        step_fn=classification_step(module),
        abstract_params=abstract_params,
        make_state=lambda params: TrainState.create(apply_fn=module.apply, params=params, tx=tx),
        make_batches=make_batches,
        take_batch=lambda pool, i: (pool[0][i], pool[1][i]),
        partition_rules=VIT_PARTITION_RULES,
        adam_b1=0.9,
    )
