"""GLM-4.7-Flash sizes through the program's latent-attention decoder:
``models.GlmMoeLite``.

Serving only: ``DecodeEngine`` behind ``ServingApp`` with int8 weight-only
matmuls and experts, and a paged pool of latent rows. The service is started
as the other decoders' is (``llama_decoder.start_service``): the same engine,
the same options, and the engine's defaults for everything the configuration
does not name. ``serving.prefill_impl`` is the module's own option of that
name: a deployment with 4,096-token buckets sets ``flash`` (whole prompts
through the flash kernel; the masked path would hold a ``[20, 4096, 4096]``
float32 score array).
"""

from __future__ import annotations

from chipbench.adapters.llama_decoder import rebind, start_service  # noqa: F401  (the runner's entry points)


def build(cfg: dict) -> dict:
    # the program's part first: a program without this decoder fails here,
    # in seconds and before any weights
    from unionml_tpu.models.glm_moe_lite import GlmMoeLite, GlmMoeLiteConfig

    import jax
    import jax.numpy as jnp

    if "training" in cfg:
        raise SystemExit("chipbench: the glm_moe_lite family is served, not trained (PERF.md, section 4)")
    module = GlmMoeLite(GlmMoeLiteConfig.from_hf(
        cfg, quantized=True, prefill_impl=cfg["serving"]["prefill_impl"],
    ))

    def abstract():
        return jax.eval_shape(module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    return dict(serve_module=module, abstract_serve_params=abstract)
