"""SDAR's mixture-of-experts decoder through the program's decoder that
generates by diffusion over blocks: ``models.SdarMoe``.

Serving only: ``DecodeEngine`` behind ``ServingApp`` with int8 weight-only
matmuls and experts and a paged pool of keys and values. The service is
started as the other decoders' is (``llama_decoder.start_service``): the same
engine, the same options, and the engine's defaults for everything the
configuration does not name. That the module generates by blocks the engine
learns from the module (``generation_scheme()``), whose settings are the
configuration's ``generation`` group.
"""

from __future__ import annotations

from chipbench.adapters.llama_decoder import rebind, start_service  # noqa: F401  (the runner's entry points)


def build(cfg: dict) -> dict:
    # the program's part first: a program without this decoder fails here,
    # in seconds and before any weights
    from unionml_tpu.models.sdar_moe import SdarMoe, SdarMoeConfig

    import jax
    import jax.numpy as jnp

    if "training" in cfg:
        raise SystemExit("chipbench: the sdar_moe family is served, not trained (PERF.md, section 4)")
    module = SdarMoe(SdarMoeConfig.from_hf(cfg, quantized=True, prefill_impl="flash"))

    def abstract():
        return jax.eval_shape(module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    return dict(serve_module=module, abstract_serve_params=abstract)
