"""Olmo-Hybrid sizes through the program's hybrid decoder: ``models.OlmoHybrid``.

Serving only: ``DecodeEngine`` behind ``ServingApp`` with int8 weight-only
matmuls, a paged KV pool for the full-attention layers and a per-slot
recurrent state for the linear ones. The service is started as the other
decoders' is (``llama_decoder.start_service``): the same engine, the same
options, and the engine's defaults for everything the configuration does
not name.
"""

from __future__ import annotations

from chipbench.adapters.llama_decoder import rebind, start_service  # noqa: F401  (the runner's entry points)


def build(cfg: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from unionml_tpu.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig

    if "training" in cfg:
        raise SystemExit("chipbench: the olmo_hybrid family is served, not trained (PERF.md, section 4)")
    module = OlmoHybrid(OlmoHybridConfig.from_hf(cfg, quantized=True))

    def abstract():
        return jax.eval_shape(module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    return dict(serve_module=module, abstract_serve_params=abstract)
