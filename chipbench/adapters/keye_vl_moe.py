"""Keye-VL-2.0's language model through the program's sparse-attention
decoder: ``models.KeyeVLMoe``.

Serving only: ``DecodeEngine`` behind ``ServingApp`` with int8 weight-only
matmuls and experts, and a paged pool whose rows hold keys, values and the
indexer's key. The service is started as the other decoders' is
(``llama_decoder.start_service``): the same engine, the same options, and the
engine's defaults for everything the configuration does not name. The vision
tower is not built (no file here gives its widths): the traffic is text.
"""

from __future__ import annotations

from chipbench.adapters.llama_decoder import rebind, start_service  # noqa: F401  (the runner's entry points)


def build(cfg: dict) -> dict:
    # the program's part first: a program without this decoder fails here,
    # in seconds and before any weights
    from unionml_tpu.models.keye_vl_moe import KeyeVLMoe, KeyeVLMoeConfig

    import jax
    import jax.numpy as jnp

    if "training" in cfg:
        raise SystemExit("chipbench: the keye_vl_moe family is served, not trained (PERF.md, section 4)")
    module = KeyeVLMoe(KeyeVLMoeConfig.from_hf(cfg, quantized=True))

    def abstract():
        return jax.eval_shape(module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    return dict(serve_module=module, abstract_serve_params=abstract)
