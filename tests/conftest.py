"""Test harness: CPU-simulated 8-device mesh (SURVEY.md §4.3).

The reference tests against a dockerized single-node Flyte sandbox
(reference: tests/integration/test_flyte_remote.py:33-57); the TPU-native
equivalent is the JAX CPU backend with a forced 8-device host platform so
DP/FSDP/TP/SP sharding is exercised without hardware. Env must be set
before the first jax import, hence at conftest import time.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# keep stage caches inside the test tmp area, not the user cache
os.environ.setdefault("UNIONML_TPU_CACHE_DIR", "/tmp/unionml_tpu_test_cache")

import jax

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache for the test suite: dozens of tests
# build fresh DecodeEngines/trainers over the SAME tiny-model geometry,
# and each re-jits byte-identical HLO (the in-memory jit cache is
# per-closure, so engine instances never share it). The persistent
# cache keys on HLO hash, so repeats hit even WITHIN one cold suite
# run, and the whole suite warms across runs. The directory follows the
# one rule of unionml_tpu/compile_cache.py (an exported
# JAX_COMPILATION_CACHE_DIR wins, else <checkout>/.jax_cache), and the
# export reaches SUBPROCESS jax runs too: the CLI scaffold tests and the
# tutorial executors each spawn child pytest/python processes that
# otherwise cold-compile the same tiny models on every suite run
# (~100 s of repeat XLA work). Only the thresholds are test-harness
# settings: tiny programs compile in milliseconds and must still cache.
from unionml_tpu.compile_cache import enable_compile_cache

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
