"""Parallelism layer: device meshes, sharding strategies, collectives.

No reference counterpart (SURVEY.md §2: the reference's only "distribution"
is task-level Flyte orchestration). This package is the TPU-native
first-class replacement: strategies compose as axes of one
``jax.sharding.Mesh`` and XLA/GSPMD inserts the collectives over ICI/DCN.

- :mod:`unionml_tpu.parallel.mesh` — mesh construction (single-chip, slice,
  multi-slice with DCN axes), multi-host bring-up.
- :mod:`unionml_tpu.parallel.sharding` — :class:`ShardingConfig` with named
  strategies (dp/fsdp/tp/sp/pp/ep), partition rules, ``compile_step``.
- :mod:`unionml_tpu.parallel.collectives` — named collective wrappers for
  shard_map kernels.
- :mod:`unionml_tpu.parallel.pipeline` — pipeline-parallel stage executor.
"""

from jax import shard_map

from unionml_tpu.parallel.collectives import bucketed_psum
from unionml_tpu.parallel.mesh import (
    cpu_multiprocess_supported,
    make_mesh,
    mesh_devices,
    multihost_initialize,
)
from unionml_tpu.parallel.pipeline import (
    pipeline_apply,
    pipeline_spmd,
    stack_stage_params,
)
from unionml_tpu.parallel.sharding import (
    PartitionRule,
    ShardingConfig,
    compile_step,
    named_sharding,
    shard_pytree,
    state_shardings,
)

__all__ = [
    "bucketed_psum",
    "cpu_multiprocess_supported",
    "shard_map",
    "make_mesh",
    "mesh_devices",
    "multihost_initialize",
    "pipeline_apply",
    "pipeline_spmd",
    "stack_stage_params",
    "PartitionRule",
    "ShardingConfig",
    "compile_step",
    "named_sharding",
    "shard_pytree",
    "state_shardings",
]
