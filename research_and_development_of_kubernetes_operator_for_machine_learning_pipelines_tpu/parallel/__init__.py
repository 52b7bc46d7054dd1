"""Device meshes, sharding rules, collectives, and multi-host init.

The reference has no distributed-communication backend at all — its only
"parallelism" is weighted traffic between two predictors (SURVEY.md §2.3).
This package is the TPU-native equivalent mandated for the rebuild:
XLA collectives over ICI within a slice (driven by ``jax.jit`` with
``NamedSharding``/``shard_map`` over a ``Mesh``) and DCN across hosts via
``jax.distributed.initialize``.
"""

from .mesh import (
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_PIPE,
    AXIS_SEQ,
    AXIS_TENSOR,
    MESH_AXIS_ORDER,
    build_mesh,
    local_mesh,
)
from .sharding import (
    LOGICAL_BATCH,
    LOGICAL_EMBED,
    LOGICAL_HEADS,
    LOGICAL_KV_HEADS,
    LOGICAL_MLP,
    LOGICAL_SEQ,
    LOGICAL_VOCAB,
    PartitionRuleError,
    ShardingRules,
    TRANSFORMER_RULES,
    logical_sharding,
    logical_spec,
    match_partition_rules,
    shard_pytree,
)
from .collectives import ring_shift
from .distributed import maybe_initialize_distributed

__all__ = [
    "AXIS_DATA",
    "AXIS_EXPERT",
    "AXIS_PIPE",
    "AXIS_SEQ",
    "AXIS_TENSOR",
    "MESH_AXIS_ORDER",
    "build_mesh",
    "local_mesh",
    "ShardingRules",
    "TRANSFORMER_RULES",
    "LOGICAL_BATCH",
    "LOGICAL_EMBED",
    "LOGICAL_HEADS",
    "LOGICAL_KV_HEADS",
    "LOGICAL_MLP",
    "LOGICAL_SEQ",
    "LOGICAL_VOCAB",
    "logical_spec",
    "logical_sharding",
    "match_partition_rules",
    "PartitionRuleError",
    "shard_pytree",
    "ring_shift",
    "maybe_initialize_distributed",
]
