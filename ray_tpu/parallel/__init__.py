"""Device meshes, sharding rules, and parallelism plans (TPU-native core).

Replaces the reference's process-group plumbing (NCCL/gloo rendezvous,
torch DDP/FSDP wrapping — ``python/ray/train/torch/config.py``,
``train_loop_utils.py``) with jax Mesh + NamedSharding: the compiler, not
the framework, owns the collective schedule.
"""
from .._private.accelerators import local_chip_count  # noqa: F401
from .mesh import (  # noqa: F401
    AXIS_ORDER,
    MeshConfig,
    create_mesh,
    initialize_multihost,
    mesh_shape,
    single_device_mesh,
)
from .sharding import (  # noqa: F401
    DP_RULES,
    LM_RULES,
    batch_sharding,
    compiled_collectives,
    replicated,
    shard_tree,
    spec_for,
    tree_shardings,
)
