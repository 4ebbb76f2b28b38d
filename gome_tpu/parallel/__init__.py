from .mesh import (
    make_mesh,
    shard_batch,
    shard_execution_report,
    sharded_batch_step,
    symbol_sharding,
)
from .router import ShardRouter, fnv1a, multihost_mesh

__all__ = [
    "make_mesh",
    "shard_batch",
    "shard_execution_report",
    "sharded_batch_step",
    "symbol_sharding",
    "ShardRouter",
    "fnv1a",
    "multihost_mesh",
]
