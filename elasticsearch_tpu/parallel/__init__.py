"""Mesh parallelism: doc routing, the device mesh, SPMD search.

The data-plane replacement for the reference's scatter-gather RPC protocol
(SURVEY.md §2.10, §5.8): shards and replicas are mesh axes, reduces are XLA
collectives over ICI instead of coordinator merge loops.
"""

from .routing import djb_hash, shard_id, select_copy
from .mesh import make_mesh, index_sharding, SHARD_AXIS, REPLICA_AXIS

__all__ = [
    "djb_hash", "shard_id", "select_copy",
    "make_mesh", "index_sharding",
    "SHARD_AXIS", "REPLICA_AXIS",
]
