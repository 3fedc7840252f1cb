"""Sharding tier: the distributed SpMV/SpMM executor
(``ShardedPlannedMatrix``, docs/sharding.md)."""
from .spmv import ShardedPlannedMatrix, build_sharded, shard_csr

__all__ = ["ShardedPlannedMatrix", "build_sharded", "shard_csr"]
