"""Distributed-execution helpers: logical-axis sharding rules + param/batch
sharding construction + the mesh / shard_map spellings.

``sharding``        - the logical-axis annotation layer (``ax`` + rule tables)
``params_sharding`` - NamedSharding trees for params / optimizer state /
                      batches / decode caches (FSDP + batch sharding)
``compat``          - mesh construction / ``shard_map`` / ambient-mesh
                      context, in one place
"""
from repro.dist import compat, params_sharding, sharding

__all__ = ["compat", "params_sharding", "sharding"]
