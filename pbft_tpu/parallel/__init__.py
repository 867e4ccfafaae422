"""pbft_tpu.parallel — sharding the crypto hot path over a device mesh.

The reference's only concurrency was one OS process per replica plus libp2p
substreams (SURVEY.md §2 "Parallelism strategies: none"); the rebuild's
scaling axis is the *signature batch*. This package shards that batch over a
``jax.sharding.Mesh`` (data-parallel over the batch axis) and aggregates
per-round quorum counts with XLA collectives (``psum`` over ICI), so one
verification launch scales from one chip to a pod slice without touching the
consensus core.
"""

from .verifier import (
    QuorumResult,
    chains_of,
    compile_sharded,
    lower_sharded,
    make_mesh,
    sharded_verify,
    quorum_certify,
    round_step,
)
from .multihost import (
    global_mesh,
    host_shard_to_global,
    initialize_distributed,
    partition_items,
)

__all__ = [
    "QuorumResult",
    "chains_of",
    "compile_sharded",
    "lower_sharded",
    "make_mesh",
    "sharded_verify",
    "quorum_certify",
    "round_step",
    "global_mesh",
    "host_shard_to_global",
    "initialize_distributed",
    "partition_items",
]
