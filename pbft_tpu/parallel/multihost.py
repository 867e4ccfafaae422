"""Multi-host scaling for the batch verifier (ICI/DCN; scaling-book recipe).

The consensus transport stays on the host network (pbftd's TCP —
SURVEY.md §5: consensus-critical small messages never route through the
TPU fabric). What scales over the accelerator fabric is the *verification burden*: when a cluster's signature volume
exceeds one host, hosts feed process-local shards of the global
(pubkey, digest, sig) batch and the same `quorum_certify` psum produces
globally-replicated per-round verdicts — XLA routes the all-reduce over
ICI within a slice and DCN across slices.

Usage (one JAX process per host):

    import jax
    jax.distributed.initialize()          # coordinator env vars per host
    mesh = global_mesh()                  # all devices, 1-D batch axis
    certify = quorum_certify(mesh, num_rounds=R)
    pubs = host_shard_to_global(mesh, local_pubs)   # etc.
    result = certify(pubs, msgs, sigs, round_ids, thresholds)
    # result.certified is replicated: every host reads the same verdicts.

Single-process (one host, N chips) needs no initialize(); the same code
runs unchanged — that is the configuration the driver's dryrun and the
unit tests exercise.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np

from .verifier import make_mesh


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """jax.distributed.initialize with explicit args or env-var discovery.

    No-op when jax.distributed is already initialized or when running a
    single process (num_processes == 1 and no cluster env markers). The
    already-initialized check must NOT touch jax.process_count()/
    jax.devices(): those initialize the XLA backend, after which
    jax.distributed.initialize() refuses to run."""
    import os

    if jax.distributed.is_initialized():
        return
    # Env-var discovery: jax's own coordinator variables mark a multi-host
    # launch even when the caller passes no explicit args (e.g. a launcher
    # exports them per host). Single-process is only assumed when neither
    # explicit args nor these markers are present.
    env_discovery = any(
        os.environ.get(k)
        for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES")
    )
    if (
        num_processes in (None, 1)
        and coordinator_address is None
        and not env_discovery
    ):
        return  # single-process deployment: nothing to do
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def global_mesh(axis: str = "batch"):
    """1-D mesh over every device of every process (the verification
    batch is pure data-parallel, so 1-D is the right shape at any scale)."""
    return make_mesh(axis=axis)


def host_shard_to_global(mesh, local: np.ndarray) -> jax.Array:
    """Assemble a globally-sharded array from this host's shard.

    Each process passes its process-local rows (equal count per process);
    the result is one global array sharded over the mesh's batch axis,
    ready for quorum_certify. Under a single process this is just
    device_put with the batch sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    if jax.process_count() == 1:
        return jax.device_put(local, sharding)
    global_shape = (local.shape[0] * jax.process_count(),) + local.shape[1:]
    return jax.make_array_from_process_local_data(sharding, local, global_shape)


def partition_items(
    items: Sequence, process_id: Optional[int] = None, num: Optional[int] = None
):
    """Deterministic round-robin split of a batch across hosts: host k
    verifies items k, k+N, k+2N, … — every host computes the same split
    from the same batch, no coordination message needed."""
    pid = jax.process_index() if process_id is None else process_id
    n = jax.process_count() if num is None else num
    return list(items[pid::n])
