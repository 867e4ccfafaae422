"""Mesh-sharded batch verification and distributed quorum certification.

Design (TPU-first, scaling-book recipe: pick a mesh, annotate shardings, let
XLA insert collectives):

- One logical axis, ``"batch"``: signature triples are embarrassingly
  parallel, so the (B, …) tensors are sharded over it and the Ed25519 kernel
  runs shard-local with zero communication (``sharded_verify``).
- The *consensus* reduction — "does round r have >= its quorum threshold of
  valid signatures?" — is the only cross-shard computation. ``quorum_certify``
  computes shard-local per-round one-hot counts and ``psum``s them over the
  mesh, so every device holds the global per-round verdict after one small
  all-reduce riding ICI. This is the TPU-era analogue of the reference's
  per-message quorum predicates (reference src/behavior.rs:177-182,:199-223),
  evaluated for a whole window of rounds in one launch.
- Multi-host: the same code runs under ``jax.distributed`` — the Mesh spans
  all processes' devices and each host feeds its process-local shard
  (``jax.make_array_from_process_local_data``); psum then rides ICI/DCN.

Everything is static-shape: B (padded batch) and R (rounds window) are fixed
per compilation; pad slots carry round_id = R (a dummy row that is sliced
off), so changing batch occupancy never recompiles.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..crypto.batch import split_block
from ..crypto.ed25519 import verify_kernel


def make_mesh(
    n_devices: Optional[int] = None, axis: str = "batch", devices=None
) -> Mesh:
    """1-D device mesh over the batch axis.

    The verifier's parallelism is pure data-parallel over signatures, so a
    1-D mesh is the right shape; n_devices defaults to all local devices.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis,))


def sharded_verify(
    mesh: Mesh, axis: str = "batch", donate: bool = False, kernel=None
):
    """jit'd (B,128) uint8 block of ``pub | msg | sig`` rows -> (B,) bool,
    rows sharded over the batch axis.

    The block (``crypto.batch.pad_batch``) is cut into its three columns
    inside the jit, where the slices fuse into the kernel's first reads: one
    array to stage, one transfer a window (the call's own, where it is
    handed a host array). Shard-local compute only, under ``shard_map`` over
    the batch axis: each chip runs the kernel on its ``B / chips`` rows with
    no collectives, and the kernel is traced for THAT row count (XLA cannot
    partition a Mosaic call, and ``ed25519.chains_for`` decides by the rows
    a chip holds); a mesh of one runs the same code. B must be divisible by
    the mesh size. ``donate=True`` marks the input buffer donated so XLA reuses its
    device memory across launches (the verify service re-stages every
    window, so its input is dead the moment the launch reads it).
    ``kernel`` overrides the Ed25519 kernel, with its ``(pubs, msgs, sigs)``
    signature (tests substitute a cheap stand-in to exercise the serving
    plumbing without a minutes-long compile).
    """
    kern = kernel or verify_kernel

    def fn(block):  # the executable's name, ``jit_fn``, is how a trace finds it
        return kern(*split_block(block))

    # check_vma=False: the crypto kernel's lax loops carry broadcast curve
    # constants whose varying-axis annotation the checker can't infer.
    local = shard_map(
        fn, mesh=mesh, in_specs=P(axis), out_specs=P(axis), check_vma=False
    )
    return jax.jit(local, donate_argnums=(0,) if donate else ())


def lower_sharded(
    mesh: Mesh,
    size: int,
    axis: str = "batch",
    donate: bool = True,
    kernel=None,
):
    """The sharded verifier traced and lowered for one fixed window size: a
    ``jax.stages.Lowered`` that takes ONE ``(size, 128)`` uint8 block, rows
    sharded over ``axis``. :func:`compile_sharded` compiles it."""
    if size % mesh.devices.size:
        raise ValueError(
            f"window {size} not divisible by mesh size {mesh.devices.size}"
        )
    fn = sharded_verify(mesh, axis, donate=donate, kernel=kernel)
    return fn.lower(
        jax.ShapeDtypeStruct(
            (size, 128), jnp.uint8, sharding=NamedSharding(mesh, P(axis))
        )
    )


def compile_sharded(
    mesh: Mesh,
    size: int,
    axis: str = "batch",
    donate: bool = True,
    kernel=None,
):
    """AOT-compile the sharded verifier for one fixed window size.

    ``jax.jit(...).lower(...).compile()`` ahead of first traffic: the
    persistent verify service warms every `_PAD_LADDER` shape at startup
    so no request ever pays tracing or compilation (the persistent
    on-disk cache makes the warm-restart compile cache-hit cheap).
    Returns a ``jax.stages.Compiled``: called on a host array it moves the
    block itself, in one transfer (what the verify service does).
    """
    return lower_sharded(mesh, size, axis, donate, kernel).compile()


def chains_of(compiled, lowered=None) -> str:
    """``"vmem"`` or ``"xla"``: where a compiled program runs its long
    multiply chains, read from the program and not from the rule that was
    meant to shape it (``ed25519.chains_for``): a Mosaic kernel is a
    ``tpu_custom_call`` in the executable's HLO, and the XLA chains have
    none. So a caller that vmaps the kernel, another leading shape or a
    later edit to the rule cannot compile one thing while the counters
    report the other. Where the runtime hands no text back for an
    executable, the ``lowered`` module it was compiled from is read."""
    text = compiled.as_text() or (lowered.as_text() if lowered else None)
    if not text:
        raise RuntimeError("neither the executable nor its module gives text to read")
    return "vmem" if "tpu_custom_call" in text else "xla"


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuorumResult:
    """Global (replicated) outputs of one quorum-certification launch."""

    valid: jax.Array  # (B,) bool  per-signature verdicts (batch-sharded)
    counts: jax.Array  # (R,) int32 valid-signature count per round
    certified: jax.Array  # (R,) bool  counts >= thresholds


def quorum_certify(mesh: Mesh, num_rounds: int, axis: str = "batch"):
    """Distributed quorum certification: verify + psum per-round counts.

    Returns a jit'd function
        (pubs (B,32), msgs (B,32), sigs (B,64), round_ids (B,), thresholds (R,))
        -> QuorumResult
    where round_ids[i] in [0, R) assigns signature i to a consensus round
    (view, seq) slot; pad slots use round_id >= R and are dropped. Each
    device verifies its batch shard, builds shard-local per-round counts,
    and one psum over the mesh replicates the global counts — the quorum
    predicate for a whole window of rounds in a single collective.
    """
    R = num_rounds

    def local(pubs, msgs, sigs, round_ids, thresholds):
        ok = verify_kernel(pubs, msgs, sigs)
        # Shard-local counts; dummy segment R swallows pad slots.
        rid = jnp.clip(round_ids.astype(jnp.int32), 0, R)
        counts = jax.ops.segment_sum(
            ok.astype(jnp.int32), rid, num_segments=R + 1
        )[:R]
        counts = jax.lax.psum(counts, axis)
        return ok, counts, counts >= thresholds

    # check_vma=False: the crypto kernel's lax loops carry broadcast curve
    # constants whose varying-axis annotation the checker can't infer.
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P()),
        out_specs=(P(axis), P(), P()),
        check_vma=False,
    )

    @jax.jit
    def certify(pubs, msgs, sigs, round_ids, thresholds):
        valid, counts, certified = fn(
            jnp.asarray(pubs, jnp.uint8),
            jnp.asarray(msgs, jnp.uint8),
            jnp.asarray(sigs, jnp.uint8),
            jnp.asarray(round_ids, jnp.int32),
            jnp.asarray(thresholds, jnp.int32),
        )
        return QuorumResult(valid=valid, counts=counts, certified=certified)

    return certify


def round_step(mesh: Mesh, num_rounds: int, axis: str = "batch"):
    """The framework's full distributed step, jitted over the mesh.

    One consensus *window* step = verify every queued PREPARE/COMMIT
    signature (batch-sharded over the mesh) + certify every round's quorum
    (psum collective) + fold the certified rounds into a running state
    digest chain (the execution analogue: replicas apply committed ops in
    sequence order, reference src/behavior.rs:383-410). This is what
    ``__graft_entry__.dryrun_multichip`` compiles and runs on an N-device
    mesh, and what the multi-chip bench drives.
    """
    certify = quorum_certify(mesh, num_rounds, axis)
    state_spec = NamedSharding(mesh, P())

    @jax.jit
    def step(state_digest, pubs, msgs, sigs, round_ids, thresholds):
        res = certify(pubs, msgs, sigs, round_ids, thresholds)
        # Chain certified rounds into the replicated state digest: a
        # data-independent fold (certified rounds contribute their count;
        # uncertified contribute 0) keeps the step fully static-shape.
        contrib = jnp.where(
            res.certified, res.counts, jnp.zeros_like(res.counts)
        )
        mixed = jnp.concatenate(
            [state_digest.astype(jnp.int32), contrib], axis=0
        )
        new_state = jax.lax.with_sharding_constraint(
            jnp.cumsum(mixed)[-state_digest.shape[0] :].astype(jnp.int32)
            % jnp.int32(2**31 - 1),
            state_spec,
        )
        return new_state, res

    return step
