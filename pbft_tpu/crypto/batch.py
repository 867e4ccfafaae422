"""The batched verifier: PBFT's crypto hot path as one XLA launch.

`verify_batch(pubs, msgs, sigs)` verifies B independent Ed25519 signatures in
a single jit-compiled call — the TPU-era replacement for the reference's
(intended) per-message checks. A replica accumulates a view-round's quorum
certificates (up to 2*(2f+1) PREPARE+COMMIT signatures per round, times the
batching window) into fixed-size (pubkey, msg-digest, signature) tensors and
gates phase transitions on the returned bitmap (BASELINE.json north_star).

Shapes are static per batch size; use padded power-of-two batches to bound
the number of XLA compilations (pad slots are filled with a known-good
self-signed triple so padding never fails a batch).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from . import ref
from .ed25519 import verify_kernel

# One known-valid (pub, msg, sig) triple for padding slots.
_PAD_SEED = bytes(range(32))
_PAD_MSG = b"pbft_tpu batch padding.........."
assert len(_PAD_MSG) == 32
_PAD_PUB = np.frombuffer(ref.public_key(_PAD_SEED), np.uint8)
_PAD_SIG = np.frombuffer(ref.sign(_PAD_SEED, _PAD_MSG), np.uint8)
_PAD_MSG_ARR = np.frombuffer(_PAD_MSG, np.uint8)


@functools.partial(jax.jit, static_argnames=())
def _verify_jit(pubs, msgs, sigs):
    return verify_kernel(pubs, msgs, sigs)


def verify_batch(pubs, msgs, sigs) -> jax.Array:
    """(B,32),(B,32),(B,64) uint8 arrays -> (B,) bool validity bitmap."""
    return _verify_jit(
        jnp.asarray(pubs, jnp.uint8),
        jnp.asarray(msgs, jnp.uint8),
        jnp.asarray(sigs, jnp.uint8),
    )


def pad_batch(items, size: int):
    """items: list of (pub32, msg32, sig64) bytes -> padded uint8 arrays.

    Returns (pubs, msgs, sigs, n) where slots >= n are the known-good pad
    triple (they verify True and are sliced off by the caller).
    """
    n = len(items)
    if n > size:
        raise ValueError(f"batch of {n} exceeds padded size {size}")
    pubs = np.tile(_PAD_PUB, (size, 1))
    msgs = np.tile(_PAD_MSG_ARR, (size, 1))
    sigs = np.tile(_PAD_SIG, (size, 1))
    for i, (pub, msg, sig) in enumerate(items):
        pubs[i] = np.frombuffer(pub, np.uint8)
        msgs[i] = np.frombuffer(msg, np.uint8)
        sigs[i] = np.frombuffer(sig, np.uint8)
    return pubs, msgs, sigs, n


# Padded sizes are drawn from a short ladder so the whole system compiles
# at most len(_PAD_LADDER) kernel shapes (recompiles are minutes on CPU).
_PAD_LADDER = (16, 64, 256, 1024, 4096)


def pad_size(n: int) -> int:
    for size in _PAD_LADDER:
        if n <= size:
            return size
    return ((n + _PAD_LADDER[-1] - 1) // _PAD_LADDER[-1]) * _PAD_LADDER[-1]


def verify_many(items, pad_to: int | None = None) -> list[bool]:
    """Convenience host API: list of (pub, msg, sig) byte triples -> bools,
    one lazy single-device launch (the in-process reference path; what
    reaches all of a host's chips is ``verifyd``)."""
    if not items:
        return []
    pubs, msgs, sigs, n = pad_batch(items, pad_to or pad_size(len(items)))
    out = np.asarray(verify_batch(pubs, msgs, sigs))
    return [bool(v) for v in out[:n]]
