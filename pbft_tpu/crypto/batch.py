"""The batched verifier: PBFT's crypto hot path as one XLA launch.

`verify_batch(pubs, msgs, sigs)` verifies B independent Ed25519 signatures in
a single jit-compiled call — the TPU-era replacement for the reference's
(intended) per-message checks. A replica accumulates a view-round's quorum
certificates (up to 2*(2f+1) PREPARE+COMMIT signatures per round, times the
batching window) into fixed-size (pubkey, msg-digest, signature) tensors and
gates phase transitions on the returned bitmap (BASELINE.json north_star).

Shapes are static per batch size; use padded power-of-two batches to bound
the number of XLA compilations (pad slots are filled with a known-good
self-signed triple so padding never fails a batch). A padded batch is staged
as ONE (B, 128) uint8 block of ``pub | msg | sig`` rows, the layout the
triples have on the verify service's wire: `pad_rows` from rows that are in
it already (what the service carries), `pad_batch` from a list of triples.
"""

from __future__ import annotations

import functools
from itertools import chain

import numpy as np
import jax
import jax.numpy as jnp

from . import ref
from .ed25519 import verify_kernel

# One known-valid (pub 32 | msg 32 | sig 64) triple for padding slots: a row
# of a staged block, in the 128-byte layout of the verify service's wire.
_PAD_SEED = bytes(range(32))
_PAD_MSG = b"pbft_tpu batch padding.........."
assert len(_PAD_MSG) == 32
_PAD_ROW = np.frombuffer(
    ref.public_key(_PAD_SEED) + _PAD_MSG + ref.sign(_PAD_SEED, _PAD_MSG), np.uint8
)


def split_block(block):
    """A (B, 128) block of triples -> its (B,32) pubs, (B,32) msgs and (B,64)
    sigs: views of a NumPy block, slices that fuse into their consumers under
    jit. The one place that knows the columns."""
    return block[:, :32], block[:, 32:64], block[:, 64:]


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


@functools.lru_cache(maxsize=16)
def _pad_template(size: int) -> np.ndarray:
    """``size`` rows of the pad triple, made once a shape and never written."""
    template = np.tile(_PAD_ROW, (size, 1))
    template.setflags(write=False)
    return template


def pad_rows(segments, size: int):
    """segments: (k, 128) uint8 arrays of ``pub | msg | sig`` rows, in item
    order (the requests of a verify-service window, as they came off the
    wire) -> one (size, 128) uint8 block, and n, the rows they hold.

    Rows >= n are the known-good pad triple (they verify True and are
    sliced off by the caller). The block is a fresh copy of the shape's
    template every time, so two windows in flight never share a row, and
    the rows land in it in ONE assignment a segment: no work per item in
    Python.
    """
    block = _pad_template(size).copy()
    n = 0
    for rows in segments:
        if n + len(rows) > size:
            raise ValueError(f"batch of {n + len(rows)} exceeds padded size {size}")
        block[n : n + len(rows)] = rows
        n += len(rows)
    return block, n


def pad_batch(items, size: int):
    """items: list of (pub32, msg32, sig64) bytes -> :func:`pad_rows` of
    their rows, joined once."""
    n = len(items)
    rows = np.frombuffer(b"".join(chain.from_iterable(items)), np.uint8)
    if rows.size != n * 128:
        raise ValueError(f"{n} items of {rows.size} bytes: not 128-byte triples")
    return pad_rows([rows.reshape(n, 128)], size)


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
    block, n = pad_batch(items, pad_to or pad_size(len(items)))
    return np.asarray(verify_batch(*split_block(block)))[:n].tolist()
