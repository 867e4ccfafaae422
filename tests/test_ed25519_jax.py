"""Equivalence tests: JAX Ed25519 verifier vs the pure-Python RFC 8032 oracle.

This is SURVEY.md §4 item 3 — the crypto-equivalence leg of the test pyramid:
known-answer RFC 8032 vectors, random valid signatures, deliberately
corrupted signatures, malleated S, bad pubkeys, and batch padding.
"""

import secrets

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from pbft_tpu.crypto import ref
from pbft_tpu.crypto import batch as B
from pbft_tpu.crypto import ed25519 as E
from pbft_tpu.crypto import field as F
from tests.test_crypto_ref import RFC8032_VECTORS

# Kernel-compile-heavy: slow tier (pytest -m slow).
pytestmark = pytest.mark.slow

# jit wrappers: eager-mode dispatch of the limb arithmetic is far too slow
# for tests; compile once per shape and reuse.
_jit_verify = jax.jit(E.verify_kernel)
_jit_compress = jax.jit(E.compress)
_jit_decompress = jax.jit(E.decompress)
_jit_add = jax.jit(E.point_add)


def as_u8(b: bytes):
    return np.frombuffer(b, np.uint8)


def jax_verify_one(pub: bytes, msg: bytes, sig: bytes) -> bool:
    return bool(_jit_verify(as_u8(pub), as_u8(msg), as_u8(sig)))


def test_point_roundtrip_and_add():
    # decompress(compress(.)) and additions agree with the oracle.
    seed, pub = ref.keygen(b"\x11" * 32)
    a = ref.point_decompress(pub)
    ok, pt = _jit_decompress(as_u8(pub))
    assert bool(ok)
    assert bytes(np.asarray(_jit_compress(pt))) == pub

    twice_oracle = ref.point_add(a, a)
    twice = _jit_add(pt, pt)
    assert bytes(np.asarray(_jit_compress(twice))) == ref.point_compress(twice_oracle)

    plus_base_oracle = ref.point_add(a, ref.BASE)
    plus_base = _jit_add(pt, E.base_point())
    assert (
        bytes(np.asarray(_jit_compress(plus_base)))
        == ref.point_compress(plus_base_oracle)
    )


def test_identity_handling():
    ident = E.identity()
    assert bytes(np.asarray(_jit_compress(ident))) == ref.point_compress((0, 1))
    pt = E.base_point()
    moved = _jit_add(pt, ident)
    assert bytes(np.asarray(_jit_compress(moved))) == ref.point_compress(ref.BASE)


@pytest.mark.parametrize("seed,pub,msg,sig", RFC8032_VECTORS[:2])
def test_rfc8032_vectors_32byte_variants(seed, pub, msg, sig):
    # The TPU pipeline always signs 32-byte digests; re-sign the vector
    # seeds over 32-byte messages and check JAX vs oracle.
    seed = bytes.fromhex(seed)
    pub = ref.public_key(seed)
    digest = secrets.token_bytes(32)
    good = ref.sign(seed, digest)
    assert ref.verify(pub, digest, good)
    assert jax_verify_one(pub, digest, good)
    bad = bytes([good[0] ^ 1]) + good[1:]
    assert not jax_verify_one(pub, digest, bad)


def test_random_equivalence():
    rng_cases = []
    for _ in range(4):
        seed, pub = ref.keygen()
        msg = secrets.token_bytes(32)
        sig = ref.sign(seed, msg)
        rng_cases.append((pub, msg, sig, True))
        # corrupted sig R
        rng_cases.append((pub, msg, bytes([sig[0] ^ 0x40]) + sig[1:], False))
        # corrupted msg
        rng_cases.append((pub, secrets.token_bytes(32), sig, False))
    for pub, msg, sig, want in rng_cases:
        assert ref.verify(pub, msg, sig) == want
        assert jax_verify_one(pub, msg, sig) == want


def test_malleated_s_rejected():
    seed, pub = ref.keygen()
    msg = secrets.token_bytes(32)
    sig = ref.sign(seed, msg)
    s = int.from_bytes(sig[32:], "little")
    mall = sig[:32] + int.to_bytes(s + ref.L, 32, "little")
    assert not jax_verify_one(pub, msg, mall)
    assert not ref.verify(pub, msg, mall)


def test_bad_pubkeys_rejected():
    msg = secrets.token_bytes(32)
    sig = bytes(64)
    # non-canonical y (y = p), and an off-curve y
    noncanon = int.to_bytes(F.P, 32, "little")
    assert not jax_verify_one(noncanon, msg, sig)
    off_curve = None
    k = 0
    while off_curve is None:
        cand = int.to_bytes(2 + k, 32, "little")
        if ref.point_decompress(cand) is None:
            off_curve = cand
        k += 1
    assert not jax_verify_one(off_curve, msg, sig)


def test_batch_mixed_validity():
    items = []
    want = []
    for i in range(5):
        seed, pub = ref.keygen()
        msg = secrets.token_bytes(32)
        sig = ref.sign(seed, msg)
        if i % 2 == 1:  # corrupt odd entries
            sig = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
        items.append((pub, msg, sig))
        want.append(i % 2 == 0)
    got = B.verify_many(items, pad_to=8)
    assert got == want


@pytest.mark.parametrize("n", [0, 1, 7, 8])
def test_verify_many_block_path_matches_the_oracle(n):
    from tests.test_verify_spans import _probe_items

    items = _probe_items(n)
    assert B.verify_many(items, pad_to=8) == [ref.verify(*item) for item in items]


def test_batch_empty_and_padding_slots():
    assert B.verify_many([]) == []
    block, n = B.pad_batch([], 4)
    out = np.asarray(B.verify_batch(*B.split_block(block)))
    assert n == 0 and out.all(), "padding triple must verify"
