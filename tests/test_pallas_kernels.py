"""Equivalence of the VMEM chain kernels against the XLA field/ed25519
pipeline and the RFC 8032 oracle (interpret mode on the CPU backend; the
same kernels compile under Mosaic on TPU). A tile is (32, 8, 128): a limb a
whole vector register, 1,024 items; a batch is padded to whole tiles.

The VMEM path must be bit-identical to the XLA path: verifier results
feed consensus quorums, and any divergence between backends would split
replicas (SURVEY.md §7 "Determinism at the FFI boundary"). The arithmetic
itself is held to field.py without the interpreter, in tier-1:
tests/test_vmem_chains.py."""

import numpy as np
import pytest

import jax.numpy as jnp

from pbft_tpu.crypto import field as F
from pbft_tpu.crypto import pallas_kernels as PK
from pbft_tpu.crypto import ref
from pbft_tpu.crypto import ed25519 as E

pytestmark = pytest.mark.slow  # interpret-mode kernels, minutes not seconds

_RNG = np.random.default_rng(0xED25519)


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    # Off the TPU the kernels run only under the interpreter, which only a
    # test asks for (read at trace time; no environment variable does).
    monkeypatch.setattr(PK, "_INTERPRET", True)


def _rand_field(batch, lo=-(2**9) + 1, hi=2**9):
    """Random carried-form limb arrays (the bound every chain input obeys)."""
    return jnp.asarray(
        _RNG.integers(lo, hi, size=(batch, F.NLIMBS)), jnp.int32
    )


def test_inv_matches_field_and_oracle():
    x = _rand_field(5)
    got = np.asarray(F.canon(PK.inv(x)))
    want = np.asarray(F.canon(F.inv(x)))
    np.testing.assert_array_equal(got, want)
    for i in range(x.shape[0]):
        v = F.limbs_to_int(np.asarray(F.canon(x))[i]) % F.P
        expect = pow(v, F.P - 2, F.P)
        assert F.limbs_to_int(got[i]) == expect


def test_pow_p58_matches_field():
    x = _rand_field(4)
    got = np.asarray(F.canon(PK.pow_p58(x)))
    want = np.asarray(F.canon(F.pow_p58(x)))
    np.testing.assert_array_equal(got, want)


def test_ladder_matches_xla_ladder():
    # Three items in a tile of 1,024 (the rest is padding): the ladder math
    # is per-element.
    batch = 3
    pubs, s_list, h_list = [], [], []
    for i in range(batch):
        seed = bytes([i + 9]) * 32
        pubs.append(ref.public_key(seed))
        s_list.append(int.from_bytes(_RNG.bytes(32), "little") % ref.L)
        h_list.append(int.from_bytes(_RNG.bytes(32), "little") % ref.L)
    pub_arr = jnp.asarray(
        np.stack([np.frombuffer(p, np.uint8) for p in pubs]), jnp.uint8
    )
    ok, a_pt = E.decompress(pub_arr)
    assert bool(np.asarray(ok).all())
    s = jnp.asarray(
        np.stack([np.frombuffer(int(v).to_bytes(32, "little"), np.uint8) for v in s_list]),
        jnp.uint8,
    )
    h = jnp.asarray(
        np.stack([np.frombuffer(int(v).to_bytes(32, "little"), np.uint8) for v in h_list]),
        jnp.uint8,
    )
    sb = F.scalar_bits(F.bytes_to_limbs(s))
    hb = F.scalar_bits(F.bytes_to_limbs(h))
    a_neg = E.point_neg(a_pt)
    got = PK.ladder(sb, hb, a_neg)
    want = E.shamir_ladder(sb, hb, a_neg)
    # Projective coords may differ; the affine encodings must be identical.
    np.testing.assert_array_equal(
        np.asarray(E.compress(got)), np.asarray(E.compress(want))
    )


def test_full_verify_pallas_path(monkeypatch):
    """verify_kernel where the shape rule says "vmem" (as on a TPU for a
    shape that fills a tile): same accept/reject set as the oracle,
    including a corrupted signature and a corrupted message."""
    monkeypatch.setattr(E, "chains_for", lambda rows, backend=None: "vmem")
    # One valid + one corrupt-R + one corrupt-message row: full coverage
    # of the accept/reject branches at the smallest interpreter cost
    # (each row re-runs the whole ladder in the Python interpreter).
    n = 3
    pubs = np.zeros((n, 32), np.uint8)
    msgs = np.zeros((n, 32), np.uint8)
    sigs = np.zeros((n, 64), np.uint8)
    for i in range(n):
        seed = bytes([0x33 ^ i]) * 32
        msg = bytes([i + 1]) * 32
        pubs[i] = np.frombuffer(ref.public_key(seed), np.uint8)
        msgs[i] = np.frombuffer(msg, np.uint8)
        sigs[i] = np.frombuffer(ref.sign(seed, msg), np.uint8)
    sigs[1, 3] ^= 0x40  # corrupt R
    msgs[2, 0] ^= 0x01  # corrupt message
    out = np.asarray(E.verify_kernel(pubs, msgs, sigs))
    assert out.tolist() == [True, False, False]
