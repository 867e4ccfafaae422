"""Equivalence: C++ core crypto (via ctypes) vs hashlib and the Python
oracle — SURVEY.md §4 item 3, native edition."""

import hashlib
import itertools
import random
import secrets

import subprocess

import pytest

from pbft_tpu import native


def _core_test(*names, timeout=600):
    """``core_test`` (every test, or those named) and a tail of what it
    printed: which CHECK failed is on stderr, how far it got on stdout."""
    native.build()
    binary = native._BUILD_DIR / "core_test"
    if not binary.exists():
        pytest.skip("core_test not built")
    out = subprocess.run([str(binary), *names], capture_output=True, text=True, timeout=timeout)
    return out, f"exit {out.returncode}\nstdout: {out.stdout[-2000:]}\nstderr: {out.stderr[-4000:]}"


def test_native_ctest_binary():
    """The pure-C++ unit suite (core_test) passes — crypto known answers,
    canonical JSON, 4-replica commit, and a native view change."""
    out, tail = _core_test()
    assert out.returncode == 0, tail
    assert "all native tests passed" in out.stdout, tail


# ISSUE 41's cases of core_test, each on its own (``core_test <name>`` runs
# the tests named): a real ReplicaServer between stub peers and a stub
# gateway, and a loop shard run a drained stretch at a time; the witness is
# core_test's own send() and fsync(), which the dynamic linker puts before
# libc's (no hook in the program).
SEND_ONCE_CASES = [
    "emit_sends_once_a_connection",
    "flushed_streams_are_the_queued_frames",
    "one_frame_in_an_emit_is_one_send",
    "no_byte_waits_for_a_later_emit",
    "wal_flush_precedes_an_emits_first_byte",
    "connection_closed_by_failed_send_is_passed_over",
    "shard_sends_once_a_drained_stretch",
]


@pytest.mark.parametrize("case", SEND_ONCE_CASES)
def test_native_send_once_an_emit(case):
    out, tail = _core_test(case, timeout=120)
    assert out.returncode == 0 and "all native tests passed" in out.stdout, tail


def test_native_ctest_binary_knows_its_tests_by_name():
    out, tail = _core_test("no_such_test", timeout=60)
    assert out.returncode == 2 and "no test named no_such_test" in out.stderr, tail
from pbft_tpu.crypto import ref
from tests.test_crypto_ref import RFC8032_VECTORS

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native core not buildable"
)


@pytest.mark.parametrize("n", [0, 1, 64, 111, 128, 129, 300, 1000])
def test_blake2b_matches_hashlib(n):
    data = secrets.token_bytes(n)
    assert native.blake2b(data) == hashlib.blake2b(data, digest_size=32).digest()
    assert native.blake2b(data, 64) == hashlib.blake2b(data).digest()


@pytest.mark.parametrize("n", [0, 1, 95, 96, 111, 112, 127, 128, 129, 300])
def test_sha512_matches_hashlib(n):
    data = secrets.token_bytes(n)
    assert native.sha512(data) == hashlib.sha512(data).digest()


@pytest.mark.parametrize("seed,pub,msg,sig", RFC8032_VECTORS)
def test_rfc8032(seed, pub, msg, sig):
    seed, pub, msg, sig = (bytes.fromhex(x) for x in (seed, pub, msg, sig))
    assert native.public_key(seed) == pub
    assert native.sign(seed, msg) == sig
    assert native.verify(pub, msg, sig)
    assert not native.verify(pub, msg + b"x", sig)


# Message lengths that meet both branches of the native hash_to_scalar (a
# stack buffer up to 128 bytes of prefix/R/A and message, the heap beyond)
# and both SHA-512 block counts on either side of 111/112 bytes.
_ORACLE_MSG_LENS = (0, 1, 32, 111, 112, 300)
_ORACLE_PAIRS_A_LEN = 34  # 6 x 34 = 204 seed/message pairs


@pytest.mark.parametrize("msg_len", _ORACLE_MSG_LENS)
def test_native_vs_oracle_random(msg_len):
    """native.sign equals the pure-Python RFC 8032 oracle byte for byte on
    seeded seed/message pairs; the first pair of each length also goes
    through key derivation and both verifiers (the oracle is slow)."""
    rng = random.Random(0x39000 + msg_len)
    for i in range(_ORACLE_PAIRS_A_LEN):
        seed, msg = rng.randbytes(32), rng.randbytes(msg_len)
        sig_native = native.sign(seed, msg)
        assert sig_native == ref.sign(seed, msg), (seed.hex(), msg.hex())
        if i:
            continue
        pub = ref.public_key(seed)
        assert native.public_key(seed) == pub
        assert native.verify(pub, msg, sig_native)
        bad = bytes([sig_native[0] ^ 1]) + sig_native[1:]
        assert not native.verify(pub, msg, bad)
        assert native.verify(pub, msg, sig_native) == ref.verify(pub, msg, sig_native)


# --- The scalar arithmetic mod L under sign, verify and the batch path, held
# to Python's `int % L` through the library's test hooks (core/ed25519.h).

_L = ref.L
_K_MAX = (2**512 - 1) // _L  # the largest multiple of L in 512 bits
_ONES = 2**64 - 1
_REDUCE_EDGES = {
    "0": 0, "1": 1, "L-1": _L - 1, "L": _L, "L+1": _L + 1,
    "2L-1": 2 * _L - 1, "2L": 2 * _L, "2L+1": 2 * _L + 1,
    "2^252-1": 2**252 - 1, "2^252": 2**252, "2^253": 2**253,
    "2^256-1": 2**256 - 1, "2^256": 2**256,
    "2^383-1": 2**383 - 1,  # the bound sc_muladd128 stated
    "2^384+2^256": 2**384 + 2**256,
    "2^512-1": 2**512 - 1,
    "kL-1": _K_MAX * _L - 1, "kL": _K_MAX * _L, "kL+1": _K_MAX * _L + 1,
    "(2^256-1)^2+2^256-1": (2**256 - 1) ** 2 + 2**256 - 1,  # a*b + c at most
    **{f"ones-limbs-0-{n}": 2 ** (64 * n) - 1 for n in range(1, 8)},
    **{f"ones-limbs-{n}-7": 2**512 - 2 ** (64 * n) for n in range(1, 8)},
    "ones-odd-limbs": sum(_ONES << (64 * i) for i in (1, 3, 5, 7)),
    "ones-even-limbs": sum(_ONES << (64 * i) for i in (0, 2, 4, 6)),
}
# 256-bit operands at the edges, for a*b + c and a + b.
_OPERAND_EDGES = (0, 1, 2, _L - 1, _L, _L + 1, 2**252 - 1, 2**252, 2**255 - 1,
                  2**255, 2**256 - 1, _ONES, _ONES << 192, 2**128 - 1, 2**128)


@pytest.mark.parametrize("x", _REDUCE_EDGES.values(), ids=_REDUCE_EDGES.keys())
def test_sc_reduce512_edge(x):
    assert native.sc_reduce512(x) == x % _L


@pytest.mark.parametrize("near", [1, 2, 3, 2**64, 2**128, 2**192, 2**252, 2**259, _K_MAX // 3, _K_MAX // 2, _K_MAX - 1])
def test_sc_reduce512_around_multiples_of_l(near):
    """k*L - 1, k*L, k*L + 1 around every k where the quotient estimate
    could be one or two short."""
    for k in range(max(near - 2, 0), min(near + 3, _K_MAX + 1)):
        for d in (-1, 0, 1):
            x = k * _L + d
            if 0 <= x < 2**512:
                assert native.sc_reduce512(x) == x % _L, (k, d)


# primitive -> (operands, their width in bits, operands -> (native, Python)).
_SC_PRIMITIVES = {
    "reduce512": (1, 512, lambda x: (native.sc_reduce512(x), x % _L)),
    "muladd": (3, 256, lambda a, b, c: (native.sc_muladd(a, b, c), (a * b + c) % _L)),
    "muladd128": (3, 256, lambda a, b, c: (
        native.sc_muladd128(a % 2**128, b, c), (a % 2**128 * b + c) % _L)),
    # add's contract is reduced operands.
    "add": (2, 256, lambda a, b: (native.sc_add(a % _L, b % _L), (a + b) % _L)),
}


@pytest.mark.parametrize("primitive", ["muladd", "muladd128", "add"])
def test_sc_operand_edges(primitive):
    arity, _bits, run = _SC_PRIMITIVES[primitive]
    for operands in itertools.product(_OPERAND_EDGES, repeat=arity):
        got, want = run(*operands)
        assert got == want, (primitive, [hex(v) for v in operands])


@pytest.mark.parametrize("primitive", _SC_PRIMITIVES)
def test_sc_random_matches_python_int(primitive):
    """10,000 seeded random values a primitive, a quarter of them shaped:
    short, with a run of ones, or with a zero limb."""
    arity, bits, run = _SC_PRIMITIVES[primitive]
    rng = random.Random(f"pr39-{primitive}")

    def value(i):
        v = rng.getrandbits(bits)
        shape = i % 16
        if shape == 1:
            v >>= rng.randrange(bits)
        elif shape == 2:
            v |= (2 ** rng.randrange(1, bits) - 1) << rng.randrange(bits)
            v %= 2**bits
        elif shape == 3:
            v &= ~(_ONES << (64 * rng.randrange(bits // 64)))
        elif shape == 4 and bits == 512:
            v = rng.randrange(_K_MAX + 1) * _L + rng.randrange(-2, 3)
            v = min(max(v, 0), 2**512 - 1)
        return v

    for i in range(10_000):
        operands = [value(i) for _ in range(arity)]
        got, want = run(*operands)
        assert got == want, (primitive, [hex(v) for v in operands])


def test_native_rejects_malleated_s():
    seed, pub = ref.keygen()
    msg = secrets.token_bytes(32)
    sig = ref.sign(seed, msg)
    s = int.from_bytes(sig[32:], "little")
    mall = sig[:32] + int.to_bytes(s + ref.L, 32, "little")
    assert not native.verify(pub, msg, mall)


def test_native_rejects_bad_pubkeys():
    msg = secrets.token_bytes(32)
    sig = bytes(64)
    noncanon = int.to_bytes(ref.P, 32, "little")
    assert not native.verify(noncanon, msg, sig)
    assert not native.verify(int.to_bytes(2, 32, "little"), msg, sig) or \
        ref.point_decompress(int.to_bytes(2, 32, "little")) is not None


def test_native_batch():
    items, want = [], []
    for i in range(7):
        seed, pub = ref.keygen()
        msg = secrets.token_bytes(32)
        sig = ref.sign(seed, msg)
        if i % 3 == 0:
            sig = sig[:33] + bytes([sig[33] ^ 0x80]) + sig[34:]
        items.append((pub, msg, sig))
        want.append(i % 3 != 0)
    assert native.verify_batch(items) == want
    assert native.verify_batch([]) == []


def _torsion_point():
    """A nonzero small-order point: [L]P for an arbitrary curve point P
    outside the prime subgroup (every nonzero torsion point has order
    dividing 8 on edwards25519)."""
    from pbft_tpu.crypto import ref

    for y in range(2, 60):
        enc = y.to_bytes(32, "little")
        pt = ref.point_decompress(enc)
        if pt is None:
            continue
        t = ref.scalar_mult(ref.L, pt)
        if t != (0, 1):  # not the identity -> genuine torsion
            return t
    raise AssertionError("no torsion point found in scan range")


def _craft_torsion_sig(seed: bytes, msg: bytes, defect):
    """A signature with verification defect exactly -defect (a Byzantine
    SIGNER crafting with its own secret key): R' = [r]B + defect,
    s = r + H(R',A,M)*a, so [s]B - [h]A - R' = -defect — torsion-only,
    invisible to any check that multiplies by the cofactor."""
    from pbft_tpu.crypto import ref

    a, _prefix = ref.secret_expand(seed)
    pub_pt = ref.scalar_mult(a, ref.BASE)
    pub = ref.point_compress(pub_pt)
    r = 0x1234567  # any fixed nonce: determinism keeps the test stable
    big_r = ref.point_compress(
        ref.point_add(ref.scalar_mult(r, ref.BASE), defect)
    )
    h = ref._h512_int(big_r, pub, msg) % ref.L
    s = (r + h * a) % ref.L
    return pub, big_r + s.to_bytes(32, "little")


def test_batch_rejects_a_lone_torsion_defect_deterministically():
    """A crafted signature whose defect is a small-order point must be
    rejected by the batch path exactly like per-item verify: the RLC
    coefficients are forced === 1 (mod 8), so a lone torsion defect can
    never cancel out of the combination (core/ed25519.cc accept-set
    note). Repeated runs pin determinism across random coefficients."""
    from pbft_tpu import native
    from pbft_tpu.crypto import ref

    t = _torsion_point()
    seed = bytes(range(32))
    msg = b"\x51" * 32
    pub, crafted = _craft_torsion_sig(seed, msg, t)
    assert not native.verify(pub, msg, crafted)
    assert not ref.verify(pub, msg, crafted)

    honest = []
    for i in range(15):
        s = bytes([i + 3]) * 32
        m = bytes([0xC0 ^ i]) * 32
        honest.append((native.public_key(s), m, native.sign(s, m)))
    for _ in range(8):  # fresh random z_i every call
        verdicts = native.verify_batch(honest[:7] + [(pub, msg, crafted)] + honest[7:])
        assert verdicts[7] == 0 and sum(verdicts) == 15, verdicts


def test_batch_torsion_pair_caveat_is_exactly_as_documented():
    """The documented accept-set caveat (core/ed25519.cc): TWO crafted
    signatures with cancelling torsion defects in ONE window pass the
    RLC check — equivalent in power to sender equivocation, which PBFT
    already tolerates. Per-item verify still rejects both; this test
    pins the caveat so any change to the batch semantics is loud."""
    from pbft_tpu import native
    from pbft_tpu.crypto import ref

    t = _torsion_point()
    neg_t = (ref.P - t[0], t[1])  # -T: negate x
    crafted = []
    for i, defect in ((0, t), (1, neg_t)):
        seed = bytes([i + 1]) * 32
        msg = bytes([0xE0 + i]) * 32
        pub, bad = _craft_torsion_sig(seed, msg, defect)
        assert not native.verify(pub, msg, bad)  # per-item: rejected
        crafted.append((pub, msg, bad))
    honest = []
    for i in range(10):
        s = bytes([i + 9]) * 32
        m = bytes([0x99 ^ i]) * 32
        honest.append((native.public_key(s), m, native.sign(s, m)))
    # Same window: the pair's defects cancel ((z1 - z2) T = 0 since
    # 8 | z1 - z2 and T has order dividing 8) -> batch accepts the pair.
    verdicts = native.verify_batch(honest + crafted)
    assert verdicts == [True] * 12, verdicts
    # Split windows (bisect below the RLC threshold): per-item authority
    # rejects each crafted signature alone.
    assert native.verify_batch([crafted[0]]) == [False]
    assert native.verify_batch([crafted[1]]) == [False]


hypothesis = __import__("pytest").importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(min_value=0, max_value=48),
    corruption=st.lists(
        st.tuples(
            st.integers(min_value=0, max_value=47),  # item (mod n)
            st.sampled_from(["sig_r", "sig_s", "pub", "msg", "s_ge_l"]),
            st.integers(min_value=0, max_value=31),  # byte offset
        ),
        max_size=6,
    ),
)
def test_batch_verify_matches_per_item_under_fuzz(n, corruption):
    """Property: for ANY mix of corruptions, the batch path's verdict
    equals per-item native.verify for every item. (The only documented
    exception — colluding torsion-defect pairs — needs secret-key
    crafting that byte-level corruption cannot produce.)"""
    from pbft_tpu import native

    if n == 0:
        assert native.verify_batch([]) == []
        return
    items = []
    for i in range(n):
        seed = bytes([i + 1, 0x33]) * 16
        msg = bytes([0x70 ^ i]) * 32
        items.append((native.public_key(seed), msg, native.sign(seed, msg)))
    for which, kind, off in corruption:
        i = which % n
        pub, msg, sig = items[i]
        if kind == "sig_r":
            sig = sig[:off] + bytes([sig[off] ^ 0x80]) + sig[off + 1 :]
        elif kind == "sig_s":
            j = 32 + off
            sig = sig[:j] + bytes([sig[j] ^ 0x40]) + sig[j + 1 :]
        elif kind == "pub":
            pub = pub[:off] + bytes([pub[off] ^ 0x20]) + pub[off + 1 :]
        elif kind == "msg":
            msg = msg[:off] + bytes([msg[off] ^ 0x10]) + msg[off + 1 :]
        else:  # s >= L: a non-canonical scalar must be rejected pre-RLC
            sig = sig[:32] + b"\xff" * 31 + b"\x1f"
        items[i] = (pub, msg, sig)
    batch = native.verify_batch(items)
    single = [native.verify(p, m, s) for p, m, s in items]
    assert batch == single
