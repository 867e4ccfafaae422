"""The benchmark's plain reference of the MAC-vector authenticator
(``chipbench/reference/mac_vector.py``, ``hashlib`` alone) against both
runtimes (ISSUE 32): the lane keys of a link from its handshake transcript,
one lane per receiver over a message's signable digest, the order of the
lanes in a frame, and the accept / reject of one's own lane, on seeded keys
and digests for n = 4 and n = 16.
"""

from __future__ import annotations

import hmac
import random
import sys
from pathlib import Path

import pytest

from pbft_tpu import native
from pbft_tpu.consensus import messages as M
from pbft_tpu.net import secure

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "chipbench"))

from reference import mac_vector  # noqa: E402

SENDER = 1  # a backup: its frames go to the primary and to the other backups


def _links(rng: random.Random, n: int) -> dict:
    """The sender's link to every other replica: the handshake's transcript
    (both ephemeral keys and the shared secret, as both ends compute it) and
    who dialed. Keys from the seed, the Diffie-Hellman by the program's own
    reference arithmetic."""
    links = {}
    for rid in (r for r in range(n) if r != SENDER):
        sec_s, pub_s = secure.dh_keypair(rng.randbytes(32))
        sec_r, pub_r = secure.dh_keypair(rng.randbytes(32))
        shared = secure.dh_shared(sec_s, pub_r)
        assert shared == secure.dh_shared(sec_r, pub_s) == native.dh_shared(sec_s, pub_r)
        initiator = rng.random() < 0.5  # whether the sender dialed this link
        eph_i, eph_r = (pub_s, pub_r) if initiator else (pub_r, pub_s)
        links[rid] = (shared, eph_i, eph_r, initiator)
    return links


def _vote(rng: random.Random) -> M.Message:
    kind = rng.choice((M.Prepare, M.Commit))
    return kind(view=rng.randrange(4), seq=rng.randrange(1, 1 << 40),
                digest=rng.randbytes(32).hex(), replica=SENDER, sig=rng.randbytes(64).hex())


def _send_keys(links: dict, derive) -> dict:
    """{receiver: the key of the sender's direction on that link}."""
    out = {}
    for rid, (shared, eph_i, eph_r, initiator) in links.items():
        i2r, r2i = derive(shared, eph_i, eph_r)
        out[rid] = i2r if initiator else r2i
    return out


def _accepted_by_the_three(frame: bytes, rid: int, key: bytes, digest: bytes) -> list:
    """The receiver's check as the reference, the Python codec and handshake
    (``messages.mac_frame_lane`` against ``secure.mac_tag``) and the native
    library make it."""
    py_lane = M.mac_frame_lane(frame, rid)
    cc_lane = native.mac_frame_lane(frame, rid)
    return [
        mac_vector.accepts(frame, rid, key, digest),
        py_lane is not None and hmac.compare_digest(py_lane, secure.mac_tag(key, digest)),
        cc_lane is not None and cc_lane == native.mac_tag(key, digest),
    ]


@pytest.mark.parametrize("n", [4, 16])
def test_the_references_lanes_are_both_runtimes_lanes(n):
    rng = random.Random(3200000000 + n)
    links = _links(rng, n)
    keys = _send_keys(links, mac_vector.lane_keys)
    assert keys == _send_keys(links, secure.derive_auth_keys)
    assert keys == _send_keys(links, native.derive_auth_keys)
    assert len(set(keys.values())) == n - 1  # a key a link and direction
    for _ in range(8):
        msg = _vote(rng)
        digest = msg.signable()
        lanes = [(rid, mac_vector.lane(keys[rid], digest)) for rid in sorted(keys)]
        assert lanes == [(rid, secure.mac_tag(keys[rid], digest)) for rid in sorted(keys)]
        assert lanes == [(rid, native.mac_tag(keys[rid], digest)) for rid in sorted(keys)]
        # One frame for every receiver, the lanes at its tail in ascending
        # order of receiver id, the same bytes from both encoders.
        frame = M.to_binary_mac(msg, lanes)
        assert frame == native.message_to_binary_mac(msg.canonical(), lanes)
        tail = mac_vector.vector(keys, digest)
        assert frame.endswith(tail) and frame[:2] == bytes((mac_vector.FRAME_MAGIC, frame[1]))
        assert frame[1] in mac_vector.MAC_CODES and len(tail) == 17 * (n - 1) + 1
        assert frame[2 : len(frame) - len(tail)] == M.to_binary(msg)[2:]
        for rid in keys:
            assert mac_vector.own_lane(frame, rid) == M.mac_frame_lane(frame, rid)
            assert mac_vector.own_lane(frame, rid) == native.mac_frame_lane(frame, rid)
            assert _accepted_by_the_three(frame, rid, keys[rid], digest) == [True] * 3
        assert mac_vector.own_lane(frame, SENDER) is None  # no lane to oneself
        assert M.mac_frame_lane(frame, SENDER) is None
        # A receiver's lane is under ITS link's key: no other receiver's key
        # accepts it, and neither does the other direction of its own link.
        rid, other = rng.sample(sorted(keys), 2)
        assert _accepted_by_the_three(frame, rid, keys[other], digest) == [False] * 3
        shared, eph_i, eph_r, initiator = links[rid]
        back = mac_vector.lane_keys(shared, eph_i, eph_r)[initiator]
        assert _accepted_by_the_three(frame, rid, back, digest) == [False] * 3


def _flip(rng: random.Random, blob: bytes, lo: int = 0, hi: int = None) -> bytes:
    at = rng.randrange(lo, len(blob) if hi is None else hi)
    return blob[:at] + bytes([blob[at] ^ (1 << rng.randrange(8))]) + blob[at + 1 :]


@pytest.mark.parametrize("n", [4, 16])
@pytest.mark.parametrize("what", ["lane", "digest", "key"])
def test_one_flipped_bit_is_rejected_by_all_three(n, what):
    rng = random.Random(3200000100 + n)
    keys = _send_keys(_links(rng, n), mac_vector.lane_keys)
    for _ in range(16):
        msg = _vote(rng)
        digest = msg.signable()
        frame = M.to_binary_mac(msg, [(r, mac_vector.lane(keys[r], digest)) for r in sorted(keys)])
        rid = rng.choice(sorted(keys))
        key = keys[rid]
        assert _accepted_by_the_three(frame, rid, key, digest) == [True] * 3
        if what == "lane":  # a bit of the receiver's own 16 bytes in the frame
            at = frame.rindex(bytes([rid]) + mac_vector.lane(key, digest))
            frame = _flip(rng, frame, at + 1, at + 17)
        elif what == "digest":  # another message under the same lanes
            digest = _flip(rng, digest)
        else:
            key = _flip(rng, key)
        assert _accepted_by_the_three(frame, rid, key, digest) == [False] * 3
