"""Plain reference of the MAC-vector authenticator (protocol 1.3.0).

In MAC mode a normal-case message (pre-prepare, prepare, commit, checkpoint)
carries, behind the fields of its signed form, one 16-byte tag ("lane") per
receiver, each under the key that the sender and that receiver derived from
their link's signed handshake; a receiver checks its own lane and no
signature. This file restates the authenticator from the wire format with
``hashlib`` alone and imports nothing of the program:

    lane key    keyed BLAKE2b-256, key = the handshake's shared secret,
                over "pbft-tpu-k1|" label "|" eph_i "|" eph_r, with the label
                "a-i2r" for what the link's initiator sends and "a-r2i" for
                what its responder sends
    lane        keyed BLAKE2b-128, key = the lane key of the direction,
                over "pbft-tpu-auth1|" and the message's 32-byte signable
                digest (the bytes its signature covers)
    frame       0xB2 | code 0x12..0x16 | the signed form's fields |
                count x (receiver id: u8 | lane: 16 bytes) | count: u8,
                lanes in ascending order of receiver id
"""

from __future__ import annotations

import hashlib

KDF_CONTEXT = b"pbft-tpu-k1|"
LANE_CONTEXT = b"pbft-tpu-auth1|"
LANE_LEN = 16
FRAME_MAGIC = 0xB2
MAC_CODES = (0x12, 0x13, 0x14, 0x15, 0x16)
MAX_LANES = 64


def lane_keys(shared: bytes, eph_i: bytes, eph_r: bytes) -> tuple:
    """(initiator-to-responder key, responder-to-initiator key) of one link,
    from the handshake's transcript: the Diffie-Hellman shared secret and
    the two ephemeral public keys, the initiator's first."""

    def kdf(label: bytes) -> bytes:
        return hashlib.blake2b(
            KDF_CONTEXT + label + b"|" + eph_i + b"|" + eph_r, key=shared, digest_size=32
        ).digest()

    return kdf(b"a-i2r"), kdf(b"a-r2i")


def lane(key: bytes, signable_digest: bytes) -> bytes:
    """The tag one receiver checks: 16 bytes over the 32-byte digest."""
    return hashlib.blake2b(
        LANE_CONTEXT + signable_digest, key=key, digest_size=LANE_LEN
    ).digest()


def vector(send_keys: dict, signable_digest: bytes) -> bytes:
    """The tail of a frame: one lane per receiver in ``send_keys``
    ({receiver id: the sender's key toward it}), ascending by receiver id,
    then the count."""
    if not 1 <= len(send_keys) <= MAX_LANES:
        raise ValueError("a frame carries 1 to 64 lanes")
    out = bytearray()
    for rid in sorted(send_keys):
        out.append(rid)
        out += lane(send_keys[rid], signable_digest)
    out.append(len(send_keys))
    return bytes(out)


def own_lane(frame: bytes, rid: int) -> bytes | None:
    """Receiver ``rid``'s lane, found from the frame's tail; None where the
    frame is no MAC frame, its vector is malformed or it has no such lane."""
    if len(frame) < 2 or frame[0] != FRAME_MAGIC or frame[1] not in MAC_CODES:
        return None
    count = frame[-1]
    start = len(frame) - 1 - (1 + LANE_LEN) * count
    if not 1 <= count <= MAX_LANES or start < 2:
        return None
    for k in range(count):
        at = start + (1 + LANE_LEN) * k
        if frame[at] == rid:
            return frame[at + 1 : at + 1 + LANE_LEN]
    return None


def accepts(frame: bytes, rid: int, recv_key: bytes, signable_digest: bytes) -> bool:
    """Whether receiver ``rid`` accepts the frame by its own lane: the lane
    is there and is the tag of the digest under the key of the direction."""
    got = own_lane(frame, rid)
    return got is not None and got == lane(recv_key, signable_digest)
