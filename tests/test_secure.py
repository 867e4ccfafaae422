"""Encrypted replica links (VERDICT r3 missing #1 + #3): signed-ephemeral-DH
handshake, keyed-BLAKE2b AEAD framing, and protocol-version negotiation —
unit round trips, C++/Python byte-identity, wire-level rejection cases, and
end-to-end secure clusters on both socket layers of pbftd.

The reference secures every libp2p link with development_transport (Noise +
yamux, reference src/main.rs:42) and names its protocol
/ackintosh/pbft/1.0.0 (reference src/protocol_config.rs:24); these tests
pin the rebuild's equivalent (pbft_tpu/net/secure.py + core/secure.cc)."""

import hashlib
import json
import os
import socket

import pytest

from pbft_tpu import native
from pbft_tpu.crypto import ref
from pbft_tpu.net import secure

needs_native = pytest.mark.skipif(
    not native.available(), reason="native core not built"
)


def _pair(secure_mode=True):
    seeds = {0: bytes([1]) * 32, 1: bytes([2]) * 32}
    pubs = {i: ref.public_key(s) for i, s in seeds.items()}
    a = secure.SecureChannel(
        0, seeds[0], pubs.get, initiator=True, expected_peer=1
    )
    b = secure.SecureChannel(1, seeds[1], pubs.get, initiator=False)
    return a, b, seeds, pubs


# -- handshake state machine (pure Python, no sockets) -----------------------


def test_handshake_round_trip_and_sealed_frames():
    a, b, _, _ = _pair()
    auth = a.on_hello_reply(b.on_hello(a.initiator_hello()))
    b.on_auth(auth)
    assert a.established and b.established
    assert a.peer_id == 1 and b.peer_id == 0
    for i in range(5):  # counters advance in lockstep per direction
        payload = b"frame-%d " % i * 20
        assert b.open_frame(a.seal_frame(payload)) == payload
        assert a.open_frame(b.seal_frame(payload[::-1])) == payload[::-1]


def test_tampered_frame_rejected():
    a, b, _, _ = _pair()
    b.on_auth(a.on_hello_reply(b.on_hello(a.initiator_hello())))
    sealed = bytearray(a.seal_frame(b"payload"))
    sealed[3] ^= 0x40
    with pytest.raises(secure.HandshakeError, match="AEAD tag mismatch"):
        b.open_frame(bytes(sealed))


def test_replayed_frame_rejected():
    """Implicit counters: the same sealed frame cannot be accepted twice."""
    a, b, _, _ = _pair()
    b.on_auth(a.on_hello_reply(b.on_hello(a.initiator_hello())))
    sealed = a.seal_frame(b"once")
    assert b.open_frame(sealed) == b"once"
    with pytest.raises(secure.HandshakeError):
        b.open_frame(sealed)


def test_version_mismatch_rejected_with_clear_error():
    a, b, _, _ = _pair()
    hello = a.initiator_hello()
    hello["ver"] = "pbft-tpu/9.9.9"
    with pytest.raises(secure.HandshakeError, match="version mismatch"):
        b.on_hello(hello)


def test_plaintext_hello_rejected_by_secure_responder():
    _, b, _, _ = _pair()
    with pytest.raises(secure.HandshakeError, match="plaintext peer rejected"):
        b.on_hello(secure.plain_hello(0))


def test_wrong_identity_signature_rejected():
    """A peer signing with a key not in the table (an impostor dialing in)
    fails the handshake even with a valid DH exchange."""
    seeds = {0: bytes([1]) * 32, 1: bytes([2]) * 32}
    pubs = {i: ref.public_key(s) for i, s in seeds.items()}
    imposter = secure.SecureChannel(
        1, bytes([9]) * 32, pubs.get, initiator=False  # wrong seed for id 1
    )
    a = secure.SecureChannel(
        0, seeds[0], pubs.get, initiator=True, expected_peer=1
    )
    reply = imposter.on_hello(a.initiator_hello())
    with pytest.raises(secure.HandshakeError, match="bad handshake signature"):
        a.on_hello_reply(reply)


def test_malformed_hex_fields_are_protocol_errors():
    """Non-hex eph/sig must surface as HandshakeError (-> a reject frame),
    never a stray ValueError escaping the connection handler."""
    a, b, _, _ = _pair()
    hello = a.initiator_hello()
    hello["eph"] = "zz" * 32
    with pytest.raises(secure.HandshakeError, match="non-hex"):
        b.on_hello(hello)
    a2, b2, _, _ = _pair()
    reply = b2.on_hello(a2.initiator_hello())
    reply["sig"] = "q" * 128
    with pytest.raises(secure.HandshakeError, match="non-hex"):
        a2.on_hello_reply(reply)


def test_small_order_ephemeral_rejected():
    # Compressed identity point (y=1): clamped-scalar multiply collapses to
    # the identity; the handshake must refuse the null key contribution.
    assert secure.dh_shared(os.urandom(32), (1).to_bytes(32, "little")) is None


# -- C++ / Python byte-identity ----------------------------------------------


@needs_native
def test_keyed_blake2b_matches_hashlib():
    for key, data in [(b"k" * 32, b"abc"), (b"x" * 64, b""), (b"y" * 17, b"z" * 300)]:
        for size in (16, 32, 64):
            assert native.blake2b_keyed(key, data, size) == hashlib.blake2b(
                data, key=key, digest_size=size
            ).digest()


@needs_native
def test_dh_cross_implementation_agreement():
    for i in range(3):
        sa, sb = bytes([i + 1]) * 32, bytes([i + 7]) * 32
        assert native.dh_public(sa) == secure.dh_keypair(sa)[1]
        # Python side computes with C++'s public key and vice versa.
        shared_py = secure.dh_shared(sa, native.dh_public(sb))
        shared_c = native.dh_shared(sb, secure.dh_keypair(sa)[1])
        assert shared_py == shared_c is not None


@needs_native
def test_aead_cross_implementation_agreement():
    key = bytes(range(64))
    for ctr in (0, 7, 2**40):
        for pt in (b"", b"a", b"x" * 64, b"frame " * 100):
            assert native.aead_seal(key, ctr, pt) == secure.seal(key, ctr, pt)
            assert native.aead_open(key, ctr, secure.seal(key, ctr, pt)) == pt
            assert secure.open_sealed(key, ctr, native.aead_seal(key, ctr, pt)) == pt
            assert native.aead_open(key, ctr + 1, secure.seal(key, ctr, pt)) is None


# -- wire-level rejection against real daemons -------------------------------


def _read_frames(sock, timeout=10.0):
    """Collect complete frames until the peer closes; returns payloads."""
    sock.settimeout(timeout)
    buf = b""
    try:
        while True:
            chunk = sock.recv(65536)
            if not chunk:
                break
            buf += chunk
    except (socket.timeout, ConnectionError):
        pass
    out = []
    while len(buf) >= 4:
        n = int.from_bytes(buf[:4], "big")
        if len(buf) < 4 + n:
            break
        out.append(buf[4 : 4 + n])
        buf = buf[4 + n :]
    return out


def _frame(obj) -> bytes:
    payload = json.dumps(obj).encode()
    return len(payload).to_bytes(4, "big") + payload


@needs_native
@pytest.mark.parametrize("net_threads", [1, 2])
def test_version_mismatch_rejected_on_the_wire(net_threads):
    """A peer speaking a different protocol version gets a clean reject
    frame naming both versions, then the connection closes — on BOTH
    socket layers (the reference's protocol id /ackintosh/pbft/1.0.0 had no
    negotiation at all)."""
    from pbft_tpu.net import LocalCluster

    with LocalCluster(
        n=4, verifier="cpu", net_threads=net_threads, secure=True
    ) as cluster:
        ident = cluster.config.replicas[0]
        with socket.create_connection((ident.host, ident.port), timeout=5) as s:
            s.sendall(
                _frame(
                    {
                        "type": "hello",
                        "ver": "pbft-tpu/0.0.1",
                        "node": 1,
                        "eph": "00" * 32,
                    }
                )
            )
            frames = _read_frames(s)
        rejects = [json.loads(f) for f in frames]
        assert rejects and rejects[-1]["type"] == "reject"
        assert "version mismatch" in rejects[-1]["reason"]
        assert rejects[-1]["ver"] == secure.PROTOCOL_VERSION


@needs_native
@pytest.mark.parametrize("net_threads", [1, 2])
def test_plaintext_peer_rejected_by_secure_cluster(net_threads):
    """A plaintext (no-ephemeral) hello into a secure cluster is refused
    with a reject frame, not silently ignored."""
    from pbft_tpu.net import LocalCluster

    with LocalCluster(
        n=4, verifier="cpu", net_threads=net_threads, secure=True
    ) as cluster:
        ident = cluster.config.replicas[0]
        with socket.create_connection((ident.host, ident.port), timeout=5) as s:
            s.sendall(_frame(secure.plain_hello(1)))
            frames = _read_frames(s)
        rejects = [json.loads(f) for f in frames]
        assert rejects and rejects[-1]["type"] == "reject"
        assert "plaintext peer rejected" in rejects[-1]["reason"]


# -- end-to-end secure clusters ----------------------------------------------


@needs_native
def test_secure_cxx_cluster_commits():
    from pbft_tpu.net import LocalCluster, PbftClient

    with LocalCluster(n=4, verifier="cpu", secure=True) as cluster:
        client = PbftClient(cluster.config)
        try:
            req = client.request("over encrypted links")
            assert client.wait_result(req.timestamp, timeout=20) == "awesome!"
        finally:
            client.close()


@needs_native
def test_secure_discovered_cluster_commits():
    """Discovery + encryption together: peers found via multicast beacons
    still complete the signed-ephemeral handshake (identity pubkeys come
    from network.json, never from the unauthenticated beacon channel)."""
    from pbft_tpu.net import LocalCluster, PbftClient

    with LocalCluster(
        n=4,
        verifier="cpu",
        discovery=True,
        secure=True,
        vc_timeout_ms=1500,
    ) as cluster:
        client = PbftClient(cluster.config)
        try:
            assert (
                client.request_with_retry("discovered+encrypted", timeout=30)
                == "awesome!"
            )
        finally:
            client.close()


@needs_native
@pytest.mark.parametrize(
    "net_threads,one_connection",
    [(1, False), (2, True)],
    ids=["loop", "shards-one-connection"],
)
def test_secure_cluster_commits_concurrent_requests(net_threads, one_connection):
    """ALL links encrypted, three requests in flight at once, on both socket
    layers: sealed frames of concurrent rounds keep their order a link. The
    shard tier keeps a client's order a connection and not across
    connections (ROADMAP D2), so there the three share one."""
    from pbft_tpu.net import LocalCluster, PbftClient

    with LocalCluster(
        n=4, verifier="cpu", net_threads=net_threads, secure=True
    ) as cluster:
        client = PbftClient(cluster.config)
        try:
            ops = [f"secure-{i}" for i in range(3)]
            if one_connection:
                assert client.request_many(ops, window=3, timeout=25) == ["awesome!"] * 3
            else:
                reqs = [client.request(op) for op in ops]
                for r in reqs:
                    assert client.wait_result(r.timestamp, timeout=25) == "awesome!"
        finally:
            client.close()
