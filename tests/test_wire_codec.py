"""Wire-codec tests for ISSUE 3: binary-v2 byte parity across runtimes,
receive-side signable reuse parity for every message type, the
serialize-once broadcast invariant (counter-pinned, in-process and across
a real cluster), and mixed binary/JSON cluster interop including a forced
1.0.0 JSON-only peer.
"""

import json
import random
import time
from pathlib import Path

import pytest

from pbft_tpu import native
from pbft_tpu.consensus import messages as M

HAVE_NATIVE = native.available()

# Strings that stress the canonical-JSON escaping rules (quotes,
# backslashes, control chars, non-ASCII -> \uXXXX, astral plane ->
# surrogate pairs) — the binary codec carries them raw, but the signable
# templates must escape them exactly like json.dumps.
TRICKY_STRINGS = [
    "",
    "plain",
    'quote " inside',
    "back\\slash",
    "new\nline\ttab",
    "control \x01\x1f chars",
    "unicode é中文",
    "astral \U0001f600",
    '","sig":"',  # must not confuse the splice
    "sig",
]


def _rng():
    return random.Random(0xB2)


def _rand_str(rng):
    if rng.random() < 0.5:
        return rng.choice(TRICKY_STRINGS)
    return "".join(
        chr(rng.choice([rng.randrange(32, 127), rng.randrange(0x20, 0x2FFF)]))
        for _ in range(rng.randrange(0, 24))
    )


def _rand_i64(rng):
    return rng.choice(
        [0, 1, -1, rng.getrandbits(62), -rng.getrandbits(62), 2**63 - 1, -(2**63)]
    )


def _rand_hex(rng, n):
    return bytes(rng.getrandbits(8) for _ in range(n)).hex()


def _rand_request(rng):
    return M.ClientRequest(
        operation=_rand_str(rng), timestamp=_rand_i64(rng), client=_rand_str(rng)
    )


def _rand_hot(rng):
    """One randomized message of each binary-v2 type — pre-prepares in
    both the legacy batch-of-one layout (0x02) and the batched layout
    (0x06; sizes 0 and 2-5, size 1 must never take this form)."""
    req = _rand_request(rng)
    return [
        req,
        M.PrePrepare(
            view=_rand_i64(rng),
            seq=_rand_i64(rng),
            digest=_rand_hex(rng, 32),
            requests=(_rand_request(rng),),
            replica=_rand_i64(rng),
            sig=_rand_hex(rng, 64),
        ),
        M.PrePrepare(
            view=_rand_i64(rng),
            seq=_rand_i64(rng),
            digest=_rand_hex(rng, 32),
            requests=tuple(
                _rand_request(rng)
                for _ in range(rng.choice([0, 2, 3, 4, 5]))
            ),
            replica=_rand_i64(rng),
            sig=_rand_hex(rng, 64),
        ),
        M.Prepare(
            view=_rand_i64(rng),
            seq=_rand_i64(rng),
            digest=_rand_hex(rng, 32),
            replica=_rand_i64(rng),
            sig=_rand_hex(rng, 64),
        ),
        M.Commit(
            view=_rand_i64(rng),
            seq=_rand_i64(rng),
            digest=_rand_hex(rng, 32),
            replica=_rand_i64(rng),
            sig=_rand_hex(rng, 64),
        ),
        M.Checkpoint(
            seq=_rand_i64(rng),
            digest=_rand_hex(rng, 32),
            replica=_rand_i64(rng),
            sig=_rand_hex(rng, 64),
        ),
    ]


def _every_type():
    """One well-formed instance of EVERY wire message type."""
    req = M.ClientRequest(operation="op", timestamp=3, client="127.0.0.1:9000")
    cp = M.Checkpoint(seq=16, digest="ab" * 32, replica=1, sig="cd" * 64)
    pp = M.PrePrepare(
        view=0, seq=1, digest=req.digest(), requests=(req,), replica=0,
        sig="ee" * 64,
    )
    prep = M.Prepare(view=0, seq=1, digest=req.digest(), replica=2, sig="ff" * 64)
    return [
        req,
        M.ClientReply(
            view=0, timestamp=3, client="127.0.0.1:9000", replica=1,
            result='res "quoted"', sig="aa" * 64,
        ),
        pp,
        prep,
        M.Commit(view=0, seq=1, digest=req.digest(), replica=2, sig="ff" * 64),
        cp,
        M.ViewChange(
            new_view=1,
            last_stable_seq=16,
            checkpoint_proof=(cp.to_dict(),),
            prepared_proofs=(
                {"pre_prepare": pp.to_dict(), "prepares": [prep.to_dict()]},
            ),
            replica=2,
            sig="bb" * 64,
        ),
        M.NewView(
            new_view=1,
            view_changes=(cp.to_dict(),),  # structurally arbitrary evidence
            pre_prepares=(pp.to_dict(),),
            replica=1,
            sig="cc" * 64,
        ),
        M.StateRequest(seq=16, replica=3, sig="dd" * 64),
        M.StateResponse(
            seq=16, snapshot='snap with "sig":" inside', replica=0, sig="ee" * 64
        ),
    ]


# -- binary codec -------------------------------------------------------------


def test_binary_roundtrip_python():
    rng = _rng()
    for _ in range(50):
        for msg in _rand_hot(rng):
            b = M.to_binary(msg)
            assert b is not None, msg
            assert b[0] == M.WIRE_BINARY_MAGIC
            back = M.from_binary(b)
            assert back == msg
            assert M.decode_payload(b) == msg


def test_binary_not_offered_for_cold_types_or_bad_hex():
    for msg in _every_type():
        if type(msg) not in (
            M.ClientRequest, M.PrePrepare, M.Prepare, M.Commit, M.Checkpoint
        ):
            assert M.to_binary(msg) is None
    # digest/sig that are not fixed-width hex fall back to JSON
    assert M.to_binary(
        M.Prepare(view=0, seq=1, digest="xx", replica=0, sig="ff" * 64)
    ) is None
    assert M.to_binary(
        M.Prepare(view=0, seq=1, digest="ab" * 32, replica=0, sig="")
    ) is None


def test_binary_rejects_malformed():
    good = M.to_binary(M.Prepare(view=0, seq=1, digest="ab" * 32, replica=0, sig="cd" * 64))
    for bad in (
        good[:-1],                      # truncated
        good + b"\x00",                 # trailing bytes
        bytes([M.WIRE_BINARY_MAGIC, 0x7F]),  # unknown type
        b"",
        b"\xb2",
    ):
        with pytest.raises(ValueError):
            M.from_binary(bad)


def test_batched_pre_prepare_one_canonical_form():
    """Each batch has ONE canonical encoding: a count==1 binary batch
    (0x06) and a one-element JSON `requests` list are both rejected, by the
    reference codec and the native one — two admissible encodings of the same content would
    fork the signable digest across replicas."""
    req = M.ClientRequest(operation="op", timestamp=3, client="c:1")
    pp1 = M.PrePrepare(
        view=0, seq=1, digest=req.digest(), requests=(req,), replica=0,
        sig="ee" * 64,
    )
    b = M.to_binary(pp1)
    assert b[1] == 0x02  # batch of one MUST take the legacy layout
    # Forge the 0x06 count==1 form of the same content.
    forged = bytes([M.WIRE_BINARY_MAGIC, 0x06]) + b[2 : 2 + 8 + 8 + 32 + 8 + 64] + (
        (1).to_bytes(4, "big") + b[2 + 8 + 8 + 32 + 8 + 64 :]
    )
    with pytest.raises(ValueError):
        M.from_binary(forged)
    # JSON: one-element `requests` list is rejected too.
    d = pp1.to_dict()
    d["requests"] = [d.pop("request")]
    with pytest.raises(ValueError):
        M.Message.from_dict(d)
    if HAVE_NATIVE:
        assert native.message_from_binary(forged) is None
        # The C++ JSON parser rejects the one-element `requests` form too
        # (message_to_binary parses the payload first; None = rejected).
        payload = json.dumps(d, sort_keys=True, separators=(",", ":")).encode()
        assert native.message_to_binary(payload) is None


@pytest.mark.skipif(not HAVE_NATIVE, reason="native core not buildable")
def test_binary_cross_runtime_byte_parity_fuzz():
    """C++ and Python binary encodings must be byte-identical for
    randomized messages of every hot type, and the C++ decode must
    recover the identical canonical JSON and signable digest."""
    rng = _rng()
    for _ in range(40):
        for msg in _rand_hot(rng):
            payload = msg.canonical()
            pyb = M.to_binary(msg)
            cxxb = native.message_to_binary(payload)
            assert cxxb == pyb, type(msg).__name__
            decoded = native.message_from_binary(pyb)
            assert decoded is not None
            canon, digest = decoded
            assert canon == payload
            assert digest == msg.signable()


@pytest.mark.skipif(not HAVE_NATIVE, reason="native core not buildable")
def test_binary_malformed_rejected_by_native():
    good = M.to_binary(M.Prepare(view=0, seq=1, digest="ab" * 32, replica=0, sig="cd" * 64))
    for bad in (good[:-1], good + b"\x00", bytes([M.WIRE_BINARY_MAGIC, 0x7F])):
        assert native.message_from_binary(bad) is None


# -- MAC-vector frame variants (ISSUE 14) -------------------------------------


def _rand_lanes(rng):
    count = rng.randrange(1, 9)
    rids = rng.sample(range(64), count)
    return [
        (rid, bytes(rng.getrandbits(8) for _ in range(16)))
        for rid in sorted(rids)
    ]


def test_mac_frame_roundtrip_python_fuzz():
    rng = _rng()
    for _ in range(40):
        for msg in _rand_hot(rng):
            if isinstance(msg, M.ClientRequest):
                continue  # no sig field, no MAC form
            lanes = _rand_lanes(rng)
            frame = M.to_binary_mac(msg, lanes)
            assert frame is not None, type(msg).__name__
            assert frame[0] == M.WIRE_BINARY_MAGIC
            assert M.payload_is_mac_frame(frame)
            assert M.from_binary(frame) == msg
            assert M.decode_payload(frame) == msg
            for rid, tag in lanes:
                assert M.mac_frame_lane(frame, rid) == tag
            absent = next(r for r in range(70) if r not in dict(lanes))
            assert M.mac_frame_lane(frame, absent) is None


@pytest.mark.skipif(not HAVE_NATIVE, reason="native core not buildable")
def test_mac_frame_cross_runtime_byte_parity_fuzz():
    """C++ and Python MAC-vector frames must be byte-identical for
    randomized messages + lane sets, the C++ decode must recover the
    identical canonical JSON/signable, and lane extraction must agree."""
    rng = _rng()
    for _ in range(30):
        for msg in _rand_hot(rng):
            if isinstance(msg, M.ClientRequest):
                continue
            lanes = _rand_lanes(rng)
            pyb = M.to_binary_mac(msg, lanes)
            cxxb = native.message_to_binary_mac(msg.canonical(), lanes)
            assert cxxb == pyb, type(msg).__name__
            decoded = native.message_from_binary(pyb)
            assert decoded is not None
            canon, digest = decoded
            assert canon == msg.canonical()
            assert digest == msg.signable()
            for rid, tag in lanes:
                assert native.mac_frame_lane(pyb, rid) == tag
            absent = next(r for r in range(70) if r not in dict(lanes))
            assert native.mac_frame_lane(pyb, absent) is None


@pytest.mark.skipif(not HAVE_NATIVE, reason="native core not buildable")
def test_mac_frame_malformed_rejected_by_native():
    msg = M.Prepare(view=0, seq=1, digest="ab" * 32, replica=0, sig="cd" * 64)
    frame = M.to_binary_mac(msg, [(1, bytes(16)), (2, b"\x11" * 16)])
    assert native.message_from_binary(frame) is not None
    for bad in (
        frame[:-2],                    # truncated vector
        frame[:-1] + bytes([77]),      # count past the bound
        frame[:-1] + bytes([0]),       # zero-lane vector
    ):
        assert native.message_from_binary(bad) is None


# -- receive-side signable reuse ---------------------------------------------


def test_signable_from_payload_parity_every_type():
    """The splice derivation and the parse -> re-serialize derivation
    must agree for the canonical payload of EVERY message type (the
    nested-sig types exercise the fallback)."""
    for msg in _every_type():
        payload = msg.canonical()
        assert M.signable_from_payload(payload, msg) == msg.signable(), type(msg)


@pytest.mark.skipif(not HAVE_NATIVE, reason="native core not buildable")
def test_signable_from_payload_parity_native():
    for msg in _every_type():
        payload = msg.canonical()
        got = native.signable_from_payload(payload)
        assert got == msg.signable(), type(msg).__name__
    # and over the binary encoding, where it has one
    for msg in _every_type():
        b = M.to_binary(msg)
        if b is not None:
            assert native.signable_from_payload(b) == msg.signable()


def test_signable_fast_templates_match_generic():
    """The fixed signable templates must render the exact bytes of the
    generic sorted-keys derivation, including escaping."""
    rng = _rng()
    for _ in range(50):
        for msg in _rand_hot(rng):
            d = msg.to_dict()
            d.pop("sig", None)
            generic = M.blake2b_256(
                json.dumps(d, sort_keys=True, separators=(",", ":")).encode()
            )
            assert msg.signable() == generic, type(msg).__name__


def test_splice_fails_closed_on_tamper():
    """Bytes tampered outside the sig field must change the derived
    digest (the signature check then rejects)."""
    msg = M.Prepare(view=5, seq=9, digest="ab" * 32, replica=2, sig="cd" * 64)
    payload = bytearray(msg.canonical())
    i = payload.index(b'"seq":9') + 6
    payload[i:i + 1] = b"8"
    tampered = bytes(payload)
    assert M.signable_from_payload(tampered, msg) != msg.signable()


# -- serialize-once fan-out ---------------------------------------------------


def _last_metrics_line(tmpdir: Path, i: int) -> dict:
    log = (tmpdir / f"replica-{i}.log").read_text(errors="ignore")
    log = log[: log.rfind("\n") + 1]  # the daemon may be mid-write of its newest line
    lines = [ln for ln in log.splitlines() if '"broadcast_encodes"' in ln]
    assert lines, f"replica {i} printed no metrics lines:\n{log[-2000:]}"
    start = lines[-1].index("{")
    return json.loads(lines[-1][start:])


@pytest.mark.skipif(not HAVE_NATIVE, reason="native core not buildable")
@pytest.mark.parametrize("net_threads", [1, 2])
def test_serialize_once_invariant_across_real_cluster(net_threads):
    """Counter-pinned serialize-once invariant on a live cluster, on both
    socket layers: every replica's broadcast fan-out encodes each broadcast
    exactly once (encodes == broadcasts, not broadcasts x peers)."""
    from pbft_tpu.net import LocalCluster, PbftClient

    with LocalCluster(
        n=4, verifier="cpu", metrics_every=1, net_threads=net_threads
    ) as cluster:
        client = PbftClient(cluster.config)
        tmpdir = Path(cluster.tmpdir.name)
        # A first request brings every link up; the counters are read from
        # the tick after it, so that what is held below is the steady state.
        # (A broadcast issued while a link is still negotiating its codec
        # legitimately encodes twice, JSON now and binary after the
        # hello-ack, and on a loaded host that window outlasts any fixed
        # number of broadcasts: 11 of 12 once under six test workers.)
        r = client.request("links-up")
        assert client.wait_result(r.timestamp, timeout=30) is not None
        time.sleep(1.6)  # one more metrics tick
        before = [_last_metrics_line(tmpdir, i) for i in range(4)]
        for k in range(6):
            r = client.request(f"op-{k}")
            assert client.wait_result(r.timestamp, timeout=30) is not None
        client.close()
        time.sleep(1.6)  # one more metrics tick
        for i in range(4):
            m = _last_metrics_line(tmpdir, i)
            broadcasts = m["broadcasts"] - before[i]["broadcasts"]
            encodes = m["broadcast_encodes"] - before[i]["broadcast_encodes"]
            assert broadcasts > 0, m
            # Encodes track broadcasts, not broadcasts x peers: per-peer
            # re-encoding would sit at ~3x broadcasts (n=4). The allowance
            # of 4 is the one the whole-run form of this check had.
            assert broadcasts <= encodes, (before[i], m)
            assert encodes <= broadcasts + 4, (before[i], m)


# -- mixed binary/JSON cluster interop ----------------------------------------


@pytest.mark.skipif(not HAVE_NATIVE, reason="native core not buildable")
@pytest.mark.parametrize("net_threads", [1, 2])
def test_mixed_codec_cluster_interop(net_threads):
    """One cluster holding two binary-v2 replicas and two JSON-only peers
    forced to the legacy 1.0.0 hello, on both socket layers — requests
    must commit, the binary speakers must actually use binary frames, and
    the forced peers must never encode one."""
    from pbft_tpu.net import LocalCluster, PbftClient

    json_env = {"PBFT_WIRE_CODEC": "json"}
    with LocalCluster(
        n=4,
        verifier="cpu",
        metrics_every=1,
        net_threads=net_threads,
        extra_env=[None, None, json_env, json_env],
    ) as cluster:
        client = PbftClient(cluster.config)
        for k in range(6):
            r = client.request(f"mixed-{k}")
            assert client.wait_result(r.timestamp, timeout=30) is not None
        client.close()
        time.sleep(1.6)
        tmpdir = Path(cluster.tmpdir.name)
        # replica 1: binary-v2 — spoke binary to its bin2 peer and JSON to
        # the forced-legacy ones, so its hot broadcasts were encoded in BOTH
        # codecs (pbftd counts encodes, not frames a codec).
        m1 = _last_metrics_line(tmpdir, 1)
        assert m1["broadcast_encodes"] > m1["broadcasts"], m1
        # replica 3: forced JSON-only — one codec, so never a second encode
        # of a broadcast (the startup allowance of the invariant above).
        m3 = _last_metrics_line(tmpdir, 3)
        assert m3["broadcasts"] <= m3["broadcast_encodes"] <= m3["broadcasts"] + 4, m3
        # the serialize-once invariant holds for everyone even with two
        # codecs live: lazy per-codec encoding still caps encodes at the
        # codec count, and equality holds per single-codec fan-out set.
        for i in range(4):
            m = _last_metrics_line(tmpdir, i)
            assert 0 < m["broadcast_encodes"] <= 2 * m["broadcasts"], (i, m)
