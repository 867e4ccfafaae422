"""Transport edge cases (VERDICT r2 weak #4): the raw-JSON client path must
line-buffer correctly (requests split across reads) and must BOUND its
buffering (oversized lines drop the connection instead of growing without
limit)."""

import socket
import time

import pytest

from pbft_tpu import native
from pbft_tpu.consensus.messages import ClientRequest


@pytest.mark.skipif(not native.available(), reason="native core not built")
@pytest.mark.parametrize("net_threads", [1, 2])
def test_cxx_client_line_reassembled_across_reads(net_threads):
    """A request arriving in several small TCP chunks must still parse: the
    loop's line buffer and the shard tier's own each hold a partial line
    across reads, and the request commits like one sent whole."""
    from pbft_tpu.net import LocalCluster, PbftClient

    with LocalCluster(n=4, verifier="cpu", net_threads=net_threads) as cluster:
        client = PbftClient(cluster.config)
        try:
            req = ClientRequest(operation="chunked", timestamp=1, client=client.address)
            payload = req.canonical() + b"\n"
            ident = cluster.config.replicas[0]
            with socket.create_connection((ident.host, ident.port), timeout=5) as s:
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                for i in range(0, len(payload), 7):  # drip-feed 7 bytes at a time
                    s.sendall(payload[i : i + 7])
                    time.sleep(0.01)
            assert client.wait_result(1, timeout=15) == "awesome!"
        finally:
            client.close()


@pytest.mark.skipif(not native.available(), reason="native core not built")
def test_cxx_unroutable_reply_address_does_not_stall():
    """The reply address is untrusted client input: requests advertising a
    dead endpoint must not stall the replica event loop (dials are
    nonblocking + deadline-bounded), and honest clients keep committing
    throughout."""
    import json as _json

    from pbft_tpu.net import LocalCluster, PbftClient

    with LocalCluster(n=4, verifier="cpu") as cluster:
        ident = cluster.config.replicas[0]
        # A batch of requests whose replies dial a port nobody listens on.
        for i in range(8):
            req = {
                "type": "client-request",
                "operation": f"void-{i}",
                "timestamp": i + 1,
                "client": "127.0.0.1:1",  # closed port: dial fails
            }
            with socket.create_connection((ident.host, ident.port), timeout=5) as s:
                s.sendall(_json.dumps(req).encode() + b"\n")
        # An honest client interleaved with the garbage must still commit
        # promptly (the old blocking dial would serialize failed dials
        # inside the event loop).
        client = PbftClient(cluster.config)
        try:
            assert client.request_with_retry("honest", timeout=20) == "awesome!"
        finally:
            client.close()


@pytest.mark.skipif(not native.available(), reason="native core not built")
def test_cxx_dialback_socket_input_discarded():
    """A malicious reply listener writing requests back on the dial-back
    connection gains no request-injection channel. (End-to-end property:
    in the common path the one-shot conn closes at flush before reading;
    the process_buffer discard guard covers the partial-flush window —
    either way nothing the evil endpoint sends may execute.)"""
    import json as _json
    import threading

    from pbft_tpu.net import LocalCluster, PbftClient

    injected = {"type": "client-request", "operation": "injected",
                "timestamp": 999, "client": "127.0.0.1:1"}
    got_dial = threading.Event()

    # Evil "client listener": on every dial-back, write a request upstream.
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(8)
    evil_port = srv.getsockname()[1]

    def evil():
        srv.settimeout(10)
        try:
            while True:
                conn, _ = srv.accept()
                got_dial.set()
                try:
                    conn.sendall(_json.dumps(injected).encode() + b"\n")
                finally:
                    conn.close()
        except (socket.timeout, OSError):
            pass

    t = threading.Thread(target=evil, daemon=True)
    t.start()
    try:
        with LocalCluster(n=4, verifier="cpu", metrics_every=1) as cluster:
            ident = cluster.config.replicas[0]
            req = {
                "type": "client-request",
                "operation": "bait",
                "timestamp": 1,
                "client": f"127.0.0.1:{evil_port}",
            }
            with socket.create_connection((ident.host, ident.port), timeout=5) as s:
                s.sendall(_json.dumps(req).encode() + b"\n")
            assert got_dial.wait(15), "no dial-back ever arrived"
            # Give the injected request time to (wrongly) commit, then
            # check no replica executed a second request.
            time.sleep(2.5)
            import re

            for i in range(4):
                log = (cluster.tmpdir and
                       (__import__("pathlib").Path(cluster.tmpdir.name)
                        / f"replica-{i}.log").read_text(errors="replace"))
                ex = re.findall(r'"executed_upto":\s*(\d+)', log)
                assert ex and int(ex[-1]) <= 1, (
                    f"replica {i} executed injected request: {ex[-1]}"
                )
    finally:
        srv.close()


@pytest.mark.skipif(not native.available(), reason="native core not built")
def test_cxx_oversized_client_line_dropped():
    """Same contract for pbftd: oversized raw-JSON input drops the
    connection, the daemon stays up and still commits a real request."""
    from pbft_tpu.net import LocalCluster, PbftClient

    with LocalCluster(n=4, verifier="cpu") as cluster:
        ident = cluster.config.replicas[0]
        with socket.create_connection((ident.host, ident.port), timeout=5) as s:
            # The daemon closes mid-send once its buffer limit trips; the
            # in-flight tail then surfaces as ECONNRESET/EPIPE on our side —
            # equivalent to the clean-EOF case for this test's purposes.
            closed = False
            try:
                s.sendall(b"{" + b"y" * ((1 << 20) + 4096))
                s.settimeout(10)
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    try:
                        if s.recv(4096) == b"":
                            closed = True
                            break
                    except socket.timeout:
                        break
            except OSError:
                closed = True
            assert closed, "pbftd kept the oversized connection open"
        client = PbftClient(cluster.config)
        try:
            req = client.request("after-flood")
            assert client.wait_result(req.timestamp, timeout=15) == "awesome!"
        finally:
            client.close()
