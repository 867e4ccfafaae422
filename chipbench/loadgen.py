#!/usr/bin/env python3
"""The benchmark's load generator: one quiet process between the harness
and the gateway.

It is started by ``harness.py`` with a spec file and speaks the gateway's
client protocol itself (one raw-JSON request line in, every replica's
signed reply line back on the same connection), one connection per client
identity; a mix may ask for several such processes (``processes``), each
with its share of the identities, so that no one process's checking of a
wave of replies becomes part of the reply time. What a traffic mix *is* lives in ``traffic/<name>.json`` and the
kind it names in ``traffic_kinds/<kind>.py``; this file only sends what is
due, keeps the f+1 quorum rule, and stamps three instants per request on
``time.monotonic()`` (one clock for every process of the host): when the
request was DUE, when it was sent, and when the reply that completed its
quorum had been checked.

The quorum rule (PBFT section 4.1): a request is complete when f+1
distinct replicas sent matching committed replies (or 2f+1 matching in one
view when some are tentative), each carrying a valid signature of the
replica it names. Every reply is decoded and checked once, when it
arrives, and never again; replies for a request that is already complete
are dropped unread. Stamps stay in memory and are written after the drain;
the garbage collector is frozen and off while load runs.
"""

from __future__ import annotations

import argparse
import gc
import heapq
import importlib
import json
import os
import random
import selectors
import socket
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from reference.state_machine import execute, reply_signable  # noqa: E402


def traffic_kind(name: str):
    """The module ``traffic_kinds/<name>.py``, found by name."""
    return importlib.import_module(f"traffic_kinds.{name}")


class Generator:
    def __init__(self, spec: dict, verify):
        self.spec = spec
        self.verify = verify
        self.f = int(spec["f"])
        self.n = int(spec["n"])
        self.pubkeys = [bytes.fromhex(p) for p in spec["pubkeys"]]
        self.seed = int(spec["seed"])
        self.t1 = float(spec["t1"])
        self.start_at = float(spec["start_at"])
        self.deadline = self.t1 + float(spec["drain_s"])
        traffic = spec["traffic"]
        rng = random.Random(self.seed)
        arrivals, self.resend = traffic_kind(traffic["kind"]).build(
            traffic, rng, self.t1 - self.start_at
        )
        # One of ``shards`` generator processes: every process builds the
        # same arrivals from the seed and keeps its own identities'.
        shard, shards = int(spec.get("shard", 0)), int(spec.get("shards", 1))
        self.heap = [
            (self.start_at + off, k, ident)
            for k, (off, ident) in enumerate(arrivals)
            if ident % shards == shard
        ]
        heapq.heapify(self.heap)
        self.idents = int(traffic["identities"])
        self.sample_every = int(spec.get("sample_every", 64))
        self.tokens = [f"gw/cb{self.seed:x}-{i}" for i in range(self.idents)]
        self.token_ix = {t: i for i, t in enumerate(self.tokens)}
        self.next_ts = [1] * self.idents
        # (ident, ts) -> [record index, votes {replica: (result, view,
        # tentative)}, kept reply objects or None]
        self.open: dict = {}
        self.rec_ident: list = []
        self.rec_ts: list = []
        self.rec_due: list = []
        self.rec_sent: list = []
        self.rec_done: list = []
        self.samples: list = []
        self.rejected = 0
        self.bad_signature = 0
        self.wrong_result = 0
        self.stray = 0
        self.op_salt = "%08x" % (self.seed & 0xFFFFFFFF)

    # -- wire ----------------------------------------------------------------

    def connect(self) -> None:
        host, port = self.spec["gateway"].rsplit(":", 1)
        self.sel = selectors.DefaultSelector()
        self.socks = []
        self.bufs = [b""] * self.idents
        mine = {ident for _, _, ident in self.heap}
        for i in range(self.idents):
            s = None
            if i in mine:
                s = socket.create_connection((host, int(port)), timeout=10)
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                s.settimeout(10)
                self.sel.register(s, selectors.EVENT_READ, i)
            self.socks.append(s)

    def operation(self, ident: int, ts: int) -> str:
        """A short distinct string per request, from the seed."""
        return f"{self.op_salt}{ident:02x}{ts:08x}"

    def send(self, due: float, ident: int) -> None:
        ts = self.next_ts[ident]
        self.next_ts[ident] = ts + 1
        line = (
            '{"client":"%s","operation":"%s","timestamp":%d,"type":"client-request"}\n'
            % (self.tokens[ident], self.operation(ident, ts), ts)
        ).encode()
        keep = (
            [] if (ts * 2654435761 + ident * 40503 + self.seed) % self.sample_every == 0
            else None
        )
        self.open[(ident, ts)] = [len(self.rec_ts), {}, keep]
        self.rec_ident.append(ident)
        self.rec_ts.append(ts)
        self.rec_due.append(due)
        self.rec_done.append(None)
        self.socks[ident].sendall(line)
        self.rec_sent.append(time.monotonic())

    def on_line(self, line: bytes) -> None:
        try:
            obj = json.loads(line)
        except (ValueError, UnicodeDecodeError):
            self.stray += 1
            return
        if not isinstance(obj, dict):
            self.stray += 1
            return
        ident = self.token_ix.get(obj.get("client"))
        ts = obj.get("timestamp")
        state = self.open.get((ident, ts))
        if state is None:
            return  # complete already (a later replica's copy), or not ours
        if obj.get("type") == "overloaded":
            self.rejected += 1
            del self.open[(ident, ts)]
            return
        rid = obj.get("replica")
        votes = state[1]
        if not isinstance(rid, int) or not 0 <= rid < self.n or rid in votes:
            return
        try:
            sig = bytes.fromhex(obj["sig"])
            ok = len(sig) == 64 and self.verify(
                self.pubkeys[rid], reply_signable(obj), sig
            )
        except (KeyError, TypeError, ValueError):
            ok = False
        if not ok:
            self.bad_signature += 1
            return
        vote = (obj["result"], obj["view"], 1 if obj.get("tentative") else 0)
        votes[rid] = vote
        if state[2] is not None:
            state[2].append(obj)
        same_view = committed = 0
        for result, view, tentative in votes.values():
            if result == vote[0]:
                same_view += view == vote[1]
                committed += not tentative
        if committed >= self.f + 1 or same_view >= 2 * self.f + 1:
            now = time.monotonic()
            self.rec_done[state[0]] = now
            if vote[0] != execute(self.operation(ident, ts)):
                self.wrong_result += 1
            if state[2] is not None:
                self.samples.append(
                    {"ident": ident, "ts": ts, "client": self.tokens[ident],
                     "operation": self.operation(ident, ts), "replies": state[2]}
                )
            del self.open[(ident, ts)]
            if self.resend and now < self.t1:
                self.send(now, ident)

    # -- the loop ------------------------------------------------------------

    def run(self) -> None:
        heap = self.heap
        while True:
            now = time.monotonic()
            while heap and heap[0][0] <= now:
                due, _, ident = heapq.heappop(heap)
                if due < self.t1:
                    self.send(due, ident)
            if now >= self.t1 and (not self.open or now >= self.deadline):
                return
            wait = 0.25
            if heap:
                wait = min(wait, max(0.0, heap[0][0] - now))
            for key, _ in self.sel.select(wait):
                ident = key.data
                chunk = key.fileobj.recv(1 << 18)
                if not chunk:
                    raise ConnectionError(f"gateway closed identity {ident}")
                data = self.bufs[ident] + chunk
                lines = data.split(b"\n")
                self.bufs[ident] = lines.pop()
                for line in lines:
                    if line:
                        self.on_line(line)

    def result(self) -> dict:
        times = os.times()
        return {
            "ident": self.rec_ident,
            "ts": self.rec_ts,
            "due": self.rec_due,
            "sent": self.rec_sent,
            "done": self.rec_done,
            "rejected": self.rejected,
            "bad_signature": self.bad_signature,
            "wrong_result": self.wrong_result,
            "stray_lines": self.stray,
            "unfinished": len(self.open),
            "samples": self.samples,
            "cpu_s": times.user + times.system,
        }

    def close(self) -> None:
        for s in self.socks:
            if s is not None:
                s.close()
        self.sel.close()


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--spec", required=True)
    args = parser.parse_args()
    spec = json.loads(Path(args.spec).read_text())
    sys.path.insert(0, spec["repo"])
    from pbft_tpu import native  # the client's own signature check

    gen = Generator(spec, native.verify)
    gen.connect()
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        gen.run()
    finally:
        out = gen.result()
        gen.close()
        tmp = Path(spec["out"] + ".tmp")
        tmp.write_text(json.dumps(out, separators=(",", ":")))
        tmp.rename(spec["out"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
