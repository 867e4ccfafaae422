#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path starts on the chip.

Drives client -> gateway -> four pbftd replicas -> verifyd -> TPU ->
committed reply once, through the entry points users have, and checks
what comes out by the repo's own means. Two stages, both against ONE
``scripts/verifyd.py --backend jax`` child, the only process that touches
JAX (this parent never initializes a backend: a chip belongs to one
process):

1. Device at full width. Every rung of the pad ladder (16, 64, 256, 1024,
   4096) warmed, then driven over the 128-byte-triple protocol: one
   window of exactly each rung size, three 4096 windows at once, and
   four connections x 1,024 items that the dispatcher coalesces into the
   4096 shape. Items are signed from ``--seed``; every window carries a
   planted reject of every class the kernel decides, and on a mesh of
   several chips a window that fills the shape it runs at (1,024 and 4,096
   slots on the 2x2 host) carries one in EVERY chip's rows. Every verdict
   is compared, item by item, with ``pbft_tpu.crypto.ref.verify`` (RFC 8032).
2. The deployment (ROADMAP B0's durable default, the shape of
   benchmarks/wal_r15.jsonl "scale f=1"): n=4, signature mode, WAL with
   fsync, batch_max_items=32, batch_flush_us=2000, one gateway process,
   8 GatewayClient identities x window 8, 3,840 requests, every pbftd
   started with ``--verifier <verifyd address>``. Every request needs f+1
   matching signed replies; the replicas must agree on ``executed`` and
   ``chain_digest``, report zero verify fallbacks and zero fired verify
   deadlines, and the items verifyd's ENGINE verified must equal the sum
   of the items the replicas sent for verification.

Any failed check, a child that exits early, a service that is not
``ready`` on platform ``tpu``, or a child still alive at the end is a
non-zero exit; with no TPU it fails fast and prints no result. The last
line of stdout is ``{"ok": true, "device": {...}}`` with the device as
JAX reports it (through verifyd's status). Compile seconds and device
facts above it are set-up facts, not performance. Beside each shape's
launch cost the log says where its long multiply chains run (``vmem`` /
``xla``, as the engine read it from the executable's own HLO), and a shape
whose executable did not take the chains ``ed25519.chains_for`` gives a TPU
shape of its rows a chip (``vmem`` from 256 rows) fails the run.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import random
import re
import shutil
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

from pbft_tpu import native  # noqa: E402
from pbft_tpu.crypto import ref  # noqa: E402
from pbft_tpu.net.gateway import GatewayClient  # noqa: E402
from pbft_tpu.net.launcher import LocalCluster  # noqa: E402
from pbft_tpu.net.verify_service import (  # noqa: E402
    probe_status_json,
    serving_table_text,
    spawn_verifyd,
    stop_child,
    wait_for_tpu_service,
)

LADDER = (16, 64, 256, 1024, 4096)
REQUESTS, CLIENTS, WINDOW = 3840, 8, 8
WARM_BUDGET_S = 1000.0  # of the 1200 s the whole run may take
OUT_DIR = REPO / "chiprun_out" / "chip_smoke"


class SmokeFailure(Exception):
    """A check did not hold. Never caught on the way out of main()."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# -- stage 1: the device, item by item against the oracle --------------------


class Oracle:
    """``ref.verify`` memoized by triple: windows re-use one signed pool,
    and the oracle is pure, so each distinct triple is decided once."""

    def __init__(self):
        self._seen: dict = {}

    def __call__(self, item) -> bool:
        verdict = self._seen.get(item)
        if verdict is None:
            verdict = self._seen[item] = ref.verify(*item)
        return verdict


def signed_pool(rng: random.Random, n: int) -> list:
    """n distinct valid (pub, msg, sig) triples, keys and messages from
    the seeded stream, signed by the native core (RFC 8032 signing is
    deterministic; the oracle re-decides every one of them anyway)."""
    pool = []
    for _ in range(n):
        seed, msg = rng.randbytes(32), rng.randbytes(32)
        pool.append((native.public_key(seed), msg, native.sign(seed, msg)))
    return pool


def _le32(v: int) -> bytes:
    return v.to_bytes(32, "little")


def planted(rng: random.Random, base) -> dict:
    """One item of every class the kernel decides, built from the valid
    triple ``base``: name -> (item, expected verdict). The expectation is
    only the generator's self-check; the oracle is the judge."""
    pub, msg, sig = base
    r_bytes, s = sig[:32], int.from_bytes(sig[32:], "little")
    k = rng.randrange(64)
    flipped = sig[:k] + bytes([sig[k] ^ (1 << rng.randrange(8))]) + sig[k + 1 :]
    # A y below p whose x^2 is a non-residue: no point to decompress.
    while True:
        y = rng.randrange(ref.P)
        if ref.point_decompress(_le32(y)) is None:
            off_curve = _le32(y)
            break
    # A = identity: [S]B == R + [h]O holds for (R = [r]B, S = r) whatever
    # the message says. Encoded canonically (y = 1) it is ACCEPTED by the
    # cofactorless equation; the same point encoded as y = p + 1, or with
    # the sign bit set on x = 0, must be refused on the encoding alone.
    r = rng.randrange(1, ref.L)
    id_sig = ref.point_compress(ref.scalar_mult(r, ref.BASE)) + _le32(r)
    # R = identity encoded as y = p + 1 with S = h*a: the group equation
    # holds, only comparing canonical bytes refuses it.
    seed = rng.randbytes(32)
    a, _ = ref.secret_expand(seed)
    a_pub = ref.public_key(seed)
    r_noncanon = _le32(ref.P + 1)
    h = ref._h512_int(r_noncanon, a_pub, msg) % ref.L
    return {
        "flipped signature byte": ((pub, msg, flipped), False),
        "S >= L": ((pub, msg, r_bytes + _le32(s + ref.L)), False),
        "public key off the curve": ((off_curve, msg, sig), False),
        "non-canonical y in the public key": (
            (_le32(ref.P + 1), msg, id_sig),
            False,
        ),
        "x = 0 with the sign bit": ((_le32(1 | 1 << 255), msg, id_sig), False),
        "non-canonical y in R": (
            (a_pub, msg, r_noncanon + _le32(h * a % ref.L)),
            False,
        ),
        "wrong message": ((pub, bytes([msg[0] ^ 1]) + msg[1:], sig), False),
        "identity key, canonical (control)": ((_le32(1), msg, id_sig), True),
    }


N_CLASSES = 8  # what planted() makes: seven the oracle rejects and its control


def make_window(
    rng: random.Random, pool: list, size: int, oracle: Oracle, shards: int = 1
):
    """``size`` items drawn from the pool with one of every planted class
    at seeded positions in EACH of ``shards`` equal parts of the window:
    where the window fills the shape it runs at, a part is the rows ONE chip
    of the mesh decides. Returns (items, {position: class name})."""
    items = rng.sample(pool, size)
    rows = size // shards
    classes = {}
    for shard in range(shards):
        first = shard * rows
        plants = planted(rng, items[first])
        for (name, (item, expect)), pos in zip(
            plants.items(), rng.sample(range(first, first + rows), len(plants))
        ):
            check(
                oracle(item) is expect,
                f"generator: oracle says {not expect} for planted {name!r}",
            )
            items[pos] = item
            classes[pos] = name
    return items, classes


def verify_over_socket(target: str, items: list, timeout: float = 120.0) -> list:
    """One request of the 128-byte-triple protocol; no fallback of any kind."""
    host, port = target.rsplit(":", 1)
    with socket.create_connection((host, int(port)), timeout=timeout) as sock:
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        sock.sendall(
            len(items).to_bytes(4, "big") + b"".join(p + m + s for p, m, s in items)
        )
        with sock.makefile("rb") as fh:
            out = fh.read(len(items))
    check(len(out) == len(items), f"service closed after {len(out)} verdicts")
    return [bool(b) for b in out]


def compare(label: str, items, classes, verdicts, oracle: Oracle) -> int:
    """Item-by-item agreement with the oracle; returns the reject count."""
    for i, (item, got) in enumerate(zip(items, verdicts)):
        want = oracle(item)
        check(
            got == want,
            f"{label}: item {i} ({classes.get(i, 'valid signature')}): "
            f"device says {got}, RFC 8032 oracle says {want}",
        )
    return verdicts.count(False)


def ready_status(target: str) -> dict:
    """verifyd's status JSON, which must still say ``ready`` on a TPU."""
    status = probe_status_json(target)
    check(status is not None, f"verify service at {target} stopped answering")
    check(
        status["state"] == "ready" and status["platform"] == "tpu",
        f"service no longer ready on a TPU: {status}",
    )
    return status


def device_stage(target: str, seed: int, ladder=LADDER, trace_path=None) -> None:
    rng = random.Random(seed)
    oracle = Oracle()
    top = ladder[-1]
    t0 = time.monotonic()
    pool = signed_pool(rng, top)
    check(all(oracle(item) for item in pool), "oracle refused a signed pool item")
    log(f"{top} items signed from seed {seed} and decided by the oracle "
        f"in {time.monotonic() - t0:.1f}s")
    before = ready_status(target)
    # Which executable serves which window on THIS mesh: the engine measured
    # every shape at warm-up (a window of exactly `fit` items runs at `runs`).
    log("serving table (smallest shape that fits→shape run): "
        + serving_table_text(before["warm_stats"]["serving_table"]))
    log("chunk plan (items→shapes run, where several launches cost less): "
        + (serving_table_text(before["warm_stats"]["chunk_plan"]) or "none"))

    # One window of exactly each rung size, alone on the wire. A window that
    # runs at its own size fills that executable, so item i is row i and a
    # quarter of the window is ONE chip's rows on a mesh of four: there every
    # class is planted in every chip's rows (a probe's few items sit together
    # in one chip's), wherever a chip's rows have room for them.
    chips = before["devices"]
    serves = before["warm_stats"]["serving_table"]
    shards_of = {
        size: chips if serves.get(str(size)) == size and size // chips >= N_CLASSES else 1
        for size in ladder
    }
    for size in ladder:
        shards = shards_of[size]
        items, classes = make_window(rng, pool, size, oracle, shards)
        verdicts = verify_over_socket(target, items)
        rejected = compare(f"rung {size}", items, classes, verdicts, oracle)
        log(f"rung {size}: {size}/{size} verdicts agree with the oracle "
            f"({rejected} rejected, every planted class among them"
            + (f", in each of the {shards} chips' rows: {size // shards} rows a chip)"
               if shards > 1 else ")"))
    every_chip = [size for size in ladder if shards_of[size] > 1]
    check(
        chips == 1 or every_chip,
        f"a mesh of {chips} chips and no window of {ladder} fills a shape "
        f"with {N_CLASSES} rows a chip: serving table {serves}",
    )

    # Three top-rung windows at once fill both launch slots and the
    # dispatcher's hand (inflight=2 + the window it holds while it waits
    # for a slot); the four 1,024-item requests sent next then queue
    # TOGETHER and leave as one merged window of the 4096 shape.
    part = top // 4
    blockers = [make_window(rng, pool, top, oracle, shards_of[top]) for _ in range(3)]
    parts = [make_window(rng, pool, part, oracle) for _ in range(4)]
    results: dict = {}

    def send(key, items) -> None:
        results[key] = verify_over_socket(target, items)

    threads = [
        threading.Thread(target=send, args=(("blocker", i), w[0]))
        for i, w in enumerate(blockers)
    ]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 60
    while ready_status(target)["requests"] < before["requests"] + len(ladder) + 3:
        check(time.monotonic() < deadline, "blocker windows never queued")
        time.sleep(0.002)
    more = [
        threading.Thread(target=send, args=(("part", i), w[0]))
        for i, w in enumerate(parts)
    ]
    for t in more:
        t.start()
    for t in threads + more:
        t.join(300)
        check(not t.is_alive(), "a window never came back")
    for kind, windows in (("blocker", blockers), ("part", parts)):
        for i, (items, classes) in enumerate(windows):
            check((kind, i) in results, f"{kind} window {i} failed on the wire")
            compare(f"{kind} window {i} ({len(items)} items)", items, classes,
                    results[(kind, i)], oracle)
    log(f"3 x {top} at once and 4 connections x {part}: every verdict agrees "
        "with the oracle")

    after = ready_status(target)
    sent = sum(ladder) + 3 * top + 4 * part
    check(
        after["engine_items"] - before["engine_items"] == sent
        and after["fallback_items"] == before["fallback_items"],
        f"engine verified {after['engine_items'] - before['engine_items']} of "
        f"{sent} items sent (fallback items "
        f"{after['fallback_items'] - before['fallback_items']})",
    )
    if trace_path is not None:
        lines = [
            e
            for e in map(json.loads, Path(trace_path).read_text().splitlines())
            if e.get("ev") == "verify_batch"
        ]
        # The windows planted a chip at a time did run as ONE executable of
        # their own size over every chip: the engine's line says so.
        for size in every_chip:
            want = {"size": size, "rung": size, "chunks": 1, "devices": chips,
                    "rows_per_chip": size // chips}
            check(
                any(all(e.get(k) == v for k, v in want.items()) for e in lines),
                f"no launch line says {want}: the {size}-item window did not "
                f"run as {size // chips} rows on each of {chips} chips",
            )
        # What the dispatcher really merged, from its own per-launch trace.
        merged = [(e["requests"], e["size"]) for e in lines if e["requests"] > 1]
        check(
            any(size > part for _, size in merged),
            f"no coalesced window reached the {top} shape: merged={merged}",
        )
        log(f"coalesced launches (requests, items): {merged}")
    log(f"device stage: {sent} verdicts, all through the engine, "
        f"{after['promoted_launches'] - before['promoted_launches']} launches "
        "on a larger shape than the smallest fit, "
        f"{after['split_launches'] - before['split_launches']} run as chunks; "
        f"every class planted in every chip's rows at {every_chip or 'no'} slots "
        f"over {chips} chip(s); launches by rows a chip "
        f"{after.get('launches_by_rows_per_chip')}; "
        f"{after.get('windows_cut_full', 0) - before.get('windows_cut_full', 0)} windows cut at "
        f"the largest window with requests left queued (at most "
        f"{after.get('overflow_items_max', 0)} items behind a cut)")


# -- stage 2: the deployment ---------------------------------------------------


def _fetch(port: int, path: str) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=5) as r:
        return r.read().decode()


def replica_reports(cluster: LocalCluster) -> list:
    """Per replica: the /status health document plus the items it sent for
    verification (the pbft_verify_items_total counter on /metrics)."""
    out = []
    for port in cluster.metrics_ports:
        doc = json.loads(_fetch(port, "/status"))
        m = re.search(
            r"^pbft_verify_items_total\{[^}]*\} (\d+)$",
            _fetch(port, "/metrics"),
            re.M,
        )
        check(m is not None, f"replica {doc['replica']}: no pbft_verify_items_total")
        doc["verify_items"] = int(m.group(1))
        out.append(doc)
    return out


@contextlib.contextmanager
def gateway_process(cfg_path: Path, log_path: Path, children: list):
    """One ``python -m pbft_tpu.net.gateway`` process; yields its address."""
    with open(log_path, "wb") as log_fh:
        proc = subprocess.Popen(
            [sys.executable, "-m", "pbft_tpu.net.gateway", "--config",
             str(cfg_path), "--host", "127.0.0.1", "--port", "0"],
            stdout=log_fh, stderr=subprocess.STDOUT, cwd=REPO,
        )
    children.append(("gateway", proc))
    try:
        deadline = time.monotonic() + 30
        while True:
            m = re.search(r"gateway listening on (\d+)",
                          log_path.read_text(errors="replace"))
            if m:
                break
            check(proc.poll() is None and time.monotonic() < deadline,
                  f"gateway never listened:\n{log_path.read_text(errors='replace')}")
            time.sleep(0.05)
        yield f"127.0.0.1:{m.group(1)}"
        check(proc.poll() is None, f"gateway exited early (code {proc.returncode})")
    finally:
        stop_child(proc)


def deployment_stage(
    target: str,
    children: list,
    requests: int = REQUESTS,
    clients: int = CLIENTS,
    window: int = WINDOW,
) -> None:
    before = ready_status(target)
    with LocalCluster(
        n=4,
        verifier=target,
        wal=True,
        wal_fsync=True,
        batch_max_items=32,
        batch_flush_us=2000,
        metrics_ports=True,
    ) as cluster:
        children.extend((f"pbftd {i}", p) for i, p in enumerate(cluster.procs))
        tmp = Path(cluster.tmpdir.name)
        try:
            with gateway_process(
                tmp / "network.json", tmp / "gateway.log", children
            ) as gw_addr:
                per_client = requests // clients
                check(per_client * clients == requests, "requests % clients != 0")
                results: dict = {}

                def drive(i: int) -> None:
                    client = GatewayClient(cluster.config, gw_addr)
                    try:
                        # Each result already carries f+1 matching replies
                        # whose signatures the client checked (wait_result).
                        results[i] = client.request_many(
                            [f"smoke-{i}-{k}" for k in range(per_client)],
                            window=window,
                            timeout=60.0,
                        )
                    finally:
                        client.close()

                t0 = time.monotonic()
                threads = [
                    threading.Thread(target=drive, args=(i,)) for i in range(clients)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(600)
                    check(not t.is_alive(), "a client never finished")
                elapsed = time.monotonic() - t0
                done = sum(
                    r == "awesome!" for rs in results.values() for r in rs
                )
                check(
                    done == requests,
                    f"{done} / {requests} requests committed with f+1 "
                    "matching replies",
                )
                log(f"{done} / {requests} requests committed with f+1 matching "
                    f"signed replies ({clients} identities x window {window}, "
                    f"{elapsed:.1f}s of wall clock, not a benchmark)")

                # Let the trailing commits and checkpoints drain, then read
                # every replica once everything it sent has come back.
                deadline = time.monotonic() + 60
                reports = replica_reports(cluster)
                while True:
                    time.sleep(0.5)
                    again = replica_reports(cluster)
                    if all(
                        a["verify_items"] == b["verify_items"]
                        and b["inbox_depth"] == 0
                        and b["executed"] == requests
                        for a, b in zip(reports, again)
                    ):
                        # The later reading: a batch still in flight at the
                        # earlier one (sent, its verdicts not yet consumed)
                        # has come back by now.
                        reports = again
                        break
                    check(time.monotonic() < deadline,
                          f"replicas never quiesced: {again}")
                    reports = again
                after = ready_status(target)
            for i, proc in enumerate(cluster.procs):
                check(proc.poll() is None,
                      f"pbftd {i} exited early (code {proc.returncode})")
        except BaseException:
            shutil.copytree(tmp, OUT_DIR / "cluster", dirs_exist_ok=True)
            raise
    for doc in reports:
        rid = doc["replica"]
        check(doc["executed"] == requests,
              f"replica {rid} executed {doc['executed']} of {requests}")
        check(doc["chain_digest"] == reports[0]["chain_digest"],
              f"replica {rid} chain_digest differs: {doc['chain_digest']}")
        check(doc["verify_service_fallbacks"] == 0,
              f"replica {rid} verified {doc['verify_service_fallbacks']} "
              "batches on the host")
        check(doc["verify_deadline_fired"] == 0,
              f"replica {rid} fired its verify deadline")
        check(doc["wal_enabled"] and doc["wal_fsyncs"] > 0,
              f"replica {rid} ran without a fsynced WAL: {doc['wal_fsyncs']}")
        # One item per verdict consumed, by definition (core/replica.cc
        # deliver_verdicts): items sent == sig_verified + sig_rejected.
        check(doc["verify_items"] == doc["sig_verified"] + doc["sig_rejected"],
              f"replica {rid}: {doc['verify_items']} items sent but "
              f"{doc['sig_verified']}+{doc['sig_rejected']} verdicts consumed")
    sent = sum(doc["verify_items"] for doc in reports)
    engine = after["engine_items"] - before["engine_items"]
    check(
        engine == sent and after["fallback_items"] == before["fallback_items"],
        f"engine verified {engine} items, replicas sent {sent} "
        f"(service fallback items "
        f"{after['fallback_items'] - before['fallback_items']})",
    )
    log(f"4 replicas: executed={requests} each, one chain_digest "
        f"{reports[0]['chain_digest'][:16]}…, verify_service_fallbacks=0, "
        f"verify_deadline_fired=0, wal_fsyncs="
        f"{[d['wal_fsyncs'] for d in reports]}")
    log(f"engine items {engine} == sum of items the replicas sent "
        f"{[d['verify_items'] for d in reports]} "
        f"(= sig_verified + sig_rejected on each), in "
        f"{after['engine_launches'] - before['engine_launches']} launches")


# -- the run -------------------------------------------------------------------


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=22, help="signs every item")
    args = parser.parse_args()
    t_start = time.monotonic()
    OUT_DIR.mkdir(parents=True, exist_ok=True)

    native.build()  # a failed build is fatal and shows the compiler's output
    children: list = []  # (name, Popen) of every process this run starts
    trace_path = OUT_DIR / "verifyd_trace.jsonl"
    trace_path.unlink(missing_ok=True)
    with open(OUT_DIR / "verifyd.log", "wb") as vlog:
        verifyd, target = spawn_verifyd(
            ("--backend", "jax", "--trace", str(trace_path)),
            stdout=vlog,
            stderr=subprocess.STDOUT,
        )
    children.append(("verifyd", verifyd))
    try:
        try:
            status = wait_for_tpu_service(target, verifyd, WARM_BUDGET_S)
        except BaseException:
            sys.stderr.write((OUT_DIR / "verifyd.log").read_text(errors="replace")[-4000:])
            raise
        warm = status["warm_stats"]
        log(f"verifyd ready on platform={status['platform']} "
            f"device_kind={status['device_kind']!r}: JAX sees "
            f"{status['devices_seen']} device(s), {status['devices']} in the mesh")
        check(status["devices"] == status["devices_seen"],
              "the mesh does not span every device JAX sees")
        check(status["warmed_shapes"] == list(LADDER),
              f"warmed shapes {status['warmed_shapes']} != ladder {LADDER}")
        # The rule a TPU shape is compiled by (imports jax, touches no backend),
        # held against what each executable says of itself (parallel.chains_of).
        from pbft_tpu.crypto.ed25519 import chains_for

        for shape in warm["per_shape"]:
            check(
                len(shape["devices"]) == status["devices_seen"]
                and shape["rows_per_device"] * len(shape["devices"]) == shape["size"],
                f"shape {shape['size']} is not sharded over every device: {shape}",
            )
            check(
                shape["chains"] == chains_for(shape["rows_per_device"], "tpu"),
                f"shape {shape['size']} ({shape['rows_per_device']} rows a chip) runs its "
                f"multiply chains on {shape['chains']!r}, not as a TPU shape of its rows does",
            )
            log(f"shape {shape['size']}: {shape['seconds']}s "
                f"({'cache hit' if shape['cache_hit'] else 'compiled'}), input "
                f"sharded over devices {shape['devices']}, "
                f"{shape['rows_per_device']} rows each, one launch "
                f"{1e3 * shape['launch_s']:.2f} ms, multiply chains: {shape['chains']}")
        log(f"warm-up: cold_compile_s={warm['cold_compile_s']} "
            f"warm_load_s={warm['warm_load_s']} compiled={warm['compiled']} "
            f"cache_hits={warm['cache_hits']} cache_dir={warm['cache_dir']} "
            f"(spawn to ready {time.monotonic() - t_start:.1f}s)")

        device_stage(target, args.seed, trace_path=trace_path)
        deployment_stage(target, children)

        status = ready_status(target)
        check(verifyd.poll() is None,
              f"verifyd exited early (code {verifyd.returncode})")
    finally:
        for _, proc in children:
            stop_child(proc)
    alive = [name for name, proc in children if proc.poll() is None]
    check(not alive, f"children still alive at the end: {alive}")
    log(f"{len(children)} child processes started, none left; "
        f"wall time {time.monotonic() - t_start:.1f}s")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": status["platform"],
            "kind": status["device_kind"],
            "count": status["devices_seen"],
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
