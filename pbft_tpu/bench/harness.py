"""Consensus throughput harness: BASELINE.json configs 1-5, both verifier arms.

Measures sustained consensus rounds/sec and signature verifications/sec
through the deterministic replica cores wired by the in-memory transport
(pbft_tpu.consensus.simulation) — the protocol-layer complement to the
repo-root bench.py kernel benchmark.

Verifier arms:
- "cpu":   the native C++ batch verifier (core/ed25519.cc via ctypes) —
           the control arm (falls back to the Python oracle if unbuilt).
- "jax":   the batched XLA kernel (one launch per batching window).

Usage: python -m pbft_tpu.bench.harness [--arm cpu|jax] [--config N] [--out f]
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Callable, List, Optional, Tuple

from ..consensus.simulation import Cluster


@dataclasses.dataclass
class BenchResult:
    config: str
    replicas: int
    f: int
    clients: int
    requests: int
    seconds: float
    rounds_per_sec: float
    sig_verifies_per_sec: float
    sig_verifications: int
    verifier: str
    byzantine: bool = False
    pipeline: int = 1  # in-flight requests per nominal client (native arms)
    # Request batching (ISSUE 4): with batch_max_items > 1 the unit of
    # agreement is a batch, so requests/sec and rounds/sec diverge —
    # mean_batch (requests executed / rounds executed, from the replicas'
    # own counters) is the measured amplification between them.
    requests_per_sec: float = 0.0
    mean_batch: float = 1.0
    batch_max_items: int = 1
    batch_flush_us: int = 0
    # Client-observed reply latency (ISSUE 9): send -> f+1 quorum, ms,
    # over the timed region's requests. reply_p99_ms is the field
    # scripts/bench_compare.py gates (lower is better).
    reply_p50_ms: float = 0.0
    reply_p95_ms: float = 0.0
    reply_p99_ms: float = 0.0
    # Per-request segment breakdown (utils/waterfall.py join of client
    # stamps with the run's replica traces): segment -> {p50, p95, p99,
    # count} in ms. Empty when the run had no trace dir.
    latency_segments_ms: dict = dataclasses.field(default_factory=dict)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))


def _verifier(arm: str, batch_pad: int) -> Callable:
    if arm == "cpu":
        from .. import native

        # False only where there is no compiler; a build that FAILS raises
        # instead of quietly timing the Python oracle.
        if native.available():
            return native.verify_batch
        from ..crypto import ref

        return lambda items: [ref.verify(p, m, s) for p, m, s in items]
    from ..crypto import batch

    def jax_arm(items):
        out = []
        for i in range(0, len(items), batch_pad):
            out.extend(batch.verify_many(items[i : i + batch_pad], pad_to=batch_pad))
        return out

    return jax_arm


CONFIGS = [
    # (name, n, clients, requests, byzantine)
    ("readme-demo f=1", 4, 1, 1, False),
    ("firehose f=1", 4, 1, 200, False),
    ("f=2 multi-client", 7, 4, 100, False),
    ("f=5 large-batch", 16, 8, 50, False),
    ("f=10 byzantine-signer", 31, 8, 12, True),
]

# In-flight requests per nominal client on the NATIVE arms, by config
# index. BASELINE's firehose is "client firehose @ 1k req/s" — an arrival
# rate far above the per-round latency, i.e. deep pipelining: the load
# generator keeps this many requests in flight (each on its own reply
# listener identity), and the replicas batch verification across the
# concurrent sequence numbers (SURVEY.md §7 "batch across pipelined
# rounds"). The lockstep simulation arms keep one request per client.
PIPELINE = {1: 32}


def run_config(
    index: int,
    arm: str = "cpu",
    batch_pad: int = 256,
    requests: Optional[int] = None,
) -> BenchResult:
    name, n, clients, default_requests, byzantine = CONFIGS[index]
    reqs_total = requests or default_requests
    cluster = Cluster(n=n, verifier=_verifier(arm, batch_pad))
    if byzantine:
        import dataclasses as dc

        def corrupt(src, msg):
            if src == n - 1 and getattr(msg, "sig", ""):
                return dc.replace(msg, sig="ff" * 64)
            return msg

        cluster.outbound_mutator = corrupt

    t0 = time.perf_counter()
    pending: List[Tuple[int, int]] = []
    submitted = 0
    # Pipelined submission: keep `clients` requests in flight (a PBFT
    # client has one outstanding request at a time, PBFT §4.1).
    client_ts = {c: 0 for c in range(clients)}
    inflight: dict = {}
    executed = 0
    while executed < reqs_total:
        for c in range(clients):
            if c not in inflight and submitted < reqs_total:
                client_ts[c] += 1
                r = cluster.submit(
                    f"op-{submitted}",
                    client=f"127.0.0.1:{9000 + c}",
                    timestamp=client_ts[c],
                )
                inflight[c] = r.timestamp
                submitted += 1
        if not cluster.step():
            # Quiesced: every in-flight request has either committed or
            # stalled; check replies.
            for c, ts in list(inflight.items()):
                cluster.committed_result(ts)  # raises if not committed
                del inflight[c]
                executed += 1
            if submitted >= reqs_total and not inflight:
                break
    elapsed = time.perf_counter() - t0
    rounds = max(
        (r.counters.get("rounds_executed", 0) for r in cluster.replicas),
        default=0,
    )
    executed = max(
        (r.counters.get("executed", 0) for r in cluster.replicas), default=0
    )
    return BenchResult(
        config=name,
        replicas=n,
        f=cluster.config.f,
        clients=clients,
        requests=reqs_total,
        seconds=round(elapsed, 3),
        rounds_per_sec=round((rounds or reqs_total) / elapsed, 1),
        sig_verifies_per_sec=round(cluster.sig_verifications / elapsed, 1),
        sig_verifications=cluster.sig_verifications,
        verifier=arm,
        byzantine=byzantine,
        requests_per_sec=round(reqs_total / elapsed, 1),
        mean_batch=round(executed / rounds, 2) if rounds else 1.0,
    )


def run_native_config(
    index: int,
    requests: Optional[int] = None,
    verifier: str = "cpu",
    tag: Optional[str] = None,
    trace_dir: Optional[str] = None,
    secure: bool = False,
    pipeline: Optional[int] = None,
    flush_us: int = 0,
    flush_items: int = 0,
    batch_max_items: int = 1,
    batch_flush_us: int = 0,
) -> BenchResult:
    """The same config driven through REAL pbftd processes over loopback
    TCP (framed wire protocol, dial-back replies) instead of the in-memory
    lockstep simulation — the deployment-shaped number. The Byzantine
    config runs replica n-1 with pbftd --byzantine (every outgoing
    signature corrupted); the honest 2f+1 must carry every round.

    ``verifier`` is the daemon's backend selector: "cpu" (in-process C++
    Ed25519) or a "host:port" / unix-path address of a running verify
    service — pass a warmed ``verifyd``'s to measure the full deployment
    shape (N daemons -> coalescing service -> one XLA launch per window)."""
    import re
    import threading
    from pathlib import Path

    from ..net import LocalCluster, PbftClient

    name, n, clients, default_requests, byzantine = CONFIGS[index]
    if pipeline is None:
        pipeline = PIPELINE.get(index, 1)
    # Pipelined load generators (PbftClient.request_many): each worker
    # streams a WINDOW of requests over one connection — the
    # windowed-async shape that actually fills the primary's request
    # batches (ISSUE 4). The pipeline depth is split across several
    # worker identities (window <= 8 each) because every reply is dialed
    # back per address with per-address serialization — one identity
    # carrying the whole pipeline would measure the reply dialer, not
    # the protocol. (The former drive used clients x pipeline lock-step
    # threads: same concurrency, one request per client per round trip,
    # which can never fill a batch from one client.)
    window = min(pipeline, 8)
    workers = clients * max(1, (pipeline + window - 1) // window)
    reqs_total = requests or max(default_requests, 100, clients * pipeline * 6)
    per_worker = max(1, reqs_total // workers)
    reqs_total = per_worker * workers
    if trace_dir:
        # Fresh trace set per run: pbftd opens trace files in append mode,
        # and stale events from a previous run would corrupt the
        # launch-cost model's occupancy measurement.
        Path(trace_dir).mkdir(parents=True, exist_ok=True)
        for old in Path(trace_dir).glob("replica-*.jsonl"):
            old.unlink()
    with LocalCluster(
        n=n,
        verifier=verifier,
        metrics_every=1,
        byzantine=[n - 1] if byzantine else None,
        trace_dir=trace_dir,
        secure=secure,
        verify_flush_us=flush_us,
        verify_flush_items=flush_items,
        batch_max_items=batch_max_items,
        batch_flush_us=batch_flush_us,
    ) as cluster:
        f_val = cluster.config.f
        handles = [PbftClient(cluster.config) for _ in range(workers)]
        # Warmup with retransmission: the paper's client retry keeps the
        # round alive while the cluster's links come up.
        handles[0].request_with_retry("warmup", timeout=600, retry_every=5)
        t0 = time.perf_counter()
        t0_mono = time.monotonic()  # client stamps are monotonic-clock

        def drive(ci: int) -> None:
            handles[ci].request_many(
                [f"op-{ci}-{k}" for k in range(per_worker)],
                window=window,
                timeout=60,
            )

        threads = [
            threading.Thread(target=drive, args=(i,)) for i in range(workers)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        # Client-side latency stamps (ISSUE 9): reply latency percentiles
        # from every worker's send->quorum records, warmup excluded; with
        # a trace dir the client records also join against the replica
        # traces into the per-request segment waterfall.
        client_records = [
            rec
            for c in handles
            for rec in c.latency_records()
            if rec["send"] >= t0_mono
        ]
        reply_ms = sorted(
            (rec["quorum"] - rec["send"]) * 1e3
            for rec in client_records
            if "quorum" in rec
        )

        def _pct(vals, q):
            return vals[min(len(vals) - 1, int(q * len(vals)))] if vals else 0.0

        latency_segments: dict = {}
        if trace_dir:
            from pathlib import Path as _Path

            from ..utils import waterfall as wf_mod

            for ci, c in enumerate(handles):
                c.write_trace(str(_Path(trace_dir) / f"client-{ci}.jsonl"))
            events = wf_mod.load_jsonl(
                sorted(_Path(trace_dir).glob("replica-*.jsonl"))
            )
            latency_segments = wf_mod.build_waterfall(events, client_records)[
                "segments_ms"
            ]
        for c in handles:
            c.close()
        # Cluster-wide counters from each replica's last metrics line
        # (core/net.cc metrics_json): signature
        # verifications, plus requests vs rounds executed — their ratio
        # is the measured batch occupancy.
        sig_total = 0
        executed_total = 0
        rounds_total = 0
        rounds_max = 0
        time.sleep(1.5)  # one more metrics tick so counters are current
        for i in range(n):
            log = (Path(cluster.tmpdir.name) / f"replica-{i}.log").read_text(
                errors="ignore"
            )
            for pattern, sink in (
                (r'"sig_verified":\s*(\d+)', "sig"),
                (r'"executed":\s*(\d+)', "executed"),
                (r'"rounds_executed":\s*(\d+)', "rounds"),
            ):
                found = re.findall(pattern, log)
                if not found:
                    continue
                val = int(found[-1])
                if sink == "sig":
                    sig_total += val
                elif sink == "executed":
                    executed_total += val
                else:
                    rounds_total += val
                    rounds_max = max(rounds_max, val)
    return BenchResult(
        config=name,
        replicas=n,
        f=f_val,
        clients=clients,
        requests=reqs_total,
        seconds=round(elapsed, 3),
        # rounds/sec = three-phase instances completed (includes the one
        # warmup round); requests/sec = driven requests over the timed
        # region. With batch_max_items=1 the two coincide.
        rounds_per_sec=round(
            (rounds_max or reqs_total) / elapsed, 1
        ),
        sig_verifies_per_sec=round(sig_total / elapsed, 1),
        sig_verifications=sig_total,
        verifier=tag or ("native-secure" if secure else "native"),
        byzantine=byzantine,
        pipeline=pipeline,
        requests_per_sec=round(reqs_total / elapsed, 1),
        mean_batch=(
            round(executed_total / rounds_total, 2) if rounds_total else 1.0
        ),
        batch_max_items=batch_max_items,
        batch_flush_us=batch_flush_us,
        reply_p50_ms=round(_pct(reply_ms, 0.5), 3),
        reply_p95_ms=round(_pct(reply_ms, 0.95), 3),
        reply_p99_ms=round(_pct(reply_ms, 0.99), 3),
        latency_segments_ms=latency_segments,
    )


def run_all(
    arm: str = "cpu",
    out_path: Optional[str] = None,
    trace_dir: Optional[str] = None,
    secure: bool = False,
) -> List[BenchResult]:
    results = []
    for i in range(len(CONFIGS)):
        # Per-config trace subdir: configs differ in n, and pbftd appends
        # to replica-<i>.jsonl — one shared dir would interleave clusters.
        cfg_traces = f"{trace_dir}/cfg{i}" if trace_dir else None
        if arm == "native":
            res = run_native_config(i, trace_dir=cfg_traces, secure=secure)
        elif arm == "native-tpu":
            res = run_native_tpu_config(i, trace_dir=cfg_traces, secure=secure)
        else:
            res = run_config(i, arm=arm)
        print(res.to_json(), flush=True)
        results.append(res)
    if out_path:
        with open(out_path, "w") as fh:
            for r in results:
                fh.write(r.to_json() + "\n")
    return results


def run_native_tpu_config(
    index: int,
    requests: Optional[int] = None,
    trace_dir: Optional[str] = None,
    secure: bool = False,
    pipeline: Optional[int] = None,
    service_backend: str = "jax",
    batch_max_items: int = 1,
    batch_flush_us: int = 0,
) -> BenchResult:
    """run_native_config against one verify service daemon shared by every
    pbftd — the TPU deployment shape (N replicas on one host, one XLA
    launch per batching window), through the same ``VerifyServiceDaemon``
    as ``verifyd``: with ``service_backend="jax"`` the run starts once the
    engine has warmed every shape (and fails if it cannot), so no window
    pays a compile. ``"native"`` swaps the chip for the C++ batch verifier:
    same wire path and coalescing, useful for measuring merged-window
    occupancy on a box without a TPU.

    The service's own per-dispatch trace (the honest items-per-LAUNCH
    measurement — per-replica traces only see each daemon's share of a
    merged window) lands in <trace_dir>-service/service.jsonl."""
    import os

    from ..net import VerifyServiceDaemon

    service_trace = None
    if trace_dir:
        service_trace_dir = f"{trace_dir.rstrip('/')}-service"
        os.makedirs(service_trace_dir, exist_ok=True)
        service_trace = os.path.join(service_trace_dir, "service.jsonl")
        if os.path.exists(service_trace):
            os.unlink(service_trace)  # append mode; stale events corrupt
    service = VerifyServiceDaemon(
        backend=service_backend, trace_path=service_trace
    ).start(wait_ready=True)
    try:
        if service_backend == "jax" and service.state_name != "ready":
            raise RuntimeError(
                f"verify service is {service.state_name}, not ready: "
                f"{service.fatal_error or 'still warming'}"
            )
        return run_native_config(
            index,
            requests=requests,
            verifier=service.address,
            tag=("native-tpu" if service_backend == "jax" else "native-svc")
            + ("-secure" if secure else ""),
            trace_dir=trace_dir,
            secure=secure,
            pipeline=pipeline,
            batch_max_items=batch_max_items,
            batch_flush_us=batch_flush_us,
        )
    finally:
        service.stop()


def main() -> None:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--arm",
        default="cpu",
        choices=["cpu", "jax", "native", "native-tpu"],
        help="native-tpu = real pbftd daemons -> one warmed verify service "
        "daemon (the TPU deployment shape)",
    )
    parser.add_argument("--config", type=int, default=None, help="0-4; default all")
    parser.add_argument("--requests", type=int, default=None)
    parser.add_argument("--out", default=None)
    parser.add_argument(
        "--trace-dir",
        default=None,
        help="write per-replica JSONL traces here (native arms only) — "
        "input for scripts/trace_report.py",
    )
    parser.add_argument(
        "--secure",
        action="store_true",
        help="encrypted replica links (native arm only): measures the "
        "handshake + AEAD overhead at protocol level",
    )
    parser.add_argument(
        "--pipeline",
        type=int,
        default=None,
        help="in-flight requests per nominal client (native arms; default "
        "per-config PIPELINE table)",
    )
    parser.add_argument(
        "--flush-us",
        type=int,
        default=0,
        help="bounded verify accumulation window of each replica, "
        "microseconds (native arm, via network.json)",
    )
    parser.add_argument(
        "--flush-items",
        type=int,
        default=0,
        help="...flushed early once this many items are pending (0 = batch pad)",
    )
    parser.add_argument(
        "--batch-max-items",
        type=int,
        default=1,
        help="requests the primary folds into one three-phase instance "
        "(native arms; ISSUE 4 batching — requests/sec vs rounds/sec)",
    )
    parser.add_argument(
        "--batch-flush-us",
        type=int,
        default=0,
        help="partial-batch flush deadline, microseconds (native arms)",
    )
    parser.add_argument(
        "--service-backend",
        default="jax",
        choices=["jax", "cpu", "native"],
        help="native-tpu arm: the verify service's backend (native = C++ "
        "batch verifier, for occupancy runs without a chip)",
    )
    args = parser.parse_args()
    if args.config is not None:
        if args.arm == "native-tpu":
            print(
                run_native_tpu_config(
                    args.config,
                    requests=args.requests,
                    trace_dir=args.trace_dir,
                    secure=args.secure,
                    pipeline=args.pipeline,
                    service_backend=args.service_backend,
                    batch_max_items=args.batch_max_items,
                    batch_flush_us=args.batch_flush_us,
                ).to_json()
            )
        elif args.arm == "native":
            print(
                run_native_config(
                    args.config,
                    requests=args.requests,
                    trace_dir=args.trace_dir,
                    secure=args.secure,
                    pipeline=args.pipeline,
                    flush_us=args.flush_us,
                    flush_items=args.flush_items,
                    batch_max_items=args.batch_max_items,
                    batch_flush_us=args.batch_flush_us,
                ).to_json()
            )
        else:
            print(
                run_config(
                    args.config, arm=args.arm, requests=args.requests
                ).to_json()
            )
    else:
        run_all(
            arm=args.arm,
            out_path=args.out,
            trace_dir=args.trace_dir,
            secure=args.secure,
        )


if __name__ == "__main__":
    main()
