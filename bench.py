"""Benchmark: PREPARE+COMMIT signature verifications/sec on one host.

The north-star metric (BASELINE.json): the reference intended per-message
Ed25519 checks on every PREPARE/COMMIT (left as TODOs, reference
src/behavior.rs:127,:185); this framework batches a window of quorum
certificates into one XLA launch sharded across every local device.

The accelerator is owned by the PERSISTENT verify service
(scripts/verifyd.py), never by the bench: this process stays off JAX.

  Default arm: use the service at PBFT_VERIFY_SERVICE if one answers,
  else spawn ``verifyd --backend jax`` once and wait for readiness under
  PBFT_SERVICE_WARM_BUDGET_S (the once-per-deploy cold start, paid
  OUTSIDE the timed region). Either way the service must report state
  ``ready`` on platform ``tpu``; if it does not, the bench prints an
  error line and exits 1. There is no fallback: a CPU number is never
  printed by the arm that measures the device.

  Arms that measure something else run only when asked for by name:
  PBFT_BENCH_NATIVE (the C++ verify pool), PBFT_BENCH_CPU (the kernel on
  XLA:CPU, in-process), PBFT_BENCH_CONSENSUS (a pbftd cluster with the
  CPU verifier).

Methodology, service arm: the timed region counts verdict bytes returned
for submitted windows (request -> merged coalesced window -> sharded XLA
launch -> per-connection verdict slices), after one untimed warmup
round-trip per connection. Verdict bitmaps are validated against the
known-planted invalid signature. The in-process XLA:CPU arm keeps the
chained-jit methodology: K kernel applications chained inside one jit so
async dispatch and launch caching cannot fake the number.

Baseline for vs_baseline: the reference publishes no numbers and does not
compile (SURVEY.md §6); BASELINE.json's target is >= 50,000 verifies/sec on
one TPU host, so vs_baseline = value / 50_000.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"backend", "platform", "device_kind", "device_count"[, ...]} — every
result names the device it was measured on.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO)

_METRIC = "ed25519_sig_verifies_per_sec"


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _emit(
    per_sec: float,
    backend: str,
    device: dict,
    extra: dict | None = None,
) -> None:
    result = {
        "metric": _METRIC,
        "value": round(per_sec, 1),
        "unit": "signatures/sec",
        "vs_baseline": round(per_sec / 50_000.0, 3),
        "backend": backend,
        **device,
    }
    if extra:
        result.update(extra)
    print(json.dumps(result))


# The arms that run no JAX verify on the host's own cores.
_HOST_DEVICE = {
    "platform": "cpu",
    "device_kind": "host C++ verify pool",
    "device_count": 0,
}


def _fail(stage: str, err: str) -> None:
    """Fail fast but still emit the one JSON line the driver parses."""
    print(
        json.dumps(
            {
                "metric": _METRIC,
                "value": 0.0,
                "unit": "signatures/sec",
                "vs_baseline": 0.0,
                "error": f"{stage}: {err}",
            }
        ),
        flush=True,
    )
    os._exit(1)


def _signed_pool(batch: int):
    """(pubs, msgs, sigs) uint8 arrays: a 64-triple signed pool tiled to
    the batch, with sigs[batch//2] corrupted (the batch-reject path must
    not cost extra). Verification cost is independent of uniqueness.
    Signed by the native C++ core (a failed build is fatal, with the
    compiler's output)."""
    from pbft_tpu import native

    pool = 64
    pubs = np.zeros((pool, 32), np.uint8)
    msgs = np.zeros((pool, 32), np.uint8)
    sigs = np.zeros((pool, 64), np.uint8)
    for i in range(pool):
        seed = bytes([i + 1, 0x42]) * 16
        msg = os.urandom(32)
        pubs[i] = np.frombuffer(native.public_key(seed), np.uint8)
        msgs[i] = np.frombuffer(msg, np.uint8)
        sigs[i] = np.frombuffer(native.sign(seed, msg), np.uint8)
    reps = (batch + pool - 1) // pool
    bp = np.tile(pubs, (reps, 1))[:batch]
    bm = np.tile(msgs, (reps, 1))[:batch]
    bs = np.tile(sigs, (reps, 1))[:batch]
    bs[batch // 2, 7] ^= 0xFF
    return bp, bm, bs


def _native_rate(native, items, target_secs: float) -> float:
    """Sustained verifies/sec over repeated full-batch calls."""
    batch = len(items)
    done = 0
    t0 = time.perf_counter()
    elapsed = 0.0
    while elapsed < target_secs or done == 0:
        native.verify_batch(items)
        done += batch
        elapsed = time.perf_counter() - t0
    return done / elapsed


def _native_arm(target_secs: float) -> None:
    """PBFT_BENCH_NATIVE: the framework's production CPU verifier arm (the
    native C++ backend pbftd uses) — no JAX involvement at all. Measures
    BOTH the single-thread rate and the pooled rate (core/verify_pool.cc
    at PBFT_VERIFY_THREADS, default hardware concurrency) and reports the
    pooled number as the headline with the scaling recorded alongside."""
    from pbft_tpu import native

    # The spec corrupts one signature per batch, so exactly one RLC window
    # pays the bisect; the fixed bisect cost amortizes over the batch.
    batch = int(os.environ.get("PBFT_BENCH_BATCH", "4096"))
    bp, bm, bs = _signed_pool(batch)
    items = [(bytes(bp[i]), bytes(bm[i]), bytes(bs[i])) for i in range(batch)]
    out = native.verify_batch(items)
    if sum(out) != batch - 1 or out[batch // 2]:
        _fail("native-verdicts", f"wrong bitmap: sum={sum(out)}")
    want_threads = int(os.environ.get("PBFT_VERIFY_THREADS", "0"))
    native.set_verify_threads(1)
    single = _native_rate(native, items, max(1.0, target_secs / 2))
    _log(f"native CPU arm (1 thread): {single:.0f} verifies/sec")
    native.set_verify_threads(want_threads)  # 0 = hardware concurrency
    threads = native.verify_threads()
    if threads > 1:
        # Pooled/serial verdict parity on the bench batch itself before
        # trusting the pooled rate.
        if native.verify_batch(items) != out:
            _fail("native-verdicts", "pooled verdicts diverge from serial")
        pooled = _native_rate(native, items, target_secs)
    else:
        pooled = single
    _log(
        f"native CPU arm: {pooled:.0f} verifies/sec pooled "
        f"({threads} threads; {pooled / single:.2f}x single-thread)"
    )
    _emit(
        pooled,
        "cpu-native",
        _HOST_DEVICE,
        extra={
            "threads": threads,
            "single_thread_per_sec": round(single, 1),
            "pooled_per_sec": round(pooled, 1),
            "pool_speedup": round(pooled / single, 2),
        },
    )


def _service_target() -> str:
    return os.environ.get("PBFT_VERIFY_SERVICE", "127.0.0.1:7600")


def _tpu_service(budget_s: float):
    """A verify service that is ``ready`` on platform ``tpu``, or exit 1.

    Uses the one at PBFT_VERIFY_SERVICE if it answers; else spawns
    ``verifyd --backend jax`` — the once-per-deploy cold start (backend
    init + the pad ladder's AOT warm-up), paid entirely OUTSIDE the timed
    region and bounded by ``budget_s``. Returns (proc, target, status,
    cold_start_s); proc and cold_start_s are None for a service we did
    not start."""
    from pbft_tpu.net.verify_service import (
        VerifydNotReady,
        probe_status_json,
        spawn_verifyd,
        stop_child,
        wait_for_tpu_service,
    )

    target = _service_target()
    proc = None
    if probe_status_json(target, timeout=2.0) is None:
        # stdout is OURS for the one result line: the daemon's
        # announcements go to devnull, its errors to our stderr.
        import subprocess

        proc, target = spawn_verifyd(stdout=subprocess.DEVNULL)
        _log(f"launched verify service on {target} (pid {proc.pid})")
    t0 = time.perf_counter()
    try:
        status = wait_for_tpu_service(target, proc=proc, budget_s=budget_s)
    except VerifydNotReady as e:
        stop_child(proc)
        _fail("verify-service", str(e))
    cold = time.perf_counter() - t0
    _log(f"verify service at {target} ready after {cold:.1f}s: {status}")
    return proc, target, status, (cold if proc is not None else None)


def _run_service_bench(
    target: str, status: dict, target_secs: float, cold_start_s: float | None
) -> None:
    """Drive a ready verify service: several connections submit windows
    concurrently (the coalescing dispatcher merges them into sharded XLA
    launches), timed AFTER one untimed warmup round-trip per connection —
    zero timed seconds on backend init or compile."""
    import socket

    batch = int(os.environ.get("PBFT_BENCH_BATCH", "1024"))
    conns = int(os.environ.get("PBFT_BENCH_SERVICE_CONNS", "4"))
    # Per-roundtrip socket deadline: generous (a warmed TPU launch is
    # milliseconds; XLA:CPU control arms take seconds per window).
    io_timeout = float(os.environ.get("PBFT_BENCH_SERVICE_TIMEOUT", "300"))
    bp, bm, bs = _signed_pool(batch)
    payload = (batch).to_bytes(4, "big") + b"".join(
        bytes(bp[i]) + bytes(bm[i]) + bytes(bs[i]) for i in range(batch)
    )
    host, port = target.rsplit(":", 1)

    def roundtrip(sock) -> int:
        sock.sendall(payload)
        got = 0
        while got < batch:
            chunk = sock.recv(batch - got)
            if not chunk:
                raise ConnectionError("service closed mid-verdicts")
            got += len(chunk)
        return got

    socks = []
    try:
        t0 = time.perf_counter()
        for _ in range(conns):
            sock = socket.create_connection(
                (host, int(port)), timeout=io_timeout
            )
            # Warmup round-trip: validates the verdict bitmap end to end
            # and keeps connect + first-window effects out of the timed
            # region. (The service compiled at startup; this is not a
            # compile, just the pipeline filling.)
            sock.sendall(payload)
            out = b""
            while len(out) < batch:
                chunk = sock.recv(batch - len(out))
                if not chunk:
                    raise ConnectionError("service closed during warmup")
                out += chunk
            if sum(out) != batch - 1 or out[batch // 2]:
                _fail("service-verdicts", f"wrong bitmap: sum={sum(out)}")
            socks.append(sock)
        warm_start_s = time.perf_counter() - t0
        _log(f"service warm-start ({conns} conns): {warm_start_s:.2f}s")

        done = [0] * conns
        errors: list = []
        stop_at = time.perf_counter() + target_secs

        def worker(idx: int, sock) -> None:
            try:
                while time.perf_counter() < stop_at or done[idx] == 0:
                    done[idx] += roundtrip(sock)
            except Exception as e:  # noqa: BLE001 - reported below
                errors.append(repr(e))

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=worker, args=(i, s), daemon=True)
            for i, s in enumerate(socks)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=target_secs * 10 + 120)
        elapsed = time.perf_counter() - t0
        if errors:
            _fail("service-timed-region", "; ".join(errors[:3]))
        per_sec = sum(done) / elapsed
    finally:
        for sock in socks:
            try:
                sock.close()
            except OSError:
                pass
    warm_stats = status.get("warm_stats", {})
    extra = {
        "service_state": status.get("state"),
        "devices_in_mesh": status.get("devices", 0),
        "connections": conns,
        "batch": batch,
        "warm_start_s": round(warm_start_s, 3),
        "steady_state_per_sec": round(per_sec, 1),
        "service_cold_compile_s": warm_stats.get("cold_compile_s"),
        "service_warm_load_s": warm_stats.get("warm_load_s"),
    }
    if cold_start_s is not None:
        # We launched the service this run: spawn -> ready wall time
        # (backend init + warmup), paid once per deploy, never timed.
        extra["cold_start_s"] = round(cold_start_s, 1)
    _log(
        f"service steady state: {per_sec:.0f} verifies/sec over "
        f"{conns} connections ({elapsed:.2f}s timed)"
    )
    device = {
        "platform": status["platform"],
        "device_kind": status["device_kind"],
        "device_count": status["devices_seen"],
    }
    _emit(per_sec, "verify-service", device, extra=extra)


def main() -> None:
    target_secs = float(os.environ.get("PBFT_BENCH_SECS", "5.0"))
    if os.environ.get("PBFT_BENCH_NATIVE"):
        # The pooled C++ verifier, reported as "cpu-native" with threads +
        # single-vs-pooled rates.
        _native_arm(target_secs)
        return
    if os.environ.get("PBFT_BENCH_CONSENSUS"):
        # Consensus-protocol entry (ISSUE 4): drive the f=1 firehose
        # through real pbftd daemons and report requests/sec alongside
        # rounds/sec plus the measured mean batch occupancy —
        # PBFT_BATCH_MAX_ITEMS / PBFT_BATCH_FLUSH_US select the batching
        # knobs (1/0 = the pre-batching protocol).
        import tempfile

        from pbft_tpu.bench.harness import run_native_config

        # Per-request latency waterfall (ISSUE 9): the run traces every
        # replica into a scratch dir and joins the client-side
        # send/quorum stamps against request_rx/batch_sealed/
        # consensus_span — requests_per_sec ships WITH its segment
        # breakdown (client queue, batch wait, prepared, committed,
        # execute, reply; p50/p95/p99 each).
        with tempfile.TemporaryDirectory(prefix="pbft-bench-traces-") as td:
            res = run_native_config(
                1,  # firehose f=1
                requests=int(os.environ.get("PBFT_BENCH_REQUESTS", "960")),
                pipeline=int(os.environ.get("PBFT_BENCH_PIPELINE", "64")),
                batch_max_items=int(os.environ.get("PBFT_BATCH_MAX_ITEMS", "1")),
                batch_flush_us=int(os.environ.get("PBFT_BATCH_FLUSH_US", "0")),
                trace_dir=td,
            )
        print(
            json.dumps(
                {
                    "metric": "pbft_requests_per_sec",
                    "value": res.requests_per_sec,
                    "unit": "requests/sec",
                    "rounds_per_sec": res.rounds_per_sec,
                    "mean_batch": res.mean_batch,
                    "batch_max_items": res.batch_max_items,
                    "batch_flush_us": res.batch_flush_us,
                    "reply_p50_ms": res.reply_p50_ms,
                    "reply_p95_ms": res.reply_p95_ms,
                    "reply_p99_ms": res.reply_p99_ms,
                    "segments_ms": res.latency_segments_ms,
                    "backend": "consensus-native",
                    **_HOST_DEVICE,
                }
            )
        )
        return
    if os.environ.get("PBFT_BENCH_CPU"):
        # Asked for by name: the kernel on XLA:CPU, in-process (the
        # chained-jit compile alone is minutes at the default batch).
        os.environ["JAX_PLATFORMS"] = "cpu"
        _run_xla_cpu_bench(target_secs)
        return

    # The device arm: a persistent verify service owns the chip. It must
    # be ready on a TPU — no fallback, a failure is an error line + exit 1.
    budget = float(os.environ.get("PBFT_SERVICE_WARM_BUDGET_S", "900"))
    proc, target, status, cold_start_s = _tpu_service(budget)
    from pbft_tpu.net.verify_service import stop_child

    try:
        _run_service_bench(target, status, target_secs, cold_start_s)
    finally:
        stop_child(proc)


def _run_xla_cpu_bench(target_secs: float) -> None:
    """PBFT_BENCH_CPU: the JAX kernel on XLA:CPU (JAX_PLATFORMS=cpu is set
    by the caller before the first backend touch)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pbft_tpu.crypto.batch import verify_batch
    from pbft_tpu.crypto.ed25519 import verify_kernel
    from pbft_tpu.utils.cache import configure_compile_cache

    configure_compile_cache()
    devices = jax.devices()
    batch = int(os.environ.get("PBFT_BENCH_BATCH", "4096"))
    chain_k = int(os.environ.get("PBFT_BENCH_CHAIN", "16"))
    _log(f"devices: {devices}; batch={batch} chain={chain_k}")
    bp, bm, bs = _signed_pool(batch)

    try:
        t0 = time.perf_counter()
        out = np.asarray(verify_batch(bp, bm, bs))
        compile_s = time.perf_counter() - t0
        if out.sum() != batch - 1 or out[batch // 2]:
            _fail("verdicts", f"wrong bitmap: sum={int(out.sum())}")
        _log(f"verify_batch compile+transfer+first: {compile_s:.1f}s; verdicts OK")
    except Exception as e:  # noqa: BLE001
        _fail("first-batch", repr(e))

    # Timed region: K data-dependent kernel applications per jit call.
    @jax.jit
    def chained(p, m, s):
        def body(carry, _):
            m2, acc = carry
            ok = verify_kernel(p, m2, s)
            # optimization_barrier ties the next iteration's message input
            # to THIS iteration's verdicts in the HLO dependency graph, so
            # XLA cannot hoist the (otherwise loop-invariant) verify out of
            # the scan body or collapse the chain. (A zero-valued XOR trick
            # gets constant-folded; the barrier is the supported tool.)
            m3, acc = lax.optimization_barrier((m2, acc + ok.astype(jnp.int32)))
            return (m3, acc), ()
        (_, acc), _ = lax.scan(
            body, (m, jnp.zeros((m.shape[0],), jnp.int32)), None, length=chain_k
        )
        return acc

    try:
        t0 = time.perf_counter()
        dp, dm, ds = jax.device_put(bp), jax.device_put(bm), jax.device_put(bs)
        jax.block_until_ready((dp, dm, ds))
        _log(f"host->device transfer: {time.perf_counter() - t0:.2f}s")
        t0 = time.perf_counter()
        acc = np.asarray(chained(dp, dm, ds))
        _log(f"chained compile+first: {time.perf_counter() - t0:.1f}s")
        if int(acc[0]) != chain_k or int(acc[batch // 2]) != 0:
            _fail("chained-verdicts", f"acc[0]={int(acc[0])}")
        chains = 0
        t0 = time.perf_counter()
        elapsed = 0.0
        while elapsed < target_secs or chains == 0:
            np.asarray(chained(dp, dm, ds))
            chains += 1
            elapsed = time.perf_counter() - t0
        per_sec = chains * chain_k * batch / elapsed
        _log(f"{chains} chains x {chain_k} batches of {batch} in {elapsed:.2f}s")
    except Exception as e:  # noqa: BLE001
        _fail("timed-region", repr(e))

    _emit(
        per_sec,
        "cpu",
        {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
        },
    )


if __name__ == "__main__":
    main()
