"""Cluster launcher: spawn a localhost pbftd cluster from a ClusterConfig.

The reference's 'launcher' was four shell windows plus netcat
(README.md:5-43); here the same scenario is a context manager used by the
integration tests and the benchmark harness. Builds the native core on
demand (pbft_tpu.native.build: rebuilt whenever core/* changed)."""

from __future__ import annotations

import dataclasses
import os
import socket
import subprocess
import tempfile
import time
from pathlib import Path
from typing import List, Optional

from .. import native
from ..consensus.config import ClusterConfig, make_local_cluster


def pbftd_path() -> Path:
    native.build()
    return native._BUILD_DIR / "pbftd"


def free_ports(n: int) -> List[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


class LocalCluster:
    """N ``pbftd`` processes on loopback ephemeral ports.

    ``verifier`` is what ``pbftd --verifier`` takes: "cpu" (the native
    pool) or the address of a verify service (``host:port`` or a unix
    path). The chip is reached through ``verifyd`` alone."""

    def __init__(
        self,
        n: int = 4,
        verifier: str = "cpu",
        metrics_every: int = 0,
        vc_timeout_ms: int = 0,
        discovery: bool = False,
        config: Optional[ClusterConfig] = None,
        seeds: Optional[List[bytes]] = None,
        trace_dir: Optional[str] = None,
        flight_dir: Optional[str] = None,
        byzantine: Optional[List[int]] = None,
        secure: bool = False,
        verify_flush_us: int = 0,
        verify_flush_items: int = 0,
        batch_max_items: "int | List[int]" = 1,
        batch_flush_us: "int | List[int]" = 0,
        extra_env: Optional[List[Optional[dict]]] = None,
        faults: Optional[dict] = None,
        chaos_drop_pct: float = 0.0,
        chaos_delay_ms: int = 0,
        chaos_seed: Optional[int] = None,
        admission_inflight: int = 0,
        admission_backlog: int = 0,
        net_threads: int = 1,
        fastpath: str = "sig",
        tentative: bool = False,
        wal: bool = False,
        wal_fsync: bool = True,
        metrics_ports: bool = False,
    ):
        self.trace_dir = trace_dir
        # Black-box flight recorders (ISSUE 9): each daemon dumps its last
        # N protocol events to {flight_dir}/replica-{i}.flight on
        # SIGTERM/fatal — kill() therefore ships the dead replica's black
        # box (decode with scripts/flight_dump.py).
        self.flight_dir = flight_dir
        # Request batching (ISSUE 4): scalars land in network.json; lists
        # become per-replica --batch-* CLI overrides (e.g. a batching
        # primary among batch=1 peers for the mixed-mode interop test).
        n_for_lists = (config.n if config is not None else n)
        self.batch_max_items = (
            batch_max_items
            if isinstance(batch_max_items, list)
            else [batch_max_items] * n_for_lists
        )
        self.batch_flush_us = (
            batch_flush_us
            if isinstance(batch_flush_us, list)
            else [batch_flush_us] * n_for_lists
        )
        self._batch_scalar = not (
            isinstance(batch_max_items, list) or isinstance(batch_flush_us, list)
        )
        # Replica ids whose daemons corrupt every outgoing signature
        # (--byzantine; the real-daemon analogue of the simulation's
        # outbound mutator).
        self.byzantine = set(byzantine or [])
        # Generalized fault injection (ISSUE 5): {replica_id: mode} maps
        # to --fault on the daemon (sig-corrupt|mute|stutter|equivocate),
        # and the chaos_* scalars become seeded --chaos-* link knobs on
        # EVERY replica (per-replica seeds derive from chaos_seed + id so
        # one scalar still gives each daemon its own stream).
        self.faults = dict(faults or {})
        # Durable recovery (ISSUE 15): wal=True gives every replica a
        # write-ahead log under {tmpdir}/wal (--wal-dir); kill(hard=True)
        # + revive(from_disk=True) then exercises the kill -9 ->
        # replay-from-disk path. wal_fsync=False keeps the writes but skips
        # the fsync (the A/B durability-cost lever).
        self.wal = wal
        self.wal_fsync = wal_fsync
        # Health introspection (ISSUE 16): metrics_ports=True gives every
        # replica a loopback scrape listener (--metrics-port) serving
        # Prometheus + the /status health document; self.metrics_ports maps
        # replica id -> bound port after __enter__ (pre-allocated — pbftd logs its ephemeral port to
        # stderr, but pre-allocation keeps revive() on the same port).
        self.want_metrics_ports = metrics_ports
        self.metrics_ports: List[int] = []  # reserved with the listen ports, below
        self.chaos_drop_pct = chaos_drop_pct
        self.chaos_delay_ms = chaos_delay_ms
        self.chaos_seed = chaos_seed
        self.discovery = discovery
        if config is None:
            config, seeds = make_local_cluster(n, base_port=0)
            # Discovery mode: every replica binds an ephemeral port and
            # finds peers via multicast beacons (the mDNS-equivalent);
            # otherwise pre-allocate loopback ports in the config.
            # ONE reservation for the listen ports and the scrape ports: a
            # second free_ports call can be handed a port the first one gave
            # and released (a replica's scrape port on another's listen
            # port: 31 x 31 chances in some 14,000 ports a cluster, one n=31
            # cluster in fourteen; the loser of the two binds ends or serves
            # no /metrics, and the peers that dial it reach the other).
            reserved = free_ports((0 if discovery else n) + (n if metrics_ports else 0))
            ports = [0] * n if discovery else reserved[:n]
            self.metrics_ports = reserved[len(reserved) - n:] if metrics_ports else []
            config = dataclasses.replace(
                config,
                replicas=[
                    dataclasses.replace(r, port=ports[i])
                    for i, r in enumerate(config.replicas)
                ],
                verifier=verifier,
                secure=secure,
                verify_flush_us=verify_flush_us,
                verify_flush_items=verify_flush_items,
                batch_max_items=(
                    batch_max_items if self._batch_scalar else 1
                ),
                batch_flush_us=(
                    batch_flush_us if self._batch_scalar else 0
                ),
                # Admission control (ISSUE 12): network.json knobs.
                admission_inflight=admission_inflight,
                admission_backlog=admission_backlog,
                # Multi-core replica core (ISSUE 13): pbftd shards its
                # event loop.
                net_threads=net_threads,
                # Fast-path modes (ISSUE 14): the MAC authenticator
                # offer and tentative execution.
                fastpath=fastpath,
                tentative=tentative,
                # Durable recovery (ISSUE 15): wal_fsync rides in
                # network.json; the directory itself is a per-launch
                # --wal-dir flag (set in __enter__, where tmpdir exists).
                wal_fsync=wal_fsync,
            )
        if verifier == "jax":
            raise ValueError(
                'verifier="jax" named the in-process arm of a Python replica '
                "that no longer exists: start scripts/verifyd.py (--backend "
                "jax owns the chip) and pass its address as verifier"
            )
        self.config = config
        self.seeds = seeds
        self.verifier = verifier
        self.metrics_every = metrics_every
        self.vc_timeout_ms = vc_timeout_ms
        # Per-replica environment overrides (e.g. PBFT_WIRE_CODEC=json to
        # force a JSON-only 1.0.0 peer in a mixed-codec interop test).
        self.extra_env = extra_env or [None] * self.config.n
        self.procs: List[subprocess.Popen] = []
        self.tmpdir: Optional[tempfile.TemporaryDirectory] = None
        self._cmds: List[tuple] = []  # (cmd, env) per replica, for revive()

    def __enter__(self) -> "LocalCluster":
        import random

        if self.discovery:
            # Unique group:port per cluster so parallel tests don't hear
            # each other's beacons.
            self._discovery_target = "239.255.%d.%d:%d" % (
                random.randint(1, 254),
                random.randint(1, 254),
                free_ports(1)[0],
            )
        daemon = pbftd_path()
        self.tmpdir = tempfile.TemporaryDirectory(prefix="pbftd-")
        cfg_path = Path(self.tmpdir.name) / "network.json"
        cfg_path.write_text(self.config.to_json())
        for i in range(self.config.n):
            log = open(Path(self.tmpdir.name) / f"replica-{i}.log", "wb")
            env = dict(os.environ, **self.extra_env[i]) if self.extra_env[i] else None
            cmd = [
                str(daemon),
                "--config",
                str(cfg_path),
                "--id",
                str(i),
                "--seed",
                self.seeds[i].hex(),
                "--verifier",
                self.verifier,
            ]
            if self.metrics_every:
                cmd += ["--metrics-every", str(self.metrics_every)]
            if self.want_metrics_ports:
                if not self.metrics_ports:  # a config of the caller's, with ports of its own
                    self.metrics_ports = free_ports(self.config.n)
                cmd += ["--metrics-port", str(self.metrics_ports[i])]
            if not self._batch_scalar:
                cmd += [
                    "--batch-max-items", str(self.batch_max_items[i]),
                    "--batch-flush-us", str(self.batch_flush_us[i]),
                ]
            if self.vc_timeout_ms:
                cmd += ["--vc-timeout-ms", str(self.vc_timeout_ms)]
            if self.discovery:
                cmd += ["--discovery", self._discovery_target]
            if self.trace_dir:
                cmd += ["--trace", str(Path(self.trace_dir) / f"replica-{i}.jsonl")]
            if self.flight_dir:
                Path(self.flight_dir).mkdir(parents=True, exist_ok=True)
                cmd += [
                    "--flight-file",
                    str(Path(self.flight_dir) / f"replica-{i}.flight"),
                ]
            if self.wal:
                wal_dir = Path(self.tmpdir.name) / "wal"
                wal_dir.mkdir(parents=True, exist_ok=True)
                cmd += ["--wal-dir", str(wal_dir)]
            if i in self.byzantine:
                cmd += ["--byzantine"]
            if self.faults.get(i):
                cmd += ["--fault", str(self.faults[i])]
            if self.chaos_drop_pct > 0:
                cmd += ["--chaos-drop-pct", str(self.chaos_drop_pct)]
            if self.chaos_delay_ms > 0:
                cmd += ["--chaos-delay-ms", str(self.chaos_delay_ms)]
            if (self.chaos_drop_pct > 0 or self.chaos_delay_ms > 0) and (
                self.chaos_seed is not None
            ):
                cmd += ["--chaos-seed", str(self.chaos_seed + i)]
            self._cmds.append((cmd, env))
            self.procs.append(
                subprocess.Popen(
                    cmd, stdout=log, stderr=log, close_fds=True, env=env
                )
            )
        if self.discovery:
            self._learn_discovered_ports()
        self._wait_listening()
        return self

    _discovery_target = ""

    def _learn_discovered_ports(self, timeout: float = 20.0) -> None:
        """Parse each replica's 'listening on N' log line so the *client*
        knows where to dial; the replicas themselves learn each other
        from beacons."""
        import re

        deadline = time.monotonic() + timeout
        ports: dict = {}
        while len(ports) < self.config.n:
            for i in range(self.config.n):
                if i in ports:
                    continue
                log = Path(self.tmpdir.name) / f"replica-{i}.log"
                if log.exists():
                    m = re.search(r"listening on (\d+)", log.read_text(errors="replace"))
                    if m:
                        ports[i] = int(m.group(1))
            if time.monotonic() > deadline:
                raise TimeoutError(f"discovery ports not learned\n{self.logs()}")
            time.sleep(0.05)
        self.config = dataclasses.replace(
            self.config,
            replicas=[
                dataclasses.replace(r, port=ports[i])
                for i, r in enumerate(self.config.replicas)
            ],
        )

    def _wait_listening(self, timeout: float = 30.0) -> None:
        deadline = time.monotonic() + timeout
        for ident in self.config.replicas:
            while True:
                try:
                    with socket.create_connection(
                        (ident.host, ident.port), timeout=0.2
                    ) as probe:
                        probe.setsockopt(
                            socket.IPPROTO_TCP, socket.TCP_NODELAY, 1
                        )
                        break
                except OSError:
                    if time.monotonic() > deadline:
                        raise TimeoutError(
                            f"replica {ident.replica_id} never listened on "
                            f"{ident.host}:{ident.port}\n{self.logs()}"
                        )
                    time.sleep(0.05)

    def logs(self) -> str:
        out = []
        if self.tmpdir:
            for p in sorted(Path(self.tmpdir.name).glob("replica-*.log")):
                out.append(f"=== {p.name} ===\n{p.read_text(errors='replace')}")
        return "\n".join(out)

    def kill(self, replica_id: int, hard: bool = False) -> None:
        """Crash-stop one replica (fault injection: PBFT tolerates f).
        ``hard=True`` sends SIGKILL (the kill -9 realism arm, ISSUE 15):
        no signal handler runs — no flight dump, no final fsync beyond
        what group commit already made durable."""
        if hard:
            self.procs[replica_id].kill()
        else:
            self.procs[replica_id].terminate()
        self.procs[replica_id].wait(timeout=5)

    _KEEP = object()  # revive() sentinel: carry the original launch flag

    def revive(
        self,
        replica_id: int,
        fault=_KEEP,
        chaos_drop_pct=_KEEP,
        chaos_delay_ms=_KEEP,
        from_disk: bool = False,
    ) -> None:
        """Restart a killed replica.

        The default is the historic FRESH-STATE restart: the daemon
        forgets everything and catches up via checkpoints + state
        transfer (PBFT §5.3). CAVEAT this default silently relies on —
        and tests composing faults must respect — the <= f window: an
        amnesiac restart has forgotten its PREPARE/COMMIT votes, so for
        the duration of its catch-up it can (under adversarial message
        timing) vote differently than its previous life and must be
        budgeted as one of the f tolerable faults. It is safe in every
        scenario that keeps total concurrent faults within f, which is
        why it was acceptable so far — but it is NOT a durability story.

        ``from_disk=True`` (ISSUE 15) is the durability story: the
        daemon relaunches with its original ``--wal-dir`` (requires the
        cluster to have been built with ``wal=True``), replays the
        write-ahead log, re-joins the SAME view at its stable-checkpoint
        floor, and refuses to emit any vote contradicting a persisted
        one — a from-disk restart never spends fault budget.

        Either way the revived daemon CARRIES the fault/chaos flags of
        the original launch, so kill -> revive composes with fault
        schedules instead of silently swapping in a clean replica. Pass
        ``fault=None`` / ``chaos_*=0`` to revive clean(er), or a new
        mode/value to change the behavior across the restart."""
        cmd, env = self._cmds[replica_id]
        if from_disk:
            if "--wal-dir" not in cmd:
                raise ValueError(
                    "revive(from_disk=True) needs a cluster launched with "
                    "wal=True (no --wal-dir on the original command)"
                )
        elif "--wal-dir" in cmd:
            # Fresh-state semantics must stay the default even on a
            # wal-enabled cluster: wipe this replica's log so the replay
            # finds nothing (the amnesia scenario, deliberately).
            ix = cmd.index("--wal-dir")
            wal_path = Path(cmd[ix + 1]) / f"replica-{replica_id}.wal"
            try:
                wal_path.unlink()
            except FileNotFoundError:
                pass
        if fault is not self._KEEP or chaos_drop_pct is not self._KEEP or (
            chaos_delay_ms is not self._KEEP
        ):
            cmd = self._strip_fault_flags(
                list(cmd),
                strip_fault=fault is not self._KEEP,
                strip_drop=chaos_drop_pct is not self._KEEP,
                strip_delay=chaos_delay_ms is not self._KEEP,
            )
            if fault is not self._KEEP and fault:
                cmd += ["--fault", str(fault)]
            if chaos_drop_pct is not self._KEEP and chaos_drop_pct > 0:
                cmd += ["--chaos-drop-pct", str(chaos_drop_pct)]
            if chaos_delay_ms is not self._KEEP and chaos_delay_ms > 0:
                cmd += ["--chaos-delay-ms", str(chaos_delay_ms)]
            self._cmds[replica_id] = (cmd, env)
        log = open(
            Path(self.tmpdir.name) / f"replica-{replica_id}.log", "ab"
        )
        self.procs[replica_id] = subprocess.Popen(
            cmd, stdout=log, stderr=log, close_fds=True, env=env
        )

    @staticmethod
    def _strip_fault_flags(cmd, strip_fault, strip_drop, strip_delay):
        out, skip = [], 0
        for arg in cmd:
            if skip:
                skip -= 1
                continue
            if strip_fault and arg == "--byzantine":
                continue
            if strip_fault and arg == "--fault":
                skip = 1
                continue
            if strip_drop and arg == "--chaos-drop-pct":
                skip = 1
                continue
            if strip_delay and arg == "--chaos-delay-ms":
                skip = 1
                continue
            out.append(arg)
        return out

    def __exit__(self, *exc) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
        if self.tmpdir:
            self.tmpdir.cleanup()
