"""One run of one cell: set-up, ramp, the timed window, the comparison that
decides ``correct``, and the metrics.

Everything a cell is made of is found by name: the cell, its configuration
and its metrics in ``BENCHMARK.json``; the configuration's sizes in the file
that entry names; the traffic mix in ``traffic/<traffic>.json`` and its kind
in ``traffic_kinds/<kind>.py``; each metric's reader in ``metrics/<name>.json``
and the reducer it names in ``reducers/<reducer>.py``. This file knows none
of those names.

The served path under test is the program's own: ``loadgen.py`` (the
benchmark's client) -> one ``python -m pbft_tpu.net.gateway`` process ->
``pbftd`` x n (``LocalCluster``) -> ``RemoteVerifier`` -> ``verifyd`` ->
``ShardedVerifyEngine.verify`` -> the verify kernel on the chip. The parent
never touches JAX: ``verifyd`` (through ``verifyd_wrap.py``) is the one
process that holds the chip.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import random
import re
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT))

import stats  # noqa: E402
from reducers._window import due_in_window  # noqa: E402
from reference import ed25519_ref as ref  # noqa: E402
from reference import state_machine  # noqa: E402

from pbft_tpu.net.verify_service import probe_status_json, stop_child  # noqa: E402

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
WORK = ROOT / ".chipbench_work"
KEEP = ROOT / "chiprun_out" / "chipbench"
WARM_BUDGET_S = 1000.0
# One traced slice, a fifth into the window. Every operation of every launch
# is an event on every device plane (83,500 a launch a plane), and verifyd
# collects them into the xspace while the run goes on (PERF.md section 3 has
# the seconds a launch a plane): a longer slice, a later one or a second one
# would take the run past the time a run may take.
TRACE_S = 0.2
# How long the harness waits for the slice's ``done`` file once the replicas
# have quiesced, which is some 41 s after the slice ended (the window's last
# four fifths and the drain). The most a run can afford that has to end inside
# the 360 s a run may take: 360 less 90 of set-up, the 51 of the window, 5 of
# quiescence and 30 for reading the trace and stopping the children. One chip
# needs 0 to 8 s of it (PERF.md section 3).
TRACE_WAIT_S = 180.0
TRACE_AT = 0.2
REPLY_SAMPLE = 64


class BenchFailure(Exception):
    """The run cannot give a result: no chip, a child died, a fallback
    answered under the chip's name. Never turned into a result line."""


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


# -- what a cell is made of, by name ------------------------------------------


def _check_name(value, what: str) -> None:
    if not isinstance(value, str) or not NAME.fullmatch(value):
        raise BenchFailure(f"{what} {value!r} is not a name (letters, digits, _ . -)")


def load_benchmark(path: Path = None) -> dict:
    bench = json.loads((path or ROOT / "BENCHMARK.json").read_text())
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            _check_name(entry["name"], f"{group} name")
            if "unit" in entry and not UNIT.fullmatch(entry["unit"]):
                raise BenchFailure(f"unit {entry['unit']!r} of {entry['name']} is not a unit")
    for cell in bench["workloads"]:
        _check_name(cell["config"], "config")
        _check_name(cell["traffic"], "traffic")
    return bench


def load_cell(bench: dict, workload: str, root: Path = None) -> dict:
    """The cell's entry, its configuration's file and its traffic file."""
    root = root or ROOT
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    bench_dir = root / bench["paths"][0]
    traffic = json.loads((bench_dir / "traffic" / f"{cell['traffic']}.json").read_text())
    return {"cell": cell, "config": config, "traffic": traffic, "dir": bench_dir}


def metrics_of(bench: dict, group: str, workload: str) -> list:
    """The metrics of ``group`` that this cell reports: those that list it,
    and those that list nothing and (per layer) move a metric it reports."""
    reported = {
        m["name"] for m in bench["end_to_end"]
        if workload in m.get("workloads", [workload])
    }
    if group == "end_to_end":
        return [m for m in bench["end_to_end"] if m["name"] in reported]
    return [
        m for m in bench["per_layer"]
        if (workload in m["workloads"] if "workloads" in m else m["moves"] in reported)
    ]


def read_metric(bench_dir: Path, name: str, run: dict):
    """``metrics/<name>.json`` names a reducer and its arguments;
    ``reducers/<reducer>.py`` reads the number out of the run."""
    spec = json.loads((bench_dir / "metrics" / f"{name}.json").read_text())
    _check_name(spec["reducer"], "reducer")
    path = bench_dir / "reducers" / f"{spec['reducer']}.py"
    found = importlib.util.spec_from_file_location(f"reducers.{spec['reducer']}", path)
    module = importlib.util.module_from_spec(found)
    found.loader.exec_module(module)
    return module.reduce(run, spec.get("args", {}))


def peaks_for(bench_dir: Path, platform: str, device_kind: str) -> dict:
    if platform != "tpu":
        raise BenchFailure(f"platform {platform!r} is not a TPU")
    table = json.loads((bench_dir / "peaks.json").read_text())
    if device_kind not in table:
        raise BenchFailure(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]


# -- the host: file system, children ------------------------------------


def fs_type(path: Path) -> str:
    """The file system type of the mount that holds ``path``."""
    path = str(path.resolve())
    best, kind = "", "unknown"
    for line in Path("/proc/mounts").read_text().splitlines():
        parts = line.split()
        if len(parts) >= 3:
            mount = parts[1]
            if (path == mount or path.startswith(mount.rstrip("/") + "/")) and len(
                mount
            ) >= len(best):
                best, kind = mount, parts[2]
    return kind


def cpu_seconds(pid: int) -> float:
    try:
        fields = Path(f"/proc/{pid}/stat").read_text().rsplit(")", 1)[1].split()
    except (OSError, IndexError):
        return float("nan")
    return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")


class Children:
    """Every process the run starts, so that none is left behind."""

    def __init__(self):
        self.procs: list = []

    def add(self, name: str, proc) -> None:
        self.procs.append((name, proc))

    def check_alive(self) -> None:
        """Every child still runs, but for a generator that has drained
        and ended with code 0 (it may do so before the harness looks)."""
        for name, proc in self.procs:
            done = name.startswith("loadgen") and proc.poll() == 0
            if proc.poll() is not None and not done:
                raise BenchFailure(f"{name} exited early with code {proc.returncode}")

    def stop_all(self) -> list:
        for _, proc in reversed(self.procs):
            stop_child(proc)
        return [name for name, proc in self.procs if proc.poll() is None]

    def cpu(self) -> dict:
        return {name: cpu_seconds(proc.pid) for name, proc in self.procs}


# -- verifyd -----------------------------------------------------------------------


class Verifyd:
    """The one process that holds the chip, started through the wrapper."""

    def __init__(self, work: Path, cfg: dict, children: Children, wrapper=()):
        """``wrapper``: the script and flags started in ``verifyd_wrap.py``'s
        place (the tests' and the hunt tool's controls)."""
        from pbft_tpu.net.launcher import free_ports

        self.work = work
        self.fifo = work / "verifyd.ctl"
        os.mkfifo(self.fifo)
        self.launch_log = work / "verifyd_launches.jsonl"
        self.log_path = work / "verifyd.log"
        port = free_ports(1)[0]
        self.target = f"127.0.0.1:{port}"
        cmd = [
            sys.executable, *(wrapper or [str(HERE / "verifyd_wrap.py")]),
            "--control-fifo", str(self.fifo),
            "--port", str(port), "--trace", str(self.launch_log),
            *cfg.get("args", ["--backend", "jax"]),
        ]
        with open(self.log_path, "wb") as fh:
            self.proc = subprocess.Popen(cmd, stdout=fh, stderr=subprocess.STDOUT, cwd=ROOT)
        children.add("verifyd", self.proc)
        self._ctl = None

    def wait_ready(self, require_tpu: bool) -> dict:
        deadline = time.monotonic() + WARM_BUDGET_S
        status = None
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"verifyd exited with code {self.proc.returncode} before ready:\n"
                    + self.log_path.read_text(errors="replace")[-3000:]
                )
            status = probe_status_json(self.target)
            if status is not None:
                platform = status.get("platform")
                if require_tpu and (
                    status.get("state") == "cpu-only"
                    or (platform is not None and platform != "tpu")
                ):
                    raise BenchFailure(
                        f"no TPU: verifyd runs on platform {platform!r} "
                        f"({status.get('device_kind')}), state {status.get('state')}"
                    )
                if status.get("state") == "ready":
                    return status
            time.sleep(0.25)
        raise BenchFailure(f"verifyd not ready after {WARM_BUDGET_S:.0f}s: {status}")

    def status(self) -> dict:
        status = probe_status_json(self.target, timeout=5.0)
        if status is None or status.get("state") != "ready":
            raise BenchFailure(f"verifyd stopped answering ready: {status}")
        return status

    def command(self, line: str) -> None:
        if self._ctl is None:
            self._ctl = open(self.fifo, "w")
        self._ctl.write(line + "\n")
        self._ctl.flush()

    def await_file(self, path: Path, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while not path.exists():
            if time.monotonic() > deadline or self.proc.poll() is not None:
                raise BenchFailure(f"verifyd never wrote {path.name}")
            time.sleep(0.05)

    def launches(self) -> list:
        out = []
        for line in self.launch_log.read_text().splitlines():
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("ev") == "verify_batch":
                out.append(rec)
        return out

    def close(self) -> None:
        if self._ctl is not None:
            self._ctl.close()


# -- the probe: items the reference rejects, riding in the window's launches --------


def _le32(v: int) -> bytes:
    return v.to_bytes(32, "little")


def planted(rng: random.Random, base) -> list:
    """One item of every class the kernel decides, built from the valid
    triple ``base`` with the reference's own arithmetic (after
    ``chip_smoke.py``): seven the reference rejects, one odd one it accepts."""
    pub, msg, sig = base
    r_bytes, s = sig[:32], int.from_bytes(sig[32:], "little")
    k = rng.randrange(64)
    flipped = sig[:k] + bytes([sig[k] ^ (1 << rng.randrange(8))]) + sig[k + 1 :]
    while True:
        y = rng.randrange(ref.P)
        if ref.point_decompress(_le32(y)) is None:
            off_curve = _le32(y)
            break
    r = rng.randrange(1, ref.L)
    id_sig = ref.point_compress(ref.scalar_mult(r, ref.BASE)) + _le32(r)
    seed = rng.randbytes(32)
    a, _ = ref.secret_expand(seed)
    a_pub = ref.public_key(seed)
    r_noncanon = _le32(ref.P + 1)
    h = ref._h512_int(r_noncanon, a_pub, msg) % ref.L
    return [
        (pub, msg, flipped),  # flipped signature byte
        (pub, msg, r_bytes + _le32(s + ref.L)),  # S >= L
        (off_curve, msg, sig),  # public key off the curve
        (_le32(ref.P + 1), msg, id_sig),  # non-canonical y in the key
        (_le32(1 | 1 << 255), msg, id_sig),  # x = 0 with the sign bit
        (a_pub, msg, r_noncanon + _le32(h * a % ref.L)),  # non-canonical y in R
        (pub, bytes([msg[0] ^ 1]) + msg[1:], sig),  # wrong message
        (_le32(1), msg, id_sig),  # identity key, canonical: accepted
    ]


class Probe:
    """A connection of the benchmark's own to verifyd that sends small
    windows at a low fixed rate (the traffic file states it), each with
    one item of every planted class among valid ones. Its items ride in
    the same launches as the replicas' and come back with verdicts of the
    same kernel; every verdict is compared with the reference's."""

    DISTINCT = 8

    def __init__(self, params: dict, seed: int):
        rng = random.Random(seed ^ 0x5EED)
        self.every_s = params["every_ms"] / 1e3
        size = int(params["items"])
        pool = []
        for _ in range(size):
            sk, msg = rng.randbytes(32), rng.randbytes(32)
            pool.append((ref.public_key(sk), msg, ref.sign(sk, msg)))
        self.windows = []
        for _ in range(self.DISTINCT):
            items = list(pool)
            plants = planted(rng, rng.choice(pool))
            for item, pos in zip(plants, rng.sample(range(size), len(plants))):
                items[pos] = item
            self.windows.append(items)
        self.expected = [[ref.verify(*it) for it in w] for w in self.windows]
        self.sent: list = []  # (monotonic stamp, window index, verdicts)
        self.error = None
        self._stop = threading.Event()
        self._thread = None

    def start(self, target: str, start_at: float) -> None:
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, args=(target, start_at), daemon=True
        )
        self._thread.start()

    def _run(self, target: str, start_at: float) -> None:
        host, port = target.rsplit(":", 1)
        try:
            with socket.create_connection((host, int(port)), timeout=30) as sock:
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                fh = sock.makefile("rb")
                k = 0
                while not self._stop.wait(max(0.0, start_at + k * self.every_s - time.monotonic())):
                    items = self.windows[k % len(self.windows)]
                    sock.sendall(
                        len(items).to_bytes(4, "big") + b"".join(p + m + s for p, m, s in items)
                    )
                    out = fh.read(len(items))
                    if len(out) != len(items):
                        raise ConnectionError("verifyd closed the probe connection")
                    self.sent.append((time.monotonic(), k % len(self.windows), [bool(b) for b in out]))
                    k += 1
        except OSError as e:
            self.error = e

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(60)

    def compare(self, t0: float, t1: float) -> dict:
        """Verdict by verdict against the reference."""
        mismatches = in_window = rejects = 0
        for stamp, w, verdicts in self.sent:
            inside = t0 <= stamp < t1
            in_window += len(verdicts) if inside else 0
            for got, want in zip(verdicts, self.expected[w]):
                mismatches += got != want
                rejects += inside and not want
        return {
            "items": sum(len(v) for _, _, v in self.sent),
            "items_in_window": in_window,
            "rejects_due_in_window": rejects,
            "mismatches": mismatches,
        }


# -- the cluster ---------------------------------------------------------------------


def _fetch(port: int, path: str) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.read().decode()


def scrape(cluster) -> dict:
    """Every replica's /status document and parsed /metrics, stamped."""
    out = {"t": time.monotonic(), "status": [], "metrics": []}
    for port in cluster.metrics_ports:
        out["status"].append(json.loads(_fetch(port, "/status")))
        out["metrics"].append(stats.parse_prometheus(_fetch(port, "/metrics")))
    return out


@contextlib.contextmanager
def gateway_process(cfg_path: Path, log_path: Path, children: Children):
    with open(log_path, "wb") as fh:
        proc = subprocess.Popen(
            [sys.executable, "-m", "pbft_tpu.net.gateway", "--config", str(cfg_path),
             "--host", "127.0.0.1", "--port", "0"],
            stdout=fh, stderr=subprocess.STDOUT, cwd=ROOT,
        )
    children.add("gateway", proc)
    try:
        deadline = time.monotonic() + 30
        while True:
            m = re.search(r"gateway listening on (\d+)", log_path.read_text(errors="replace"))
            if m:
                break
            if proc.poll() is not None or time.monotonic() > deadline:
                raise BenchFailure(
                    f"gateway never listened:\n{log_path.read_text(errors='replace')}"
                )
            time.sleep(0.02)
        yield f"127.0.0.1:{m.group(1)}"
    finally:
        stop_child(proc)


def merge_generators(parts: list) -> dict:
    """The generator processes' records as one: lists joined, counts added."""
    out = dict(parts[0])
    for part in parts[1:]:
        for key, value in part.items():
            out[key] = out[key] + value
    return out


def sleep_until(stamp: float) -> None:
    while True:
        left = stamp - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.5))


def serve_window(
    loaded: dict, verifyd: Verifyd, probe: Probe, ready: dict, *, seed: int,
    seconds: float, trace: bool, children: Children, work: Path,
    population_scale: float = 1.0,
) -> dict:
    """A fresh cluster, gateway and generator against a ready verifyd:
    ramp, the timed window, drain, quiescence. Returns what was observed."""
    from pbft_tpu.net.launcher import LocalCluster

    config, traffic = loaded["config"], dict(loaded["traffic"])
    if population_scale != 1.0:  # the hunt's population lever only
        traffic["outstanding_per_identity"] = int(
            traffic["outstanding_per_identity"] * population_scale
        )
    cl = config["cluster"]
    before = verifyd.status()
    cluster = LocalCluster(
        n=cl["n"], verifier=verifyd.target, wal=cl["wal"], wal_fsync=cl["wal_fsync"],
        batch_max_items=cl["batch_max_items"], batch_flush_us=cl["batch_flush_us"],
        vc_timeout_ms=cl["vc_timeout_ms"], fastpath=cl["fastpath"],
        tentative=cl["tentative"], net_threads=cl["net_threads"], metrics_ports=True,
        extra_env=[dict(cl.get("env", {})) for _ in range(cl["n"])],
    ).__enter__()
    try:
        for i, proc in enumerate(cluster.procs):
            children.add(f"pbftd-{i}", proc)
        if cluster.config.f != cl["f"]:
            raise BenchFailure(f"n={cl['n']} gives f={cluster.config.f}, file says {cl['f']}")
        tmp = Path(cluster.tmpdir.name)
        log(f"cluster of {cl['n']} up; WAL under {tmp} on file system {fs_type(tmp)!r}")
        with gateway_process(tmp / "network.json", work / "gateway.log", children) as gw_addr:
            start_at = time.monotonic() + 1.5
            t0 = start_at + float(traffic["ramp_s"])
            t1 = t0 + seconds
            shards = int(traffic.get("processes", 1))
            spec = {
                "repo": str(ROOT), "gateway": gw_addr, "n": cl["n"], "f": cl["f"],
                "pubkeys": [r.pubkey for r in cluster.config.replicas],
                "traffic": traffic, "seed": seed, "start_at": start_at, "t1": t1,
                "drain_s": traffic["drain_s"], "shards": shards,
                "sample_every": traffic.get("sample_every", 64),
            }
            gens = []
            for k in range(shards):
                out_path = work / f"loadgen-{k}.json"
                out_path.unlink(missing_ok=True)
                spec_path = work / f"loadgen_spec-{k}.json"
                spec_path.write_text(json.dumps(dict(spec, shard=k, out=str(out_path))))
                with open(work / f"loadgen-{k}.log", "wb") as fh:
                    gen = subprocess.Popen(
                        [sys.executable, str(HERE / "loadgen.py"), "--spec", str(spec_path)],
                        stdout=fh, stderr=subprocess.STDOUT, cwd=ROOT,
                    )
                children.add(f"loadgen-{k}", gen)
                gens.append((k, gen, out_path))
            probe.sent.clear()
            probe.start(verifyd.target, start_at)
            sleep_until(t0)
            children.check_alive()
            edge_a, cpu_a = scrape(cluster), children.cpu()
            trace_dir = work / "trace"
            if trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
                sleep_until(t0 + TRACE_AT * seconds)
                verifyd.command(f"trace {TRACE_S} {trace_dir}")
            sleep_until(t1)
            edge_b, cpu_b = scrape(cluster), children.cpu()
            children.check_alive()
            mem_path = work / "device_memory.json"
            mem_path.unlink(missing_ok=True)
            verifyd.command(f"mem {mem_path}")
            for k, gen, _ in gens:
                try:
                    gen.wait(timeout=float(traffic["drain_s"]) + 60)
                except subprocess.TimeoutExpired:
                    raise BenchFailure("a generator never finished its drain") from None
                if gen.returncode != 0:
                    raise BenchFailure(
                        f"generator {k} exited {gen.returncode}:\n"
                        + (work / f"loadgen-{k}.log").read_text(errors="replace")[-3000:]
                    )
            probe.stop()
            if probe.error is not None:
                raise BenchFailure(f"probe connection failed: {probe.error!r}")
            # Trailing commits and checkpoints drain; then every replica is
            # read once everything it sent for verification has come back
            # and a replica that fell behind has caught up (one digest).
            deadline = time.monotonic() + 60
            last = scrape(cluster)
            while True:
                time.sleep(0.3)
                final = scrape(cluster)
                items = [stats.counter_delta({}, m, "pbft_verify_items_total") for m in final["metrics"]]
                prev = [stats.counter_delta({}, m, "pbft_verify_items_total") for m in last["metrics"]]
                settled = items == prev and all(d["inbox_depth"] == 0 for d in final["status"])
                one_digest = len({d["chain_digest"] for d in final["status"]}) == 1
                if settled and (one_digest or time.monotonic() > deadline):
                    break
                if time.monotonic() > deadline + 30:
                    raise BenchFailure("replicas never quiesced")
                last = final
            after = verifyd.status()
            verifyd.await_file(mem_path, 30)
            memory = json.loads(mem_path.read_text())
            reduced = None
            if trace:
                import xplane

                waited = time.monotonic()
                verifyd.await_file(trace_dir / "done", TRACE_WAIT_S)
                stamps = json.loads((trace_dir / "done").read_text())
                log(f"traced slice of {TRACE_S}s ended {t1 - stamps['off']:.1f}s before the window "
                    f"did and was written {stamps['stopped'] - stamps['off']:.1f}s after it ended "
                    f"({stamps['xspace_bytes']} bytes, collected in "
                    f"{stamps['collected'] - stamps['off']:.1f}s; the harness waited "
                    f"{time.monotonic() - waited:.1f}s of {TRACE_WAIT_S:.0f}s for it)")
                try:
                    # A slice beyond the window, or no operation on a TPU in
                    # it: fatal on the chip; the CPU rehearsal goes on
                    # without device numbers.
                    if stamps["off"] > t1:
                        raise ValueError("the traced slice ended after the window did")
                    reduced = xplane.reduce_trace(
                        xplane.find_xplane(trace_dir), verifyd.launches(), config["ladder"],
                        stamps["off"],
                    )
                except ValueError as e:
                    if ready.get("platform") == "tpu":
                        raise BenchFailure(str(e)) from None
                    log(f"trace without device numbers: {e}")
            children.check_alive()
        gen_out = merge_generators([json.loads(p.read_text()) for _, _, p in gens])
    except BaseException:
        KEEP.mkdir(parents=True, exist_ok=True)
        shutil.copytree(work, KEEP / "failed", dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("*.ctl", "wal", "trace"))
        raise
    finally:
        cluster.__exit__(None, None, None)
    launches = verifyd.launches()
    return {
        "seed": seed, "seconds": seconds, "t0": t0, "t1": t1, "start_at": start_at,
        "gen": gen_out, "edge_a": edge_a, "edge_b": edge_b, "final": final,
        "cpu_window": {k: cpu_b[k] - cpu_a.get(k, 0.0) for k in cpu_b},
        "launches": [e for e in launches if t0 <= e["ts"] < t1],
        "ladder": config["ladder"], "trace": reduced, "memory": memory,
        "verifyd_before": before, "verifyd_after": after, "ready": ready,
        "config": config, "traffic": traffic, "probe": probe.compare(t0, t1),
        "n": cl["n"], "f": cl["f"],
        "pubkeys": [bytes.fromhex(r.pubkey) for r in cluster.config.replicas],
    }


# -- the comparison that decides `correct` ------------------------------------------


def compare_run(run: dict) -> list:
    """Every number compared, beside its limit: (name, value, op, limit)."""
    gen, final = run["gen"], run["final"]["status"]
    acked = sum(d is not None for d in gen["done"])
    # A request is acknowledged on f+1 signed replies, so at least f+1
    # replicas executed it; a replica that fell behind and caught up by
    # state transfer executes less and still ends on the same digest.
    executed = sorted((d["executed"] for d in final), reverse=True)
    replica_items = sum(
        stats.counter_delta({}, m, "pbft_verify_items_total") for m in run["final"]["metrics"]
    )
    engine = run["verifyd_after"]["engine_items"] - run["verifyd_before"]["engine_items"]
    rng = random.Random(run["seed"] ^ 0xC0FFEE)
    samples = gen["samples"]
    if len(samples) > REPLY_SAMPLE:
        longest = max(samples, key=lambda s: len(s["replies"]))
        samples = rng.sample(samples, REPLY_SAMPLE - 1) + [longest]
    bad_replies = 0
    for s in samples:
        want = state_machine.execute(s["operation"])
        got = state_machine.quorum_result(
            s["replies"], run["f"], run["n"], run["pubkeys"], ref.verify
        )
        own = all(r["client"] == s["client"] and r["timestamp"] == s["ts"] for r in s["replies"])
        bad_replies += not (own and got == want)
    probe = run["probe"]
    # The WAL, on every replica that executed each request itself (one that
    # caught up by state transfer skipped votes): both its votes of every
    # sequence number were logged, and it flushed with fsync no more rarely
    # than the configuration's file says. One flush covers the votes of one
    # pass over a batch of verdicts, so flushes are counted per verify batch
    # (steady over role and load), not per sequence number (it is not).
    own = [d for d in final if d["executed"] == executed[0]]
    votes_missing = sum(max(0, 2 * d["executed_upto"] - d["wal_appends"]) for d in own)
    fsyncs_per_batch = min(d["wal_fsyncs"] / max(1, d["verify_batches"]) for d in own)
    return [
        ("probe_verdicts_differing_from_reference", probe["mismatches"], "<=", 0),
        ("probe_rejects_due_in_window", probe["rejects_due_in_window"], ">=", 1),
        ("sampled_replies_compared", len(samples), ">=", 1),
        ("sampled_replies_failing_reference_quorum", bad_replies, "<=", 0),
        ("replies_with_bad_signature", gen["bad_signature"], "<=", 0),
        ("replies_with_wrong_result", gen["wrong_result"], "<=", 0),
        ("acknowledged_but_not_executed_by_f_plus_1", max(0, acked - executed[run["f"]]), "<=", 0),
        ("sequence_spread_over_replicas",
         max(d["executed_upto"] for d in final) - min(d["executed_upto"] for d in final), "<=", 0),
        ("distinct_chain_digests", len({d["chain_digest"] for d in final}), "<=", 1),
        ("views_above_zero", sum(d["view"] != 0 for d in final), "<=", 0),
        ("votes_missing_from_a_wal", votes_missing, "<=", 0),
        ("fewest_wal_fsyncs_per_verify_batch", fsyncs_per_batch, ">=",
         run["config"]["limits"]["wal_fsyncs_per_verify_batch_min"]),
        ("engine_items_minus_items_sent", engine - (replica_items + probe["items"]), "==", 0),
    ]


def fallbacks(run: dict) -> dict:
    final = run["final"]["status"]
    return {
        "verify_service_fallbacks": sum(d["verify_service_fallbacks"] for d in final),
        "verify_deadline_fired": sum(d["verify_deadline_fired"] for d in final),
        "verifyd_fallback_items": run["verifyd_after"]["fallback_items"]
        - run["verifyd_before"]["fallback_items"],
    }


_OPS = {"<=": lambda a, b: a <= b, ">=": lambda a, b: a >= b, "==": lambda a, b: a == b}


# -- one run --------------------------------------------------------------------------


def run_cell(
    workload: str, seed: int, seconds: float, trace: bool, *, t_start: float,
    require_tpu: bool = True, verifyd_wrapper=(), benchmark: Path = None, root: Path = None,
) -> dict:
    """The whole of one run; returns the result line as a dict. Raises
    BenchFailure where no result may be printed."""
    bench = load_benchmark(benchmark)
    loaded = load_cell(bench, workload, root)
    config, traffic = loaded["config"], loaded["traffic"]
    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir(parents=True)
    os.environ["TMPDIR"] = str(WORK)  # LocalCluster's WAL directory lands here
    tempfile.tempdir = None
    log(f"cell {workload}: seed {seed}, {seconds}s window, trace {int(trace)}, "
        f"{len(os.sched_getaffinity(0))} cores")
    children = Children()
    verifyd = None
    try:
        verifyd = Verifyd(WORK, config["verifyd"], children, verifyd_wrapper)
        from pbft_tpu import native

        native.build()  # overlaps verifyd's warm-up, as does the probe's signing
        probe = Probe(traffic["probe"], seed)
        ready = verifyd.wait_ready(require_tpu)
        warm = ready.get("warm_stats", {})
        log(f"verifyd ready: platform={ready['platform']} device_kind={ready['device_kind']!r} "
            f"devices={ready['devices_seen']} warmed={ready['warmed_shapes']} "
            f"compiled={warm.get('compiled')} cache_hits={warm.get('cache_hits')} "
            f"cache_dir={warm.get('cache_dir')} after {time.monotonic() - t_start:.1f}s")
        log("one launch of each shape at warm-up, ms: "
            f"{ {p['size']: round(1e3 * p['launch_s'], 3) for p in warm.get('per_shape', []) if 'launch_s' in p} }"
            f" on {ready.get('devices')} device(s); serving table {warm.get('serving_table')}; "
            f"chunk plan {warm.get('chunk_plan')}")
        if require_tpu:
            peaks = peaks_for(loaded["dir"], ready["platform"], ready["device_kind"])
            if ready["devices_seen"] < loaded["cell"]["chips"]:
                raise BenchFailure(
                    f"{ready['devices_seen']} chip(s), the cell asks for {loaded['cell']['chips']}"
                )
            if ready["warmed_shapes"] != config["ladder"]:
                raise BenchFailure(f"warmed {ready['warmed_shapes']}, file says {config['ladder']}")
        else:
            peaks = None
        run = serve_window(
            loaded, verifyd, probe, ready, seed=seed, seconds=seconds, trace=trace,
            children=children, work=WORK,
        )
    finally:
        alive = children.stop_all()
        if verifyd is not None:
            verifyd.close()
        if not os.environ.get("CHIPBENCH_KEEP_WORK"):
            shutil.rmtree(WORK, ignore_errors=True)
    if alive:
        raise BenchFailure(f"children still alive at the end: {alive}")
    run["setup_s"] = run["t0"] - t_start
    run["peaks"] = peaks
    fb = fallbacks(run)
    log(f"fallbacks: {fb}")
    if any(fb.values()):
        raise BenchFailure(f"the CPU answered under the chip's name: {fb}")
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, group, workload):
        value = read_metric(loaded["dir"], m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    gen = run["gen"]
    due_in = due_in_window(run)
    failed = sum(gen["done"][i] is None for i in due_in)
    comparisons = compare_run(run)
    correct = all(_OPS[op](value, limit) for _, value, op, limit in comparisons)
    device = {
        "platform": run["ready"]["platform"], "kind": run["ready"]["device_kind"],
        "count": run["ready"]["devices_seen"],
        "memory_peak_bytes": run["memory"]["memory_peak_bytes"],
    }
    line = {"correct": correct, "attempted": len(due_in), "failed": failed,
            "metrics": metrics, "device": device}
    if trace and run["trace"] is not None:
        import xplane

        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = xplane.breakdown(run["trace"])
        seen = run["trace"]["launches"]
        log(f"device time of a launch by the shape it ran at, in the slice, on {run['trace']['planes']} "
            f"of {run['trace']['devices']} device plane(s): " + ", ".join(
                f"{slots}: {1e3 * sum(v) / len(v):.2f} ms x{len(v)}"
                for slots, v in sorted(xplane.launches_by_shape(run["trace"], None).items())
            ) + f"; of {len(seen)} launches {sum(not x['slots'] for x in seen)} with no shape, "
            f"{sum(not x['spans'] for x in seen)} in no span, {sum(x['spans'] > 1 for x in seen)} in two, "
            f"{run['trace']['cut']} more left out because the trace's end cut them short; "
            "in order: " + " ".join(f"{x['slots']}:{1e3 * x['seconds']:.2f}" for x in seen))
    log(f"window: {len(gen['due'])} requests sent in all, {len(due_in)} due in the window, "
        f"{failed} of them failed, {gen['rejected']} refused by the gateway; "
        f"setup_s {run['setup_s']:.1f}; cpu seconds in the window {run['cpu_window']}")
    launch = stats.launch_stats(run["launches"])
    log(f"launches in the window: {launch.get('launches')} of {launch.get('items_per_launch', 0):.1f} "
        f"items, fill {launch.get('pad_fill', 0):.3f}, by rung {launch.get('rungs')}")
    for name, value, op, limit in comparisons:
        ok = "ok" if _OPS[op](value, limit) else "NOT OK"
        log(f"compare {name}: {value} (limit {op} {limit}) {ok}")
    line["compared"] = {
        name: {"value": value, "limit": f"{op} {limit}"} for name, value, op, limit in comparisons
    }
    line["_run"] = run
    return line
