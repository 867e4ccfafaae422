"""The persistent multi-chip verify service: own the accelerator, pay
compile once, shard every window. This module holds the engine (the one
way a served window reaches the chip), the daemon that joins it to
``service.py``'s dispatcher and the ``verifyd`` CLI: the one entry point
of the one daemon.

One long-lived process per host initializes the JAX backend ONCE,
AOT-compiles the sharded verify kernel for every fixed `_PAD_LADDER`
window shape at startup (``jax.jit(...).lower().compile()`` ahead of
first traffic, through JAX's persistent compile cache placed by
``utils/cache.configure_compile_cache``), and then serves the
128-byte-triple protocol from ``service.py`` for its whole lifetime —
batches from ALL colocated replicas coalesce into one XLA launch sharded
across every local device (``parallel/verifier.py``). A chip belongs to
one process: this one. Replicas and benches stay off JAX and dial it.

Readiness handshake: a request with item count 0 returns an 8-byte
status record (state warming|ready|cpu-only + device count + warmed
shape count); count 0xFFFFFFFF returns a length-prefixed JSON status
(platform, device kind, devices seen and in the mesh, per-shape compile
seconds, engine and fallback dispatch counts) for humans, the bench and
``chip_smoke.py``. Replicas (``core/verifier.cc`` RemoteVerifier) dial
with a SHORT connect deadline, consume the handshake, and fall back to
their native pool while the service is warming or gone: a cold
accelerator can never block consensus. Both ends count those fallbacks,
so a run that never reached the device shows it.

``--backend jax`` is the chip deployment: a warm-up failure, or a
backend that is not a TPU (unless ``JAX_PLATFORMS`` itself names cpu,
the test arm), ends the process non-zero. ``auto`` degrades to
``cpu-only`` instead, for chip-less deployments.

Which executable a window runs on is decided by what each one COSTS on the
device the engine has, not by size alone: warm-up launches every shape on
the all-pad window, keeps the least of a few timed launches as the shape's
``launch_s``, and :func:`serving_table` sends a window to a larger shape
where that one measured clearly cheaper (on a TPU v5e the 256-slot program
takes 5 ms and the 16- and 64-slot ones 42 ms). Where cost grows with size,
as on a CPU, that is the smallest shape that fits. Where it grows FASTER
than the size (the v5e's 4,096-slot program takes 53 ms, the 1,024-slot one
16), :func:`chunk_plan` runs the window as a few launches of the smaller
shapes instead (1,100 items on 1,024 + 256 slots). The same measurement
bounds how long a window waits for company: while it leaves room on the
last shape of its plan, the dispatcher keeps it open for at most one
``launch_s`` of that shape (:meth:`ShardedVerifyEngine.hold_s`), so
replicas' batches that arrive a few ms apart share a launch.

Host↔device pipeline: every window is staged as ONE ``(size, 128)`` uint8
block of the triples as they came off the wire (``crypto.batch.pad_rows``: a
copy of the shape's all-pad template and one assignment a request, from the
blocks the dispatcher's ``service.Window`` holds; nothing per item)
and handed, still on the host, to a precompiled executable: its call moves
the block in ONE async transfer against the batch sharding, cuts it into
``pub | msg | sig`` on the device and DONATES the input buffer (XLA reuses
the device memory window over window). With the two launch slots the daemon
gives its dispatcher (``service.DAEMON_INFLIGHT``) the service ships window
N+1 from a second launch thread while window N computes — the
double-buffered transfer/compute overlap, with verdict slicing per
connection untouched.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time
import warnings
from bisect import bisect_left
from itertools import combinations_with_replacement
from typing import Callable, List, Optional, Sequence, Tuple

# The readiness wire format (STATUS_* / STATE_* / pack_status /
# unpack_status) lives in service.py next to the protocol handler;
# re-exported here as the deployment-facing surface.
from .service import (  # noqa: F401 - re-exported API
    DAEMON_INFLIGHT,
    Item,
    STATE_CPU_ONLY,
    STATE_NAMES,
    STATE_READY,
    STATE_WARMING,
    STATUS_JSON_PROBE,
    STATUS_LEN,
    STATUS_MAGIC,
    STATUS_PROBE,
    STATUS_VERSION,
    VerifierService,
    Window,
    _recv_exact,
    as_rows,
    pack_status,
    unpack_status,
)
from ..utils.trace import current_span


# -- the accelerator-owning engine -------------------------------------------

# A larger shape takes a smaller one's windows only where it measured under
# a THIRD of the smaller one's time. Running on a larger shape is not free
# (a larger block to copy and move, fewer rows of real work a device), and
# ``launch_s`` is the least of a few launches read on the host's clock: on a
# loaded host two programs of equal cost have read 1.8x apart (tier-1 under
# eight-fold contention). The gap this is for is 8x
# (TPU v5e: 42 ms at 16 and 64 slots, 5.3 at 256, 5.5x with the host's
# staging on both sides). Equal costs, and costs inside the margin, keep the
# smaller shape, so wherever cost grows with size the table is the identity.
PROMOTE_MARGIN = 3.0


def serving_table(launch_s: dict) -> dict:
    """{shape: the shape that serves its windows}, from what one launch of
    each warmed shape cost (``{shape: seconds}``). From the largest shape
    down, a shape serves itself unless the shape that serves the next
    larger one measured cheaper by ``PROMOTE_MARGIN``: so one cheap shape
    takes every window under it, and none above it."""
    table: dict = {}
    above = None  # what serves the next larger shape
    for shape in sorted(launch_s, reverse=True):
        promote = (
            above is not None
            and launch_s[above] * PROMOTE_MARGIN < launch_s[shape]
        )
        above = table[shape] = above if promote else shape
    return dict(sorted(table.items()))


def serving_table_text(table: dict) -> str:
    """``16→256 64→256 …``: a window that fits 16 slots runs at 256 (and, of
    :func:`chunk_plan_words`, ``1025-1280→1024+256 …``)."""
    return " ".join(f"{fit}→{runs}" for fit, runs in table.items())


# A window runs as several launches of smaller shapes only where their
# ``launch_s`` add up to under the single fitting shape's by a QUARTER. The
# sum is what the chunks cost one after another on an idle device, so it is
# pessimistic (a chunk's staging hides behind the chunk before it), but it is
# made of readings of the host's clock. On the TPU v5e (7.1 / 15.8 / 53.3 ms
# at 256 / 1,024 / 4,096 slots, the same to 2% in every warm-up read) the
# covers that matter stand at 2.3x, 1.8x and 1.4x (1,024 + 256, 1,024 + 2 x
# 256, 2 x 1,024 + 256 slots against 4,096) and the ones that do not at 1.12x
# and 1.11x (3 x 1,024 against 4,096, 2 x 256 against 1,024): 1.25 lies
# midway between 1.4 and 1.12, a tenth from either, five times what a
# reading moves. Where cost is flat in the slots no cover comes under the
# margin and the plan is the one shape; where it is linear (a CPU: a pad
# slot is real work) a window splits wherever smaller shapes cover it with a
# fifth fewer slots.
SPLIT_MARGIN = 1.25

# More chunks than this are never worth their host work (a chunk is a pad,
# a transfer, a dispatch and a read-back in the service's one Python
# process), and the bound keeps the search a handful of sums.
MAX_CHUNKS = 4


def chunk_plan(n: int, launch_s: dict, serves: dict) -> tuple:
    """The shapes a window of ``n`` items runs at, largest first, from what
    one launch of each warmed shape cost and the serving table made of it.
    As a rule that is the ONE shape the table gives for the smallest that
    fits. Among the covers of ``n`` by two to ``MAX_CHUNKS`` shapes that
    serve themselves (so every chunk is where the table would send it), the
    one with the least summed ``launch_s`` (fewer chunks, then fewer slots,
    on a tie) takes its place where that sum is under the one shape's cost
    by ``SPLIT_MARGIN``. Beyond the largest shape: chunks of that shape, and
    the rest (1 to a full one) by the same rule."""
    if n <= 0 or not launch_s:
        return ()
    top = max(launch_s)
    if n > top:
        whole = (n - 1) // top
        return (top,) * whole + chunk_plan(n - whole * top, launch_s, serves)
    fit = min(s for s in launch_s if s >= n)
    one = serves.get(fit, fit)
    runs = sorted({serves.get(s, s) for s in launch_s}, reverse=True)
    best = None
    for k in range(2, MAX_CHUNKS + 1):
        for cover in combinations_with_replacement(runs, k):
            if sum(cover) >= n:
                key = (sum(launch_s[s] for s in cover), k, sum(cover))
                if best is None or key < best[0]:
                    best = (key, cover)
    if best is not None and best[0][0] * SPLIT_MARGIN < launch_s[one]:
        return best[1]
    return (one,)


def plan_table(launch_s: dict, serves: dict) -> list:
    """:func:`chunk_plan` for every window up to the largest shape, as
    ascending ``[(largest n, plan)]`` with neighbours that differ: a plan
    can change only where ``n`` passes the slots of some cover."""
    runs = {serves.get(s, s) for s in launch_s}
    edges = set(launch_s)
    for k in range(2, MAX_CHUNKS + 1):
        edges.update(map(sum, combinations_with_replacement(runs, k)))
    top = max(launch_s, default=0)
    table: list = []
    for n in sorted(e for e in edges if e <= top):
        plan = chunk_plan(n, launch_s, serves)
        if table and table[-1][1] == plan:
            table.pop()
        table.append((n, plan))
    return table


def chunk_plan_words(table: list) -> dict:
    """``{"1025-1280": "1024+256", …}``: the windows of a :func:`plan_table`
    that run as several launches, for the status JSON (empty where none
    does)."""
    words, lo = {}, 1
    for hi, plan in table:
        if len(plan) > 1:
            words[f"{lo}-{hi}"] = "+".join(map(str, plan))
        lo = hi + 1
    return words


class ShardedVerifyEngine:
    """Owns the JAX backend: one mesh over the host's local devices and one
    AOT-compiled, input-donating sharded verify executable per window shape.

    ``init_backend()`` touches the backend (platform, device kind, the
    devices JAX sees, the mesh); ``warm()`` then lowers and compiles every
    window shape — the once-per-deploy cost the daemon pays at startup,
    outside any request. JAX's persistent compile cache, keyed by the
    lowered module, makes a restart over unchanged kernels a cache hit
    and a changed kernel a miss. ``warm()`` also launches each shape on
    the all-pad window and times it: ``verify()`` pads a window to the
    shape :func:`serving_table` gives for the smallest one that fits, or
    runs it as the chunks :func:`chunk_plan` gives where those cost less.
    """

    def __init__(
        self,
        shapes: Optional[Sequence[int]] = None,
        devices: Optional[int] = None,
        kernel=None,
    ):
        if shapes is None:
            from ..crypto.batch import _PAD_LADDER

            shapes = _PAD_LADDER
        self._want_shapes = tuple(sorted(set(shapes)))
        self._want_devices = devices
        self._kernel = kernel
        self._lock = threading.Lock()
        self._mesh = None
        self._compiled: dict = {}  # padded size -> jax.stages.Compiled
        self._chips: dict = {}  # padded size -> devices its input is sharded over
        self._chains: dict = {}  # padded size -> "vmem" | "xla" (parallel.chains_of)
        self._launch_s: dict = {}  # padded size -> seconds, read at warm-up
        self._serves: dict = {}  # smallest fitting size -> size it runs at
        self._plans: list = []  # plan_table(): [(largest n, shapes run)]
        self.platform: Optional[str] = None
        self.device_kind: Optional[str] = None
        self.devices_seen = 0  # len(jax.devices())
        self.device_count = 0  # devices in the mesh
        self.stats: dict = {}

    # -- startup -------------------------------------------------------------

    def init_backend(self) -> None:
        """First backend touch: record what JAX runs on and build the mesh."""
        from ..utils.cache import configure_compile_cache

        cache_dir = configure_compile_cache()
        import jax

        from ..parallel import make_mesh

        with self._lock:
            seen = jax.devices()
            devs = jax.local_devices()
            if self._want_devices:
                devs = devs[: self._want_devices]
            self.platform = seen[0].platform
            self.device_kind = seen[0].device_kind
            self.devices_seen = len(seen)
            self.device_count = len(devs)
            self._mesh = make_mesh(devices=devs)
            self.stats = {"cache_dir": cache_dir}

    def warm(self) -> dict:
        """Precompile every window shape (``init_backend`` runs first if
        the caller has not).

        Returns (and stores in ``self.stats``) the warm-up accounting, as
        set-up facts: per shape the seconds spent, whether the persistent
        cache answered, the devices its input sharding spans and what one
        launch of it costs (``launch_s``, :meth:`_measure`) and how it runs
        its long multiply chains (``chains``: ``"vmem"`` or ``"xla"``, read
        from the executable's own HLO by ``parallel.chains_of``, not from the
        rule ``ed25519.chains_for`` that shaped it);
        ``cold_compile_s`` sums the shapes that traced+compiled,
        ``warm_load_s`` the shapes the cache answered; ``serving_table`` is
        :func:`serving_table` of every shape's ``launch_s`` and ``chunk_plan``
        the windows that run as several launches (:func:`chunk_plan_words`).
        """
        if self._mesh is None:
            self.init_backend()
        import jax

        from ..parallel import chains_of, lower_sharded

        hits: list = []

        def on_event(name: str, **_kw) -> None:
            if name == "/jax/compilation_cache/cache_hits":
                hits.append(name)

        jax.monitoring.register_event_listener(on_event)
        try:
            with self._lock:
                stats = dict(
                    self.stats,
                    shapes=[],
                    per_shape=[],
                    compiled=0,
                    cache_hits=0,
                    cold_compile_s=0.0,
                    warm_load_s=0.0,
                )
                for want in self._want_shapes:
                    size = self._round_to_mesh(want)
                    if size in self._compiled:
                        continue
                    hits.clear()
                    t0 = time.perf_counter()
                    with warnings.catch_warnings():
                        # Donation cannot alias the (B,128) input to the
                        # (B,) bool output, so XLA warns per shape; the
                        # donation still releases the staged input buffer
                        # eagerly, and the warning is pure noise here.
                        warnings.filterwarnings(
                            "ignore", message="Some donated buffers"
                        )
                        lowered = lower_sharded(
                            self._mesh, size, kernel=self._kernel
                        )
                        compiled = lowered.compile()
                    secs = time.perf_counter() - t0
                    hit = bool(hits)
                    stats["cache_hits" if hit else "compiled"] += 1
                    stats["warm_load_s" if hit else "cold_compile_s"] += secs
                    # What the executable itself says about placement —
                    # the devices its first input is sharded over and the
                    # rows each one holds — not what we asked for.
                    in_sharding = compiled.input_shardings[0][0]
                    launch_s = round(self._measure(size, compiled), 6)
                    chips = sorted(d.id for d in in_sharding.device_set)
                    rows = in_sharding.shard_shape((size, 128))[0]
                    chains = chains_of(compiled, lowered)
                    stats["per_shape"].append(
                        {
                            "size": size,
                            "seconds": round(secs, 3),
                            "cache_hit": hit,
                            "devices": chips,
                            "rows_per_device": rows,
                            "launch_s": launch_s,
                            "chains": chains,
                        }
                    )
                    self._launch_s[size] = launch_s
                    self._chips[size] = len(chips)
                    self._chains[size] = chains
                    self._compiled[size] = compiled
                    stats["shapes"].append(size)
                stats.update(self._route(self._launch_s))
                stats["warm_load_s"] = round(stats["warm_load_s"], 3)
                stats["cold_compile_s"] = round(stats["cold_compile_s"], 3)
                self.stats = stats
        finally:
            jax.monitoring.unregister_event_listener(on_event)
        return stats

    def _route(self, launch_s: dict) -> dict:
        """Make the serving table and the chunk plans of what one launch of
        each shape cost; returns both as ``warm()`` reports them."""
        self._launch_s = dict(launch_s)
        self._serves = serving_table(self._launch_s)
        self._plans = plan_table(self._launch_s, self._serves)
        return {
            # JSON has no integer keys.
            "serving_table": {str(fit): runs for fit, runs in self._serves.items()},
            "chunk_plan": chunk_plan_words(self._plans),
        }

    # Timed launches a shape at warm-up, after one that is not timed.
    WARM_LAUNCHES = 3

    def _measure(self, size: int, compiled) -> float:
        """What one launch of ``compiled`` costs here, in seconds: the
        all-pad window through the path ``verify()`` takes (``pad_rows``,
        the executable on the host block, ``np.asarray``), once untimed and
        then the least of ``WARM_LAUNCHES`` timed ones, with nothing else in
        flight (the daemon serves from its fallback until ``warm()``
        returns). Every
        slot holds the known-good triple, so the engine's own kernel has to
        answer True in every slot of every launch: a self-test of each
        executable, and a failure of warm-up like a compile failure. (A
        stand-in kernel decides by its own rule, which the pad triple need
        not satisfy.)"""
        import numpy as np

        from ..crypto.batch import pad_rows

        took = []
        for _ in range(1 + self.WARM_LAUNCHES):
            t0 = time.perf_counter()
            verdicts = np.asarray(compiled(pad_rows([], size)[0]))
            took.append(time.perf_counter() - t0)
            if self._kernel is None and not verdicts.all():
                raise RuntimeError(
                    f"warm-up self-test: the {size}-slot executable rejected "
                    f"the known-good pad triple in "
                    f"{size - int(verdicts.sum())} of {size} slots"
                )
        return min(took[1:])

    def _plan(self, n: int) -> tuple:
        """:func:`chunk_plan` of ``n`` items, looked up in the table made at
        warm-up (nothing before it)."""
        if not self._plans or n <= 0:
            return ()
        top = self._plans[-1][0]
        whole = (n - 1) // top
        at = bisect_left(self._plans, (n - whole * top,))
        return (top,) * whole + self._plans[at][1]

    def hold_s(self, n: int) -> float:
        """How long a window of ``n`` items may be held open for more, in
        seconds: one launch of the last shape of its plan, while that shape
        has room (the chunks before it are full). Below a shape's size an
        extra item costs the device nothing and a launch of its own costs
        it ``launch_s``, so company is worth waiting for, but never longer
        than the launch the wait would save; a window that fills its plan
        goes at once. 0 before warm-up has timed the shapes."""
        plan = self._plan(n)
        if not plan or n >= sum(plan):
            return 0.0
        return self._launch_s.get(plan[-1], 0.0)

    def _round_to_mesh(self, size: int) -> int:
        d = max(1, self.device_count)
        return ((size + d - 1) // d) * d

    @property
    def warmed_sizes(self) -> Tuple[int, ...]:
        return tuple(sorted(self._compiled))

    # -- serving -------------------------------------------------------------

    # The steps of a chunk, in order; each is timed and is a profiler span.
    # ``put_s`` is 0 since the executable takes the host block (the transfer
    # is inside its call, so inside ``dispatch_s``); the name stays on every
    # line because the launch lines' readers sum ``pad_s + put_s + dispatch_s``.
    STEPS = ("pad_s", "put_s", "dispatch_s", "wait_s", "unpack_s")

    def verify(self, items: Sequence[Item]):
        """Pad to a warmed window shape (one block, ``pad_rows``: one slice
        assignment a request of the dispatcher's ``Window``, two for one that
        straddles a chunk's edge; a plain list of triples is packed into a
        window here, and answered with a list), call the
        precompiled executable on it (which moves the block in ONE async
        transfer against the batch sharding and launches), read back.
        The shape is what the serving table gives for the smallest one that
        fits: that one, or a larger one that warm-up measured clearly
        cheaper (``promoted`` counts such chunks; the pad slots verify True
        and are sliced off, so the verdicts are the same). Where
        :func:`chunk_plan` says that a few launches of smaller shapes cost
        less, and beyond the top of the ladder, the window runs as those
        chunks (``chunks`` of them, ``split`` 1): every chunk is staged and
        dispatched before the first verdict is read back, so a chunk's
        staging hides behind the one before it on the device. The service
        never compiles a new shape at runtime. Verdicts are bit-identical
        to the single-device and CPU paths (pinned in tests/test_parallel
        and tests/test_service_coalesce), and leave as ONE bool array.

        The five steps of every chunk are timed (summed over chunks) into
        the caller's ``utils.trace.current_span()``, where one is open —
        the service's ``verify_batch`` line — and each is a profiler
        annotation (``verifyd.<step>``; free while no trace runs). Beside
        them the line says over how many chips the window's executables are
        sharded (``devices``: what each executable's input sharding said at
        warm-up, not what was asked for) and how many rows a chip its
        thinnest chunk gave (``rows_per_chip``: the number to set against
        the rows under which the kernel runs in its slow regime), and how
        many of the window's items reached the executables as the rows they
        came off the wire as (``block_items``: all of a ``Window``'s, none
        of a list's)."""
        mark = time.monotonic()
        if not len(items):
            return []
        listed = not isinstance(items, Window)
        window = Window([as_rows(items)]) if listed else items
        plan = self._plan(len(window))
        if not plan:
            raise RuntimeError("engine not warmed")
        import numpy as np
        import jax

        from ..crypto.batch import pad_rows

        step = jax.profiler.TraceAnnotation
        secs = dict.fromkeys(self.STEPS, 0.0)

        def took(name: str) -> float:
            """Charge ``name`` with the time since the step before it."""
            nonlocal mark
            now = time.monotonic()
            secs[name] += now - mark
            mark = now
            return now

        promoted = off = 0
        t_dev = None
        flying = []  # a chunk dispatched: (verdicts to come, its first item, its items)
        for size in plan:
            with step("verifyd.pad"):
                block, n = pad_rows(window.rows(off, off + size), size)
            promoted += size != min(s for s in self._compiled if s >= n)
            pad = took("pad_s")
            if t_dev is None:
                t_dev = pad  # the first dispatch
            # The executable's call is the chunk's ONE host->device transfer
            # and its launch, both async; with the service's overlapped
            # launches (DAEMON_INFLIGHT) window N+1 stages here while window
            # N computes. The donated input lets XLA reuse the same device
            # memory for every window of this shape.
            with step("verifyd.dispatch"):  # returns once enqueued
                flying.append((self._compiled[size](block), off, n))
            took("dispatch_s")
            off += size
        out = np.empty(len(window), bool)
        while flying:
            result, at, n = flying.pop(0)
            # Behind the other launch in flight, then the device, then the
            # read-back: np.asarray returns when the verdicts are here.
            with step("verifyd.wait"):
                verdicts = np.asarray(result)
            took("wait_s")
            with step("verifyd.unpack"):
                out[at : at + n] = verdicts[:n]
                # Dropping the device buffer takes its time too (~0.1 ms):
                # here, so that it is timed, not at the function's exit.
                del result, verdicts
            took("unpack_s")
        span = current_span()
        if span is not None:
            span.update({k: round(v, 6) for k, v in secs.items()})
            thinnest = min(plan)
            chips = self._chips[thinnest]
            span.update(
                rung=sum(plan),
                promoted=promoted,
                chunks=len(plan),
                split=int(len(plan) > 1),
                t_dev=round(t_dev, 6),
                devices=chips,
                rows_per_chip=thinnest // chips,
                fused=round(
                    sum(s for s in plan if self._chains.get(s) == "vmem") / sum(plan), 4
                ),
                block_items=0 if listed else len(window),
            )
        return out.tolist() if listed else out

    def memory_peak_bytes(self) -> Optional[int]:
        """The fullest local device's ``peak_bytes_in_use``; None before the
        backend is up and where the backend reports no memory statistics."""
        peaks = [
            (stats or {}).get("peak_bytes_in_use") for stats in self.memory_stats() or []
        ]
        peaks = [int(p) for p in peaks if p is not None]
        return max(peaks) if peaks else None

    def memory_stats(self) -> Optional[list]:
        """Every local device's ``memory_stats()`` as it stands: what the
        service's stall record carries of the device (``stall_probe``)."""
        if self._mesh is None:
            return None
        import jax

        return [dev.memory_stats() for dev in jax.local_devices()]


# -- the daemon --------------------------------------------------------------


def _jax_platforms_names_cpu() -> bool:
    return "cpu" in os.environ.get("JAX_PLATFORMS", "").lower().split(",")


class VerifyServiceDaemon:
    """A :class:`~pbft_tpu.net.service.VerifierService` that owns its
    accelerator lifecycle: starts in ``warming`` (all traffic served by the
    native-pool fallback), warms the :class:`ShardedVerifyEngine` on a
    background thread, and flips to ``ready``. What a failed warm-up does
    depends on ``backend``: ``auto`` flips to ``cpu-only`` (chip-less
    deployments keep coalescing on the native pool); ``jax`` — the chip
    deployment — records ``fatal_error`` and the CLI exits non-zero,
    and so does a backend that is not a TPU unless ``JAX_PLATFORMS``
    itself names cpu. ``native``/``cpu`` never touch JAX. The status
    counts engine dispatches apart from fallback dispatches."""

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        unix_path: Optional[str] = None,
        backend: str = "auto",
        devices: Optional[int] = None,
        trace_path: Optional[str] = None,
        metrics_port: Optional[int] = None,
        engine: Optional[ShardedVerifyEngine] = None,
        fallback: Optional[Callable[[List[Item]], List[bool]]] = None,
    ):
        if backend not in ("auto", "jax", "native", "cpu"):
            raise ValueError(f"unknown backend {backend!r}")
        self.backend = backend
        self._t0 = time.monotonic()
        self._state = STATE_WARMING
        self._state_lock = threading.Lock()
        self._warm_error: Optional[str] = None
        self.fatal_error: Optional[str] = None
        self._warm_thread: Optional[threading.Thread] = None
        self._counts_lock = threading.Lock()
        self.engine_launches = 0
        self.engine_items = 0
        self.fallback_launches = 0
        self.fallback_items = 0
        self.engine = engine
        if engine is None and backend in ("auto", "jax"):
            self.engine = ShardedVerifyEngine(devices=devices)
        if fallback is None:
            if backend == "cpu":
                from .service import cpu_backend as fallback
            else:
                from ..consensus.replica import host_batch_verify as fallback
        self._fallback = fallback
        self.service = VerifierService(
            host=host,
            port=port,
            unix_path=unix_path,
            backend=self._dispatch,
            trace_path=trace_path,
            inflight=DAEMON_INFLIGHT,
            metrics_port=metrics_port,
            status_provider=self._status,
            status_json_provider=self.status_json,
        )
        if self.engine is not None:
            self.service.stall_probe = getattr(self.engine, "memory_stats", None)
        if self.service.metrics_registry.enabled:
            # The warm/cold compile gauges exist from the first scrape
            # (service.py's preregister only covers its own emitter set).
            self.service.metrics_registry.preregister("verify_service.py")

    # -- state machine -------------------------------------------------------

    @property
    def state(self) -> int:
        with self._state_lock:
            return self._state

    @property
    def state_name(self) -> str:
        return STATE_NAMES[self.state]

    @property
    def address(self) -> str:
        return self.service.address

    def _set_state(self, state: int) -> None:
        with self._state_lock:
            self._state = state

    def _status(self) -> Tuple[int, int, int]:
        eng = self.engine
        return (
            self.state,
            eng.device_count if eng else 0,
            len(eng.warmed_sizes) if eng else 0,
        )

    def status_json(self) -> dict:
        eng = self.engine
        with self._counts_lock:
            counts = {
                "engine_launches": self.engine_launches,
                "engine_items": self.engine_items,
                "fallback_launches": self.fallback_launches,
                "fallback_items": self.fallback_items,
            }
        out = {
            "state": self.state_name,
            # None until the backend has been touched (and always for
            # native/cpu): a reader must not mistake "unknown" for a chip.
            "platform": eng.platform if eng else None,
            "device_kind": eng.device_kind if eng else None,
            "devices_seen": eng.devices_seen if eng else 0,
            "devices": eng.device_count if eng else 0,
            "warmed_shapes": list(eng.warmed_sizes) if eng else [],
            "backend": self.backend,
            "uptime_s": round(time.monotonic() - self._t0, 3),
            "requests": self.service.requests,
            **counts,
        }
        # A stall without --trace: where launches spend their time, the
        # slowest one with the step that held it, the device's peak memory.
        out.update(self.service.launch_status())
        out["memory_peak_bytes"] = eng.memory_peak_bytes() if eng else None
        if eng and eng.stats:
            out["warm_stats"] = eng.stats
        if self._warm_error:
            out["warm_error"] = self._warm_error
        return out

    # -- serving -------------------------------------------------------------

    def _dispatch(self, items: Sequence[Item]):
        """The service backend: the warmed sharded engine when ready, the
        native-pool fallback otherwise — a request never waits on warmup.
        Counted apart, after the verdicts exist, so ``engine_items`` is
        what the device really verified."""
        if self.state == STATE_READY:
            verdicts = self.engine.verify(items)
            with self._counts_lock:
                self.engine_launches += 1
                self.engine_items += len(items)
            return verdicts
        verdicts = self._fallback(items)
        with self._counts_lock:
            self.fallback_launches += 1
            self.fallback_items += len(items)
        return verdicts

    def _warm(self) -> None:
        try:
            self.engine.init_backend()
            if (
                self.backend == "jax"
                and self.engine.platform != "tpu"
                and not _jax_platforms_names_cpu()
            ):
                raise RuntimeError(
                    f"--backend jax needs a TPU but JAX runs on "
                    f"{self.engine.platform!r} ({self.engine.device_kind}); "
                    f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}"
                )
            stats = self.engine.warm()
        except Exception as e:  # noqa: BLE001 - any backend failure
            self._warm_error = f"{type(e).__name__}: {e}"
            if self.backend == "jax":
                self.fatal_error = self._warm_error
            else:
                self._set_state(STATE_CPU_ONLY)
            return
        reg = self.service.metrics_registry
        if reg.enabled:
            reg.gauge("pbft_verify_service_cold_compile_seconds").set(
                stats["cold_compile_s"]
            )
            reg.gauge("pbft_verify_service_warm_compile_seconds").set(
                stats["warm_load_s"]
            )
        # From here on a window that leaves room on its shape waits for
        # company, at most one launch of that shape (``hold_s``).
        self.service.hold_s = getattr(self.engine, "hold_s", None)
        self._set_state(STATE_READY)

    def start(self, wait_ready: bool = False, timeout: float = 900.0):
        self.service.start()
        if self.engine is None:
            self._set_state(STATE_CPU_ONLY)
            return self
        self._warm_thread = threading.Thread(target=self._warm, daemon=True)
        self._warm_thread.start()
        if wait_ready:
            self._warm_thread.join(timeout)
        return self

    def stop(self) -> None:
        self.service.stop()


# -- the replica-side client -------------------------------------------------


def _dial(target: str, timeout: float) -> socket.socket:
    if target.startswith("/"):
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        sock.settimeout(timeout)
        sock.connect(target)
        return sock
    host, port = target.rsplit(":", 1)
    sock = socket.create_connection((host, int(port)), timeout=timeout)
    # Socket-option discipline (ISSUE 10): every TCP dial sets
    # TCP_NODELAY — a 4-byte verify header must not sit in a Nagle stall.
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return sock


def probe_status(
    target: str, timeout: float = 0.5
) -> Optional[Tuple[int, int, int]]:
    """One short-deadline status probe: (state, devices, warmed) or None
    when the service is unreachable (or pre-handshake legacy: state READY
    with devices/warmed unknown is NOT inferred here — callers decide)."""
    try:
        with _dial(target, timeout) as sock:
            sock.sendall(STATUS_PROBE.to_bytes(4, "big"))
            return unpack_status(_recv_exact(sock, STATUS_LEN))
    except (OSError, ConnectionError, ValueError):
        return None


def probe_status_json(target: str, timeout: float = 2.0) -> Optional[dict]:
    """The JSON status (state, devices, warm stats …), or None."""
    try:
        with _dial(target, timeout) as sock:
            sock.sendall(STATUS_JSON_PROBE.to_bytes(4, "big"))
            n = int.from_bytes(_recv_exact(sock, 4), "big")
            if n > 1 << 20:
                return None
            return json.loads(_recv_exact(sock, n).decode())
    except (OSError, ConnectionError, ValueError):
        return None


class VerifydNotReady(RuntimeError):
    """No verify service ready on a TPU (the message says what was found)."""


def spawn_verifyd(
    args: Sequence[str] = ("--backend", "jax"), stdout=None, stderr=None
):
    """Start ``scripts/verifyd.py`` on a free loopback port as the process
    that owns the chip. Returns (Popen, "127.0.0.1:port"). The caller — a
    bench or smoke parent that itself stays off JAX — stops it."""
    import subprocess

    from .launcher import free_ports

    port = free_ports(1)[0]
    script = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        "scripts",
        "verifyd.py",
    )
    proc = subprocess.Popen(
        [sys.executable, script, "--port", str(port), *args],
        stdout=stdout,
        stderr=stderr,
    )
    return proc, f"127.0.0.1:{port}"


def stop_child(proc, grace_s: float = 15.0) -> None:
    """SIGTERM, wait, SIGKILL: the parent's half of "stops every process
    it starts" (``proc`` may be None or already gone)."""
    import subprocess

    if proc is None or proc.poll() is not None:
        return
    proc.terminate()
    try:
        proc.wait(timeout=grace_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


def wait_for_tpu_service(target: str, proc=None, budget_s: float = 900.0) -> dict:
    """Poll ``target`` until it reports ``ready`` on platform ``tpu`` and
    return that status. Raises :class:`VerifydNotReady` as soon as the
    answer cannot become yes: ``proc`` (the daemon, if we started it)
    exited, the backend came up on another platform, the state is
    ``cpu-only``, or ``budget_s`` ran out. Never settles for a CPU."""
    deadline = time.monotonic() + budget_s
    status = None
    while time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            raise VerifydNotReady(
                f"verifyd exited with code {proc.returncode} before ready "
                f"(last status: {status})"
            )
        status = probe_status_json(target, timeout=2.0)
        if status is not None:
            platform = status.get("platform")
            if platform is not None and platform != "tpu":
                raise VerifydNotReady(
                    f"no TPU: verify service at {target} runs on platform "
                    f"{platform!r} ({status.get('device_kind')})"
                )
            if status.get("state") == "cpu-only":
                raise VerifydNotReady(
                    f"no TPU: verify service at {target} is cpu-only "
                    f"({status.get('warm_error') or status.get('backend')})"
                )
            if status.get("state") == "ready" and platform == "tpu":
                return status
        time.sleep(0.5)
    raise VerifydNotReady(
        f"verify service at {target} not ready on a TPU after "
        f"{budget_s:.0f}s (last status: {status})"
    )


def main(
    argv: Optional[List[str]] = None,
    engine: Optional[ShardedVerifyEngine] = None,
) -> None:
    """The verifyd CLI (scripts/verifyd.py is a thin path-setup wrapper).
    ``engine`` substitutes the accelerator engine (tests)."""
    import argparse

    parser = argparse.ArgumentParser(
        description="persistent multi-chip verify service daemon",
    )
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=7600)
    parser.add_argument("--unix", default=None, help="unix socket path instead of TCP")
    parser.add_argument(
        "--backend",
        default="auto",
        choices=["auto", "jax", "native", "cpu"],
        help="jax = the chip deployment: warm the sharded engine and EXIT "
        "non-zero if warm-up fails or the backend is not a TPU (unless "
        "JAX_PLATFORMS names cpu); auto = same engine but degrade to "
        "cpu-only on failure; native/cpu skip JAX entirely (state "
        "cpu-only). The native pool serves while the engine warms.",
    )
    parser.add_argument(
        "--devices",
        type=int,
        default=None,
        help="shard windows over this many local devices (default: all)",
    )
    parser.add_argument("--trace", default=None)
    parser.add_argument("--metrics-port", type=int, default=None)
    parser.add_argument(
        "--wait-ready",
        action="store_true",
        help="block until warmup finishes before announcing readiness "
        "on stdout (the socket still answers status probes meanwhile)",
    )
    args = parser.parse_args(argv)
    daemon = VerifyServiceDaemon(
        host=args.host,
        port=args.port,
        unix_path=args.unix,
        backend=args.backend,
        devices=args.devices,
        trace_path=args.trace,
        metrics_port=args.metrics_port,
        engine=engine,
    )
    daemon.start(wait_ready=args.wait_ready)
    print(
        json.dumps(
            {
                "ev": "verify_service_listening",
                "addr": daemon.address,
                **daemon.status_json(),
            }
        ),
        flush=True,
    )
    try:
        state = daemon.state
        while daemon.fatal_error is None:
            if daemon.state != state:
                state = daemon.state
                print(json.dumps(daemon.status_json()), flush=True)
            time.sleep(0.25)
    except KeyboardInterrupt:  # pragma: no cover - operator stop
        daemon.stop()
        return
    # --backend jax and no usable chip: never serve on as a CPU service.
    print(f"verifyd: fatal: {daemon.fatal_error}", file=sys.stderr, flush=True)
    daemon.stop()
    sys.exit(1)


if __name__ == "__main__":  # pragma: no cover - CLI entry
    main()
