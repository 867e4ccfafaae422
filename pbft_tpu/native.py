"""ctypes bindings to the C++ core (core/ -> libpbftcore.so).

The native library provides the CPU verifier backend (the control arm of the
CPU-vs-TPU A/B) plus Blake2b/SHA-512/Ed25519 primitives, all equivalence-
tested against the Python oracle and the JAX kernels. pybind11 is not in this
environment; the C ABI in core/capi.cc is the binding surface.
"""

from __future__ import annotations

import contextlib
import ctypes
import fcntl
import hashlib
import json
import os
import shutil
import subprocess
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

_REPO_ROOT = Path(__file__).resolve().parent.parent
_BUILD_DIR = _REPO_ROOT / "build-core"
_LIB_PATH = _BUILD_DIR / "libpbftcore.so"
# What a current build directory holds; LocalCluster needs pbftd, the
# ctest wrapper needs core_test.
_ARTIFACTS = ("libpbftcore.so", "pbftd", "core_test")
_STAMP = ".source-stamp"

_lib: Optional[ctypes.CDLL] = None

# Library sources in core/CMakeLists.txt order; pbftd.cc / core_test.cc
# link against the shared library.
_LIB_SOURCES = [
    "blake2b.cc", "sha512.cc", "ed25519.cc", "json.cc", "messages.cc",
    "metrics.cc", "flight.cc", "wal.cc", "replica.cc", "verifier.cc",
    "verify_pool.cc",
    "secure.cc", "net.cc", "net_shard.cc", "discovery.cc", "capi.cc",
]


class NativeToolchainMissing(RuntimeError):
    """No C++ compiler on this machine: the native core cannot exist here
    (the one case ``available()`` answers False for)."""


class NativeBuildError(RuntimeError):
    """The compiler ran and failed; the message carries its output."""


def _run_build_step(cmd: List[str]) -> None:
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise NativeBuildError(
            f"native core build failed (exit {proc.returncode}): "
            f"{' '.join(cmd)}\n{proc.stdout[-4000:]}{proc.stderr[-8000:]}"
        )


def _build_direct() -> None:
    """Fallback build without cmake/ninja: drive g++ directly (same flags
    as the CMake Release config). Keeps the native arm usable on stripped
    containers where only a compiler is present."""
    cxx = shutil.which("g++") or shutil.which("c++") or shutil.which("clang++")
    if cxx is None:
        raise NativeToolchainMissing("no C++ compiler found for the native core")
    _BUILD_DIR.mkdir(exist_ok=True)
    core = _REPO_ROOT / "core"
    # Strict by default, like the CMake STRICT option: warnings fail the
    # build. PBFT_CORE_NO_WERROR=1 is the escape hatch for toolchains
    # whose headers trip -Wextra (mirrors cmake -DSTRICT=OFF).
    common = ["-O2", "-std=c++17", "-Wall", "-Wextra", "-pthread"]
    if not os.environ.get("PBFT_CORE_NO_WERROR"):
        common.append("-Werror")
    _run_build_step(
        [cxx, *common, "-fPIC", "-shared", "-o", str(_BUILD_DIR / "libpbftcore.so")]
        + [str(core / s) for s in _LIB_SOURCES]
    )
    for exe, src in (("pbftd", "pbftd.cc"), ("core_test", "core_test.cc")):
        _run_build_step(
            [cxx, *common, "-o", str(_BUILD_DIR / exe), str(core / src),
             "-L", str(_BUILD_DIR), "-lpbftcore", "-Wl,-rpath,$ORIGIN"]
        )


def _build_cmake() -> None:
    core = _REPO_ROOT / "core"
    cache = _BUILD_DIR / "CMakeCache.txt"
    if cache.exists():
        # A build directory configured for another checkout (a copied
        # tree) is not ours to update: start over.
        text = cache.read_text(errors="replace")
        if (
            f"CMAKE_HOME_DIRECTORY:INTERNAL={core}\n" not in text
            or f"CMAKE_CACHEFILE_DIR:INTERNAL={_BUILD_DIR}\n" not in text
        ):
            shutil.rmtree(_BUILD_DIR)
    _run_build_step(
        ["cmake", "-S", str(core), "-B", str(_BUILD_DIR), "-G", "Ninja"]
    )
    _run_build_step(["cmake", "--build", str(_BUILD_DIR)])


def _source_stamp() -> str:
    """Digest of everything the binaries are made from: every file under
    core/, the checkout's path (cmake bakes it into the artifacts' run
    paths, so a copied tree is not current) and the -Werror lever."""
    h = hashlib.sha256()
    h.update(str(_REPO_ROOT).encode())
    h.update(b"no-werror" if os.environ.get("PBFT_CORE_NO_WERROR") else b"strict")
    for path in sorted((_REPO_ROOT / "core").iterdir()):
        if path.is_file():
            h.update(path.name.encode() + b"\0")
            h.update(path.read_bytes())
    return h.hexdigest()


def _is_current(stamp: str) -> bool:
    try:
        recorded = (_BUILD_DIR / _STAMP).read_text()
    except OSError:
        return False
    return recorded == stamp and all(
        (_BUILD_DIR / name).exists() for name in _ARTIFACTS
    )


@contextlib.contextmanager
def _build_lock():
    """One builder at a time across processes (a cluster's replicas and
    the test runner all call build() at start-up)."""
    # The lock rides on the core/ directory itself: nothing to create,
    # and it survives a wipe of the build directory.
    fd = os.open(_REPO_ROOT / "core", os.O_RDONLY)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        yield
    finally:
        os.close(fd)


def build(force: bool = False) -> Path:
    """Make build-core/ current for the sources as they are NOW and return
    the library path. Current means: the recorded source stamp matches
    core/* and this checkout's path, and the library, pbftd and core_test
    all exist — an existing .so proves nothing. Otherwise rebuild with
    cmake+ninja (direct g++ when either is missing). A failed build raises
    NativeBuildError with the compiler's output."""
    stamp = _source_stamp()
    with _build_lock():
        if force or not _is_current(stamp):
            if shutil.which("cmake") is None or shutil.which("ninja") is None:
                _build_direct()
            else:
                _build_cmake()
            (_BUILD_DIR / _STAMP).write_text(stamp)
    return _LIB_PATH


def lib() -> ctypes.CDLL:
    global _lib
    if _lib is None:
        path = build()
        _lib = ctypes.CDLL(str(path))
        _lib.pbft_ed25519_verify.restype = ctypes.c_int
    return _lib


def available() -> bool:
    """False only where there is no C++ toolchain. A build that FAILS is
    not "unavailable": it raises, so nothing quietly swaps in the Python
    oracle for a broken core."""
    try:
        lib()
        return True
    except NativeToolchainMissing:
        return False


def blake2b(data: bytes, digest_size: int = 32) -> bytes:
    out = ctypes.create_string_buffer(digest_size)
    lib().pbft_blake2b(out, digest_size, data, len(data))
    return out.raw


def sha512(data: bytes) -> bytes:
    out = ctypes.create_string_buffer(64)
    lib().pbft_sha512(out, data, len(data))
    return out.raw


def public_key(seed: bytes) -> bytes:
    out = ctypes.create_string_buffer(32)
    lib().pbft_ed25519_public_key(out, seed)
    return out.raw


def sign(seed: bytes, msg: bytes) -> bytes:
    out = ctypes.create_string_buffer(64)
    lib().pbft_ed25519_sign(out, seed, msg, len(msg))
    return out.raw


def verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    if len(pub) != 32 or len(sig) != 64:
        return False
    return bool(lib().pbft_ed25519_verify(pub, msg, len(msg), sig))


def blake2b_keyed(key: bytes, data: bytes, digest_size: int = 32) -> bytes:
    out = ctypes.create_string_buffer(digest_size)
    lib().pbft_blake2b_keyed(out, digest_size, key, len(key), data, len(data))
    return out.raw


def dh_public(secret: bytes) -> bytes:
    out = ctypes.create_string_buffer(32)
    lib().pbft_dh_public(out, secret)
    return out.raw


def dh_shared(secret: bytes, peer_pub: bytes) -> Optional[bytes]:
    out = ctypes.create_string_buffer(32)
    ok = lib().pbft_dh_shared(out, secret, peer_pub)
    return out.raw if ok else None


def aead_seal(key: bytes, ctr: int, plaintext: bytes) -> bytes:
    out = ctypes.create_string_buffer(len(plaintext) + 16)
    lib().pbft_aead_seal(key, ctypes.c_uint64(ctr), plaintext, len(plaintext), out)
    return out.raw


def aead_open(key: bytes, ctr: int, sealed: bytes) -> Optional[bytes]:
    out = ctypes.create_string_buffer(max(len(sealed), 1))
    fn = lib().pbft_aead_open
    fn.restype = ctypes.c_long
    n = fn(key, ctypes.c_uint64(ctr), sealed, len(sealed), out)
    return out.raw[:n] if n >= 0 else None


def verify_batch(items: Sequence[Tuple[bytes, bytes, bytes]]) -> List[bool]:
    """Native batch verify over (pub32, msg32, sig64) triples — the CPU
    control arm with the same call shape as crypto.batch.verify_many.
    Dispatched through the native verify pool (core/verify_pool.cc); width
    is set_verify_threads (default: hardware concurrency)."""
    n = len(items)
    if n == 0:
        return []
    pubs = b"".join(i[0] for i in items)
    msgs = b"".join(i[1] for i in items)
    sigs = b"".join(i[2] for i in items)
    out = ctypes.create_string_buffer(n)
    lib().pbft_ed25519_verify_batch(pubs, msgs, sigs, out, n)
    return [b == 1 for b in out.raw]


def set_verify_threads(threads: int) -> None:
    """Reconfigure the native verify pool width (0 = hardware
    concurrency). Tears down the existing pool; call between batches."""
    lib().pbft_set_verify_threads(ctypes.c_int(threads))


def verify_threads() -> int:
    """The native verify pool's actual width (creates the pool)."""
    fn = lib().pbft_verify_threads
    fn.restype = ctypes.c_int
    return fn()


def verify_pool_stats() -> dict:
    """Lifetime pool counters: threads, batches, windows, items, busy/wall
    seconds, utilization, last queue depth / window items."""
    fn = lib().pbft_verify_pool_stats_json
    fn.restype = ctypes.c_size_t
    buf = ctypes.create_string_buffer(512)
    n = fn(buf, len(buf))
    return json.loads(buf.raw[:n].decode())


def force_entropy_exhaustion(on: bool) -> None:
    """TEST hook: simulate entropy exhaustion so the RLC fast path
    disables and windows verify per-item (ADVICE round-5 regression)."""
    lib().pbft_test_force_entropy_exhaustion(ctypes.c_int(1 if on else 0))


def _sc_call(name: str, *operands: Tuple[int, int]) -> int:
    """One scalar test hook on (value, width in bytes) operands."""
    out = ctypes.create_string_buffer(32)
    getattr(lib(), name)(out, *(v.to_bytes(n, "little") for v, n in operands))
    return int.from_bytes(out.raw, "little")


def sc_reduce512(x: int) -> int:
    """TEST hook: a 512-bit value mod L through the native reduction."""
    return _sc_call("pbft_test_sc_reduce512", (x, 64))


def sc_muladd(a: int, b: int, c: int) -> int:
    """TEST hook: (a*b + c) mod L, any 256-bit operands (signing's form)."""
    return _sc_call("pbft_test_sc_muladd", (a, 32), (b, 32), (c, 32))


def sc_muladd128(a: int, b: int, c: int) -> int:
    """TEST hook: (a*b + c) mod L with a < 2^128 (the batch coefficients)."""
    return _sc_call("pbft_test_sc_muladd128", (a, 16), (b, 32), (c, 32))


def sc_add(a: int, b: int) -> int:
    """TEST hook: (a + b) mod L for a, b < L."""
    return _sc_call("pbft_test_sc_add", (a, 32), (b, 32))


def pubkey_cache_clear() -> None:
    """Drop every entry in the native per-key decompressed-point cache."""
    lib().pbft_pubkey_cache_clear()


def pubkey_cache_disable(on: bool) -> None:
    """TEST hook: force the cold (uncached) pubkey-decompression path so
    parity tests can compare warm vs cold verdicts."""
    lib().pbft_test_pubkey_cache_disable(ctypes.c_int(1 if on else 0))


def flight_configure(capacity: int) -> None:
    """(Re)size + enable the native black-box flight recorder ring
    (core/flight.cc); capacity 0 disables it."""
    lib().pbft_flight_configure(ctypes.c_size_t(capacity))


def flight_record(ev: int, view: int = 0, seq: int = 0, peer: int = -1) -> None:
    """Record one event into the native ring (trace_schema.FLIGHT_EVENTS
    ids) — a no-op (one branch) while the recorder is disabled."""
    lib().pbft_flight_record(
        ctypes.c_int(ev),
        ctypes.c_longlong(view),
        ctypes.c_longlong(seq),
        ctypes.c_int(peer),
    )


def flight_total() -> int:
    """Total records the native ring ever accepted (not capacity-clamped)."""
    fn = lib().pbft_flight_total
    fn.restype = ctypes.c_ulonglong
    return int(fn())


def flight_dump(path: str) -> int:
    """Write the native ring's binary dump; returns the record count
    (-1 on failure). Decode with pbft_tpu.utils.flight.decode_file."""
    fn = lib().pbft_flight_dump
    fn.restype = ctypes.c_long
    return int(fn(str(path).encode()))


def flight_reset() -> None:
    lib().pbft_flight_reset()


def message_to_binary(payload: bytes) -> Optional[bytes]:
    """Parse a JSON message payload in the C++ core and encode it with the
    native binary-v2 codec (None when the type has no binary form) — the
    cross-runtime byte-parity surface for tests/test_wire_codec.py."""
    fn = lib().pbft_message_to_binary
    fn.restype = ctypes.c_size_t
    out = ctypes.create_string_buffer(len(payload) + 256)
    n = fn(payload, len(payload), out, len(out))
    if n == 0 or n > len(out):
        return None
    return out.raw[:n]


def message_from_binary(payload: bytes) -> Optional[Tuple[bytes, bytes]]:
    """Decode a binary-v2 payload in the C++ core: returns (canonical
    JSON bytes, signable digest) or None on decode failure."""
    fn = lib().pbft_message_from_binary
    fn.restype = ctypes.c_size_t
    out = ctypes.create_string_buffer(4 * len(payload) + 1024)
    digest = ctypes.create_string_buffer(32)
    n = fn(payload, len(payload), out, len(out), digest)
    if n == 0 or n > len(out):
        return None
    return out.raw[:n], digest.raw


def signable_from_payload(payload: bytes) -> Optional[bytes]:
    """The C++ receive-side signable derivation (JSON sig-splice / binary
    template, with the generic fallback) for a framed payload."""
    fn = lib().pbft_signable_from_payload
    fn.restype = ctypes.c_int
    digest = ctypes.create_string_buffer(32)
    if not fn(payload, len(payload), digest):
        return None
    return digest.raw


def message_to_binary_mac(payload: bytes, lanes) -> Optional[bytes]:
    """Encode a JSON message payload as a native MAC-vector frame
    (ISSUE 14): ``lanes`` is a sequence of (rid, 16-byte tag). None when
    the type has no MAC form — the cross-runtime byte-parity surface."""
    blob = b"".join(
        rid.to_bytes(1, "big") + bytes(tag) for rid, tag in lanes
    )
    fn = lib().pbft_message_to_binary_mac
    fn.restype = ctypes.c_size_t
    out = ctypes.create_string_buffer(len(payload) + len(blob) + 256)
    n = fn(payload, len(payload), blob, len(lanes), out, len(out))
    if n == 0 or n > len(out):
        return None
    return out.raw[:n]


def mac_frame_lane(payload: bytes, rid: int) -> Optional[bytes]:
    """The C++ lane extraction for a MAC frame; None when absent."""
    fn = lib().pbft_mac_frame_lane
    fn.restype = ctypes.c_int
    tag = ctypes.create_string_buffer(16)
    if not fn(payload, len(payload), ctypes.c_longlong(rid), tag):
        return None
    return tag.raw


def derive_auth_keys(shared: bytes, eph_i: bytes, eph_r: bytes):
    """The C++ lane keys of one link, (a_i2r, a_r2i) (net/secure.py
    derive_auth_keys parity)."""
    assert len(shared) == len(eph_i) == len(eph_r) == 32
    i2r, r2i = ctypes.create_string_buffer(32), ctypes.create_string_buffer(32)
    lib().pbft_derive_auth_keys(shared, eph_i, eph_r, i2r, r2i)
    return i2r.raw, r2i.raw


def mac_tag(key: bytes, signable: bytes) -> bytes:
    """The C++ authenticator-lane tag (net/secure.py mac_tag parity)."""
    assert len(key) == 32 and len(signable) == 32
    tag = ctypes.create_string_buffer(16)
    lib().pbft_mac_tag(key, signable, tag)
    return tag.raw
