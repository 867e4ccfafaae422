"""Mesh-sharded verification + distributed quorum certification.

Runs on the virtual 8-device CPU mesh (conftest sets
xla_force_host_platform_device_count=8), mirroring the driver's multi-chip
dryrun — the same code paths run on a real TPU slice.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from pbft_tpu.crypto import ref
from pbft_tpu.crypto.batch import pad_batch, split_block
from pbft_tpu.parallel import make_mesh, sharded_verify, quorum_certify, round_step

# Kernel-compile-heavy: slow tier (pytest -m slow).
pytestmark = pytest.mark.slow


def _signed_items(count, bad=()):
    items = []
    for i in range(count):
        seed = bytes([i]) * 32
        msg = bytes([0xA0 ^ i]) * 32
        sig = ref.sign(seed, msg)
        if i in bad:
            sig = sig[:10] + bytes([sig[10] ^ 0xFF]) + sig[11:]
        items.append((ref.public_key(seed), msg, sig))
    return items


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8


def test_sharded_verify_matches_oracle():
    mesh = make_mesh(8)
    fn = sharded_verify(mesh)
    items = _signed_items(16, bad={3, 11})
    block, n = pad_batch(items, 16)
    out = np.asarray(fn(block))
    expect = [i not in {3, 11} for i in range(16)]
    assert out.tolist() == expect


def test_quorum_certify_counts_and_thresholds():
    mesh = make_mesh(8)
    R = 4
    certify = quorum_certify(mesh, R)
    # 16 signatures: rounds 0..3 get 4 each; corrupt one sig in round 1,
    # two in round 2. Pad rows -> round_id R.
    items = _signed_items(16, bad={5, 9, 10})
    pubs, msgs, sigs = split_block(pad_batch(items, 16)[0])
    round_ids = np.arange(16) // 4
    thresholds = np.array([4, 4, 3, 3], np.int32)
    res = certify(pubs, msgs, sigs, round_ids, thresholds)
    assert np.asarray(res.counts).tolist() == [4, 3, 2, 4]
    assert np.asarray(res.certified).tolist() == [True, False, False, True]
    assert np.asarray(res.valid).sum() == 13


def test_quorum_certify_pad_slots_ignored():
    mesh = make_mesh(8)
    R = 2
    certify = quorum_certify(mesh, R)
    items = _signed_items(8)
    pubs, msgs, sigs = split_block(pad_batch(items, 16)[0])  # 8 pad rows (valid pad sig)
    round_ids = np.concatenate([np.arange(8) // 4, np.full(8, R)])
    thresholds = np.array([3, 3], np.int32)
    res = certify(pubs, msgs, sigs, round_ids, thresholds)
    # Pad rows verify True but must not leak into any round's count.
    assert np.asarray(res.counts).tolist() == [4, 4]


def test_round_step_runs_and_is_deterministic():
    mesh = make_mesh(8)
    R = 4
    step = round_step(mesh, R)
    items = _signed_items(16, bad={2})
    pubs, msgs, sigs = split_block(pad_batch(items, 16)[0])
    round_ids = np.arange(16) // 4
    thresholds = np.full(R, 3, np.int32)
    state = jnp.zeros(8, jnp.int32)
    s1, res1 = step(state, pubs, msgs, sigs, round_ids, thresholds)
    s2, res2 = step(state, pubs, msgs, sigs, round_ids, thresholds)
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    assert np.asarray(res1.certified).all()
    # State advanced (some certified rounds folded in).
    assert not np.array_equal(np.asarray(s1), np.zeros(8, np.int32))


def test_multihost_helpers_single_process():
    """The multi-host helpers degrade to single-process correctly (the
    same code path a one-host deployment runs)."""
    from pbft_tpu.parallel import (
        global_mesh,
        host_shard_to_global,
        initialize_distributed,
        partition_items,
    )

    initialize_distributed()  # no-op single process
    mesh = global_mesh()
    assert mesh.devices.size == 8
    local = np.arange(16 * 32, dtype=np.uint8).reshape(16, 32)
    arr = host_shard_to_global(mesh, local)
    assert arr.shape == (16, 32)
    assert np.array_equal(np.asarray(arr), local)
    items = list(range(10))
    assert partition_items(items, process_id=0, num=2) == [0, 2, 4, 6, 8]
    assert partition_items(items, process_id=1, num=2) == [1, 3, 5, 7, 9]
    assert partition_items(items) == items  # single process keeps all


def test_sharded_matches_unsharded():
    from pbft_tpu.crypto.batch import verify_batch

    mesh = make_mesh(8)
    fn = sharded_verify(mesh)
    items = _signed_items(8, bad={1, 6})
    block, n = pad_batch(items, 8)
    assert np.asarray(fn(block)).tolist() == np.asarray(
        verify_batch(*split_block(block))
    ).tolist()


@pytest.mark.parametrize("n", [0, 1, 15, 16])
def test_block_path_gives_the_oracles_verdicts_item_by_item(n):
    """ISSUE 31 parity: valid items plus one of every class the kernel
    decides (the probe's seven rejects and its control), staged as ONE
    block and cut into columns on the device, get ``ref.verify``'s verdict
    item by item, at n = 0, 1, size - 1, size."""
    from pbft_tpu.crypto import ref
    from pbft_tpu.net import ShardedVerifyEngine
    from tests.test_verify_spans import _probe_items

    items = _probe_items(n)
    want = [ref.verify(*item) for item in items]
    assert want[:8] == ([False] * 7 + [True])[:n]
    eng = ShardedVerifyEngine(shapes=(16,))
    eng.warm()
    assert eng.verify(items) == want


@pytest.fixture(scope="module")
def real_engine():
    from pbft_tpu.net import ShardedVerifyEngine

    eng = ShardedVerifyEngine(shapes=(8, 16))
    eng.warm()
    return eng


@pytest.mark.parametrize(
    "costs, sizes",
    [
        ({8: 0.001, 16: 0.004}, [3, 7, 1]),  # 8 + 8 slots: the second request straddles the edge
        ({8: 0.001, 16: 0.004}, [8, 3]),  # the first request fills the first chunk exactly
        ({8: 0.001, 16: 0.001}, [16]),  # one request fills the shape
        ({8: 0.001, 16: 0.001}, [5, 4, 2]),  # one shape, pad rows behind three requests
    ],
    ids=["straddle", "fills-first-chunk", "fills-16", "padded"],
)
def test_a_window_of_wire_blocks_gets_the_oracles_verdicts_from_the_real_kernel(real_engine, costs, sizes):
    """ISSUE 45 parity: the REAL kernel on the dispatcher's ``Window`` (the
    requests' blocks as they came off the wire, staged one slice a request):
    the probe's seven rejects and its control first, last and at every
    request's edge, ``ref.verify``'s verdict item by item, the same from the
    same items as a list."""
    from itertools import accumulate

    from pbft_tpu.net.service import Window
    from tests.test_service_coalesce import _wire_blocks
    from tests.test_verify_spans import _probe_items

    n = sum(sizes)
    pool = _probe_items(8 + n)
    plants, items = pool[:8], pool[8:]
    edges = sorted({0, n - 1} | {e - 1 for e in accumulate(sizes)} | {e for e in accumulate(sizes) if e < n})
    for k, at in enumerate(edges):
        items[at] = plants[k % 8]
    want = [ref.verify(*item) for item in items]
    assert not all(want) and any(want)
    real_engine._route(costs)
    got = real_engine.verify(Window(_wire_blocks(items, sizes)))
    assert isinstance(got, np.ndarray) and got.tolist() == want
    assert real_engine.verify(items) == want


def test_persistent_engine_matches_oracle_and_native():
    """ISSUE 7 parity pin: the persistent service's AOT-compiled,
    donated-buffer engine must produce the SAME accept set as the Python
    oracle (and the native C++ pool when built) with the REAL Ed25519
    kernel — invalid items planted at window boundaries and pad slots
    exercised by an off-ladder batch size."""
    from pbft_tpu.net import ShardedVerifyEngine

    # 11 items over an (8, 16) ladder: chunk boundary at 8, pad slots
    # 11..15 in the second window; invalids straddle the boundary.
    bad = {0, 7, 8, 10}
    items = _signed_items(11, bad=bad)
    want = [i not in bad for i in range(11)]

    eng = ShardedVerifyEngine(shapes=(8, 16))
    stats = eng.warm()
    assert stats["shapes"] == [8, 16]
    got = eng.verify(items)
    assert got == want  # vs the oracle-signed construction

    from pbft_tpu.crypto import ref

    assert [ref.verify(p, m, s) for p, m, s in items] == want
    try:
        from pbft_tpu import native

        native_ok = native.available()
    except Exception:
        native_ok = False
    if native_ok:
        assert native.verify_batch(items) == want
    # And as two chunks (costs injected so that the plan splits: 8 + 8
    # slots, the boundary between items 7 and 8, both rejected).
    assert eng._route({8: 0.001, 16: 0.004})["chunk_plan"] == {"9-16": "8+8"}
    assert eng.verify(items) == want

    # Every shape's input sharding spans the whole virtual mesh, and the
    # smallest rung still gives each device at least one row.
    n_dev = eng.device_count
    assert n_dev >= 1
    for shape in stats["per_shape"]:
        assert len(shape["devices"]) == n_dev
        assert shape["rows_per_device"] * n_dev == shape["size"]
