"""The four-chip deployment on four virtual CPU devices, for
``test_f5_x4_rehearsal.py`` and ``test_four_chips.py`` (after
``_f1_mac_rehearse.py``). Three entries, each a process of its own:

    python3 _f5_x4_rehearse.py run WORKLOAD SECONDS TRACE     one run of a cell, prints the result line
    python3 _f5_x4_rehearse.py verifyd --control-fifo F ...   what the harness starts as verifyd
    python3 _f5_x4_rehearse.py classes SLOTS SEED             every chip decides every class

``run`` is ``chipbench/run.py`` with the look for a chip skipped. Its
``verifyd`` is the program's own entry with the REAL ``ShardedVerifyEngine``
over a mesh of four devices: the sharded executables of the whole ladder, the
one block a window, its transfer against the batch sharding and the gather of
the verdicts are the served ones; only the arithmetic is not (the program's
native host verifier answers from inside each executable, through a
callback, so that real signatures get their real verdicts without the
minutes the kernel takes to compile for a CPU).

``classes`` is the real kernel: one window of ``SLOTS`` slots in which every
shard's rows hold one item of each class the reference rejects and one sound
one; prints, a shard, the engine's verdicts beside the reference's.
"""

import time

T_START = time.monotonic()

import json  # noqa: E402
import random  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

TESTS = Path(__file__).resolve().parent
BENCH = TESTS.parent / "chipbench"
sys.path[:0] = [str(BENCH), str(BENCH / "tools"), str(TESTS), str(TESTS.parent)]

CHIPS = 4


def host_arithmetic(lie=None, delay_s: float = 0.0):
    """A kernel that stands where ``crypto.ed25519.verify_kernel`` stands:
    the same columns in, one verdict a row out, decided by the native host
    verifier. ``lie(row, item, verdict)`` may alter a verdict by the row of
    the executable its item sat in (``test_chip_smoke.py``'s faulty chip):
    the kernel is traced for one chip's rows (``shard_map``), so that is the
    row it sees behind the rows of the chips before its own. ``delay_s``:
    what a chip's share of a launch takes at least (the chips' callbacks run
    side by side, so a launch of a few items is over in a millisecond: too
    soon for a test that needs launches in flight while requests queue)."""

    def kernel(pubs, msgs, sigs):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from pbft_tpu import native

        def decide(first_row, p, m, s):
            time.sleep(delay_s)
            p, m, s = (np.asarray(a) for a in (p, m, s))
            items = [(p[i].tobytes(), m[i].tobytes(), s[i].tobytes()) for i in range(len(p))]
            out = [bool(v) for v in native.verify_batch(items)]
            if lie is not None:
                out = [
                    lie(row, item, v)
                    for row, (item, v) in enumerate(zip(items, out), int(first_row))
                ]
            return np.asarray(out, dtype=np.bool_)

        rows = pubs.shape[0]
        return jax.pure_callback(
            decide, jax.ShapeDtypeStruct((rows,), jnp.bool_),
            jax.lax.axis_index("batch") * rows, pubs, msgs, sigs,
        )

    return kernel


def serve(argv: list) -> None:
    from _cpu_backend import force_cpu

    force_cpu(CHIPS)  # before the first backend touch: the mesh is "all local devices"
    import verifyd_wrap

    from pbft_tpu.net.verify_service import ShardedVerifyEngine

    class HostArithmetic(ShardedVerifyEngine):
        def __init__(self):
            super().__init__(kernel=host_arithmetic())

    verifyd_wrap.main(argv, engine=verifyd_wrap.traced(HostArithmetic))


def run(workload: str, seconds: str, trace: str) -> int:
    import harness

    # A work directory of this rehearsal's own: tier-1's workers run it beside
    # ``test_f1_mac_rehearsal.py``, whose run clears the harness's default one.
    harness.WORK = harness.ROOT / ".chipbench_work_x4"
    try:
        line = harness.run_cell(
            workload, 3600000041, float(seconds), bool(int(trace)), t_start=T_START,
            require_tpu=False, verifyd_wrapper=[__file__, "verifyd"],
        )
    except harness.BenchFailure as e:
        print(f"[chipbench] no result: {e}", file=sys.stderr, flush=True)
        return 1
    kept = line.pop("_run")
    line["replicas"] = [{k: d[k] for k in ("executed", "chain_digest")} for d in kept["final"]["status"]]
    line["launches"] = [
        {k: e.get(k) for k in ("size", "rung", "chunks", "devices", "rows_per_chip")}
        for e in kept["launches"]
    ]
    ready = kept["ready"]
    line["verifyd"] = {
        "devices": ready["devices"], "warmed_shapes": ready["warmed_shapes"],
        "per_shape": [{k: p[k] for k in ("size", "devices", "rows_per_device")}
                      for p in ready["warm_stats"]["per_shape"]],
        "launches_by_rows_per_chip": kept["verifyd_after"]["launches_by_rows_per_chip"],
        "launches_by_rung": kept["verifyd_after"]["launches_by_rung"],
    }
    print(json.dumps(line), flush=True)
    return 0


def classes(slots: str, seed: str) -> int:
    from _cpu_backend import force_cpu

    force_cpu(CHIPS)
    import harness
    from reference import ed25519_ref as ref

    from pbft_tpu.net.verify_service import ShardedVerifyEngine
    from pbft_tpu.utils.trace import open_span

    slots, rng = int(slots), random.Random(int(seed))
    rows = slots // CHIPS
    items, names = [], []
    for shard in range(CHIPS):
        sk, msg = rng.randbytes(32), rng.randbytes(32)
        base = (ref.public_key(sk), msg, ref.sign(sk, msg))
        part = harness.planted(rng, base)[:7] + [base]  # the seven it rejects, one sound
        part += [base] * (rows - len(part))
        order = rng.sample(range(rows), rows)  # each class at a seeded row of THIS shard
        items += [part[k] for k in order]
        names.append([min(k, 7) for k in order])
    engine = ShardedVerifyEngine(shapes=(slots,), devices=CHIPS)
    stats = engine.warm()
    with open_span() as span:
        verdicts = engine.verify(items)
    print(json.dumps({
        "per_shape": [{k: p[k] for k in ("size", "devices", "rows_per_device")} for p in stats["per_shape"]],
        "span": {k: span[k] for k in ("rung", "chunks", "devices", "rows_per_chip")},
        "shards": [
            {"classes": names[s], "engine": verdicts[s * rows:(s + 1) * rows],
             "reference": [ref.verify(*it) for it in items[s * rows:(s + 1) * rows]]}
            for s in range(CHIPS)
        ],
    }), flush=True)
    return 0


if __name__ == "__main__":
    if sys.argv[1] == "verifyd":
        serve(sys.argv[2:])
    elif sys.argv[1] == "classes":
        sys.exit(classes(*sys.argv[2:4]))
    else:
        sys.exit(run(*sys.argv[2:5]))
