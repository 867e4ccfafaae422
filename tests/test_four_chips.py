"""Four chips under one verify service (ISSUE 36), on the CPU.

*Every chip decides every class*: the real kernel through
``ShardedVerifyEngine`` over four virtual devices, one window in which each
shard's rows hold one item of every class the reference rejects and one
sound one; verdicts item by item against the benchmark's reference. (A
probe's 16 items sit together, so in ONE chip's rows; this is the check
that no chip's quarter of an executable decides differently.) The kernel
compiles once, in a process of its own with a time limit
(``_f5_x4_rehearse.py classes``).

*The second cost table*: what warm-up read on the 2x2 TPU v5e host and on
one chip in the same session (PERF.md section 7 row 4, PR 35), through the
engine's three rules as pure functions. Four chips are not one chip four
times faster: 64 rows a chip are the slow regime that 64 slots are on one
chip, so every rule decides anew. This pins what a rewrite of the rules
(ROADMAP D14) has to keep on BOTH tables.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from pbft_tpu.net.verify_service import (
    ShardedVerifyEngine,
    chunk_plan_words,
    plan_table,
    serving_table,
    serving_table_text,
)

TESTS = Path(__file__).resolve().parent
ROOT = TESTS.parent
SLOTS = 32  # 8 rows a chip: the seven classes the reference rejects and one sound item
CLASSES = ("flipped byte", "S >= L", "key off the curve", "non-canonical y in the key",
           "x = 0 with the sign bit", "non-canonical y in R", "wrong message", "sound")


@pytest.fixture(scope="module")
def decided():
    proc = subprocess.run(
        [sys.executable, str(TESTS / "_f5_x4_rehearse.py"), "classes", str(SLOTS), "3600000043"],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("shard", range(4))
def test_every_chip_decides_every_class(decided, shard):
    assert decided["per_shape"] == [{"size": SLOTS, "devices": [0, 1, 2, 3], "rows_per_device": 8}]
    assert decided["span"] == {"rung": SLOTS, "chunks": 1, "devices": 4, "rows_per_chip": 8}
    rows = decided["shards"][shard]
    # this shard's own rows hold every class, each at a row of its own
    assert sorted(rows["classes"]) == list(range(8))
    for k, got, want in zip(rows["classes"], rows["engine"], rows["reference"]):
        assert want is (k == 7), f"the reference on a {CLASSES[k]} item"
        assert got is want, f"chip {shard}: a {CLASSES[k]} item got {got}, the reference says {want}"


# One launch of each shape through the host, as warm-up read it in ONE session
# (builders, PR 35): the one chip of a v5e host, and the four of a 2x2 host
# with every shape a quarter of its rows a chip.
ONE_CHIP = {16: 0.0445, 64: 0.0436, 256: 0.0068, 1024: 0.0150, 4096: 0.0524}
FOUR_CHIPS = {16: 0.0499, 64: 0.0449, 256: 0.0443, 1024: 0.0079, 4096: 0.0181}


@pytest.mark.parametrize(
    "launch_s, table, plan, holds",
    [
        (ONE_CHIP, "16→256 64→256 256→256 1024→1024 4096→4096",
         {"1025-1280": "1024+256", "1281-1536": "1024+256+256", "1537-2048": "1024+1024",
          "2049-2304": "1024+1024+256"},
         # a window that has just passed 1,024 items waits for company one
         # launch of the 256-slot chunk it leaves room on, not of 4,096 slots
         {40: 0.0068, 256: 0.0, 690: 0.0150, 1024: 0.0, 1025: 0.0068, 2305: 0.0524, 4096: 0.0}),
        (FOUR_CHIPS, "16→1024 64→1024 256→1024 1024→1024 4096→4096", {},
         # the 256-slot program never runs: everything up to 1,024 items waits
         # one launch of the 1,024-slot program, and nothing splits
         {40: 0.0079, 256: 0.0079, 690: 0.0079, 1024: 0.0, 1025: 0.0181, 2305: 0.0181, 4096: 0.0}),
    ],
    ids=["one-chip", "four-chips"],
)
def test_the_engines_three_rules_on_both_cost_tables(launch_s, table, plan, holds):
    serves = serving_table(launch_s)
    assert serving_table_text(serves) == table
    assert chunk_plan_words(plan_table(launch_s, serves)) == plan
    engine = ShardedVerifyEngine(shapes=tuple(launch_s))
    routed = engine._route(launch_s)  # what warm() does with its readings
    assert routed == {"serving_table": {str(k): v for k, v in serves.items()}, "chunk_plan": plan}
    assert {n: engine.hold_s(n) for n in holds} == holds
    # every window runs where the table sends the shapes of its plan
    runs = set(serves.values())
    for n in (1, 16, 17, 300, 690, 1024, 1025, 1500, 2304, 2305, 4096, 4097, 9000):
        shapes = engine._plan(n)
        assert sum(shapes) >= n and set(shapes) <= runs
        assert list(shapes) == sorted(shapes, reverse=True)  # largest first: the thinnest is last
