"""pbft_tpu.bench — the benchmark harness for BASELINE.json's five configs.

The repo-root ``bench.py`` prints the single headline metric (batched
Ed25519 verifies/sec on one chip); this package measures the *consensus*
side: sustained rounds/sec and sig-verifies/sec through the replica state
machines for each BASELINE.json config (4/7/16/31 replicas, firehose
clients, Byzantine signers), on either verifier arm.
"""

from .harness import BenchResult, run_config, run_all

__all__ = ["BenchResult", "run_config", "run_all"]
