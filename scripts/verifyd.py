#!/usr/bin/env python
"""verifyd — the persistent multi-chip verify service daemon.

One per TPU host, and the ONLY process on it that touches JAX: it owns
the accelerator, initializes the backend once, AOT-warms the sharded
verify kernel for every pad-ladder window shape (through JAX's persistent
compile cache: $JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache),
then serves coalesced signature windows to every colocated replica for
its whole lifetime. Replicas dial it with a short connect deadline and
fall back to their native verify pool while it warms — start it before,
after, or during the cluster; consensus never waits.

    python scripts/verifyd.py --backend jax --port 7600    # the chip: TPU or exit 1
    python scripts/verifyd.py --backend native             # chip-less control arm
    python scripts/verifyd.py --unix /tmp/verify.sock --metrics-port 9100

Readiness: probe with an item count of 0 (8-byte binary status) or
0xFFFFFFFF (JSON status: platform, device kind, devices, per-shape
compile seconds, engine vs fallback dispatch counts); see
pbft_tpu/net/verify_service.py and scripts/verify_status.py.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pbft_tpu.net.verify_service import main  # noqa: E402

if __name__ == "__main__":
    main()
