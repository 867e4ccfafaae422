"""Test configuration: force pure-CPU JAX with 8 virtual devices.

The virtual 8-device CPU mesh (for the multi-chip sharding tests) needs
XLA_FLAGS and JAX_PLATFORMS=cpu before the first backend init. The logic
lives in tests/_cpu_backend.py so subprocess workers (which never see
conftest) share it. The TPU path itself is exercised by chip_smoke.py on
a machine with a chip, not by unit tests.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(__file__))

from _cpu_backend import force_cpu

force_cpu(n_devices=8)
