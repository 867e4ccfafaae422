"""The benchmark's own quick tests, in tier-1: the traced run's reading on
hand-made traces (``chipbench/tests/test_chipbench_trace.py``) and the
arithmetic of its statistics and reducers (``test_arithmetic.py``); no
cluster, no chip, six seconds together. The driver's command collects
``tests/`` alone, so the cases are imported here and stay where the
benchmark keeps them (each runs against its own module's helpers). The
rehearsals of whole runs (``chipbench/tests/test_rehearsal.py``, minutes)
are run by hand: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "chipbench" / "tests"))

from test_arithmetic import *  # noqa: E402,F401,F403 - the cases themselves
from test_chipbench_trace import *  # noqa: E402,F401,F403
