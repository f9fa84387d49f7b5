#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the checks on standard error, each with its limit, and as the
last line of standard output one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device``. Without a TPU it
runs every phase and check, then exits 3 with no result line.
"""

import os
import signal
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness.core import main  # noqa: E402

if __name__ == "__main__":
    # a time limit's SIGTERM still runs the finally that stops serve
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    sys.exit(main())
