#!/usr/bin/env python3
"""Chip benchmark: one run of one cell of BENCHMARK.json.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Exits nonzero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.  See benchlib/harness.py for what a run does.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

from benchlib import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main())
