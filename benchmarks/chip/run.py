#!/usr/bin/env python3
"""One run of one benchmark cell on the chip; see chipbench/cli.py.

    python3 benchmarks/chip/run.py --workload trimkv4b.reasoning \
        --seed 7 --seconds 30 --trace 0
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(T_START))
