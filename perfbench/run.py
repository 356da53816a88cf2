#!/usr/bin/env python3
"""perfbench/run.py: one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU; without one it exits non-zero and prints no result. The last
line of standard output is the result (see PERF.md). --rehearse-cpu runs the
same code tiny on the CPU backend to check the harness and prints no result
line.
"""

import time

T_START = time.time()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from perfbench.lib.harness import run

    sys.exit(run(sys.argv[1:], T_START))
