#!/usr/bin/env python3
"""One cell of the benchmark, once, on the chip this process is started on:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result object; the lines before it
are one JSON object each (set-up, the window's detail, every number compared
beside its limit). Without a TPU it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
