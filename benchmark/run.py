#!/usr/bin/env python3
"""python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json once in this process and prints the
result as the last line of standard output. See benchmark/README.md.
"""

import time

_T_START = time.perf_counter()          # set-up counts from here

import pathlib                          # noqa: E402
import sys                              # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness.runner import main         # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=_T_START))
