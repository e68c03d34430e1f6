#!/usr/bin/env python3
"""Chip benchmark of Flex admission: one cell of BENCHMARK.json per run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell
asks for.  The run sets up the cell (inputs from the seed, compile or
cache load, warm-up), measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints one JSON object
as the last line of standard output.  Without a TPU it exits non-zero
and prints no result.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
