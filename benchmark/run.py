#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One new process per run: set-up (weights and inputs from the seed, every
shape the cell uses warmed), the measured window, the comparison with the
plain reference, and as the last line of standard output one JSON object.
Fails, with no result, without the accelerator the cell asks for. See
``benchmark/README.md``.
"""
import os
import sys
import time

_PROCESS_START = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

if __name__ == "__main__":
    from benchmark.harness.main import main
    sys.exit(main(sys.argv[1:], _PROCESS_START))
