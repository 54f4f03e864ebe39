#!/usr/bin/env python3
"""The benchmark's command (BENCHMARK.json `command`):

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it holds the chip(s), builds the system under test, warms every
shape, measures for --seconds, checks what the timed path produced against
the plain reference, and prints one JSON object as its last stdout line.
`--rehearse-cpu` walks the same code at the tiny sizes of the files'
`rehearsal` groups on the CPU and prints a rehearsal, never a result.
"""
import os
import sys
import time

T0 = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402

if __name__ == "__main__":
    harness.main(sys.argv[1:], T0)
