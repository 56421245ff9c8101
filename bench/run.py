#!/usr/bin/env python3
"""Benchmark entry point: one cell, one seed, one process.

    python3 bench/run.py --workload cifar10-r18feat.margin --seed 7 \
        --seconds 30 --trace 0

Prints the check numbers as the last lines of standard error and one JSON
result line as the last line of standard output.  Exits non-zero, with
no result, when no TPU (or too few chips) is attached.
"""
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from bench.harness import main
    sys.exit(main(t_start=T_START))
