#!/usr/bin/env python3
"""Runs one benchmark cell once; see ``harness/cli.py``.

    python3 bench/run.py --workload qwen4b-walk --seed 1 --seconds 20 --trace 0
"""
import os
import sys
import time

T0 = time.perf_counter()  # set-up is timed from here

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    from harness import cli

    sys.exit(cli.main(sys.argv[1:], t0=T0))
