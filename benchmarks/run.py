#!/usr/bin/env python3
"""Entry point of the benchmark: see ``benchmarks/harness/cli.py``."""

import os
import sys
import time

STARTED = time.time()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmarks.harness import cli

    cli.main_then_leave(started_wall=STARTED)
