#!/usr/bin/env python3
"""Run one benchmark cell once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  It needs a TPU (or another
accelerator) with as many chips as the cell asks for, and exits
non-zero without a result otherwise.  See ``bench/harness.py``.
"""
import pathlib
import sys
import time

if __name__ == "__main__":
    t_start = time.perf_counter()
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from bench import harness
    sys.exit(harness.main(t_start=t_start))
