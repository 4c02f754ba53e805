#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload smollm_360m.train.seq1024 --seed 7 \
        --seconds 10 --trace 0

Everything the run needs is found by name from ``BENCHMARK.json``: the
cell's configuration (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``, whose ``kind`` names the driver
``bench/traffic/<kind>.py``), its correctness limits
(``bench/limits/<cell>.json``) and, with ``--trace 1``, one reader per
per-layer metric (``bench/metrics/<metric>.py``).  The last line of
standard output is one JSON object; a run that finds no TPU, or fewer
chips than the cell asks for, exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
