#!/usr/bin/env python3
"""The benchmark's one command:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in BENCHMARK.json, warms it, measures for ``--seconds``,
checks what the timed path produced against the plain reference, and prints
one JSON object as the last line of standard output.  Everything that belongs
to one configuration, traffic mix, cell or per-layer metric is a file of its
own under ``perfbench/``; nothing of them is named here.
"""

import os
import sys
import time

T_PROCESS_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from perfbench.harness import runner

    sys.exit(runner.main(sys.argv[1:], t_start=T_PROCESS_START))
