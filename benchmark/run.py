"""python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU this process finds and prints
one JSON object as the last line of its output.  See ``benchmark/README.md``.
"""

import os
import sys
import time

T_START = time.monotonic()  # set-up is counted from here
HERE = os.path.dirname(os.path.abspath(__file__))
# the harness (benchlib), the reference, and the program under test
sys.path[:0] = [HERE, os.path.dirname(HERE)]

if __name__ == "__main__":
    from benchlib import harness

    sys.exit(harness.main(t_start=T_START))
