"""``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell of ``BENCHMARK.json``; the last
line of standard output is the result."""

import time

_STARTED = time.perf_counter()  # before any import: the process starts here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmark import harness

    sys.exit(harness.main(sys.argv[1:], started=_STARTED))
