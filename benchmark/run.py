#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU (no fall-back), runs in one process, and prints the result
as one JSON object on the last line of its standard output.
"""
import time
T_START = time.time()           # set-up counts from here

import os                       # noqa: E402
import sys                      # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmark import harness
    sys.exit(harness.main(sys.argv[1:], T_START))
