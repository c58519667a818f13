#!/usr/bin/env python3
"""Reads, on the chip, the numbers that `correct` compares: the sound
program's over several seeds, or those of the cell's lower-precision
control (`--control 1`) or of the program's own lower-precision path
(`--control 2`), all in one process. A limit is then set
above the sound runs' largest and below the control's smallest, and
written into the traffic file with the readings into PERF.md.

    python3 benchmark/read_limits.py --workload <name> --seeds 1,2,3 \
        [--control 1|2] [--seconds 20]

Training's readings need no window (`--seconds 0` skips it); serving's
need one long enough to finish the mix's longest requests.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from benchmark import harness
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        result = harness.run_cell(
            args.workload, seed, args.seconds, 0, t0,
            control=args.control, check_only=args.seconds <= 0,
            keep_checks=True)
        rows.append({"seed": seed, "control": args.control,
                     "correct": result["correct"],
                     "checks": {c["name"]: c["value"]
                                for c in result["checks"]}})
        print("READING " + json.dumps(rows[-1]), flush=True)
    names = rows[0]["checks"]
    for name in names:
        vals = [r["checks"][name] for r in rows]
        print(f"SUMMARY {args.workload} control={args.control} {name}: "
              f"min {min(vals):.6g} max {max(vals):.6g} over "
              f"{len(vals)} seeds", flush=True)


if __name__ == "__main__":
    main()
