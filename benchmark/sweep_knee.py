#!/usr/bin/env python3
"""The sweep that finds a serving cell's knee: the engine is built
once, and each rate gets the cell's own pre-roll, a full window and
the drain, on the same seed. The knee is the highest swept rate at
which the tokens delivered are >= 0.97 of those offered and the queue
at the window's end is no longer than at its start. The cell's rate
(0.7 of the knee, rounded down to 0.05/s) is then written into the
traffic file by hand, and this output into PERF.md.

    python3 benchmark/sweep_knee.py --workload <cell> --rates 0.4,0.55,0.7,0.85,1.0 \
        [--seconds 51] [--seed 7]
"""
import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import numpy as np
    from benchmark import harness, traffic
    from benchmark.kinds import serve_open
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    bench = harness.load_benchmark()
    cell = harness.load_cell(bench, args.workload)
    info = harness.require_tpu(cell["chips"])
    harness.enable_compile_cache()
    engine, _, _ = serve_open.build_engine(cell, args.seed)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell["mix"])
        mix["arrivals"]["rate_per_s"] = rate
        requests = traffic.serve_requests(
            mix, cell["sizes"]["vocab_size"], args.seconds, args.seed)
        offered = sum(r["max_new_tokens"] for r in requests
                      if 0 <= r["arrival_s"] < args.seconds) / args.seconds
        engine.reset()
        seen = serve_open.drive(engine, requests, args.seconds, 30.0)
        s = serve_open.summarise(seen, args.seconds)
        life = [r.finished_at - r.arrival_time for r in seen["all"]
                if r.finished_at is not None]
        row = {"rate_per_s": rate, "offered_tokens_per_s": offered,
               "delivered_tokens_per_s": s["serve_tokens_per_s"],
               "delivered_over_offered": s["serve_tokens_per_s"] / offered,
               "queued_at_open": s["queued_at_open"],
               "queued_at_close": s["queued_at_close"],
               "ttft_mean_ms": s["ttft_mean_ms"],
               "itl_mean_ms": s["itl_mean_ms"],
               "slots_occupied_mean": s["slots_occupied_mean"],
               "attempted": s["attempted"], "failed": s["failed"],
               "request_lifetime_mean_s": float(np.mean(life)) if life
               else None}
        row["sustained"] = bool(row["delivered_over_offered"] >= 0.97 and
                                row["queued_at_close"] <= row["queued_at_open"])
        rows.append(row)
        print("SWEEP " + json.dumps(row), flush=True)
    held = [r["rate_per_s"] for r in rows if r["sustained"]]
    print(json.dumps({"device": info, "knee_per_s": max(held) if held
                      else None, "rows": rows}), flush=True)


if __name__ == "__main__":
    main()
