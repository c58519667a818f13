#!/usr/bin/env python3
"""`sweep_knee.py` for a serving cell of any kind, with the pre-roll
swept too. The engine comes from the kind that the cell's traffic file
names (`kind`), built once; each row gets its pre-roll, a full window
and the drain. A row is `sweep_knee.py`'s (same names, same rule for
`sustained`: delivered >= 0.97 of offered and the queue no longer at
close than at open) with the pre-roll and the requests' lifetimes
beside it.

The pre-roll decides what "delivered" reads: a window that opens
before the longest answers of the steady state have begun lacks their
tokens, and a rate the slots sustain then reads as not sustained. The
rule this tool applies for `--sweep rule:<rates>`: the lifetime of a
request at the 90th percentile of the answers' lengths, taken from the
FIRST row of the run (run it at the cell's rate),

    ttft_mean + itl_mean * q90(answer tokens of the window's requests),

rounded to the nearest 5 s. Both means are over a whole window; the
quantile is the traffic file's own.

    python3 benchmark/sweep_knee_kind.py --workload <cell> \
        --sweep 18:0.45,0.65,0.7 --sweep rule:0.65,0.7,0.75 \
        [--seconds 51] [--seed 7] [--traffic-seed 8]

`--seed` makes the weights; `--traffic-seed` (default: the same) moves
the arrivals as `--seed` does in a run of the cell. Rows also go to
`chiprun_out/sweep_<traffic seed>.jsonl`.
"""
import argparse
import copy
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one_row(engine, cell, rate, preroll, seconds, seed):
    import numpy as np
    from benchmark import traffic
    from benchmark.kinds import serve_open
    mix = copy.deepcopy(cell["mix"])
    mix["arrivals"]["rate_per_s"] = rate
    mix["arrivals"]["preroll_s"] = preroll
    requests = traffic.serve_requests(mix, cell["sizes"]["vocab_size"],
                                      seconds, seed)
    due = [r["max_new_tokens"] for r in requests
           if 0 <= r["arrival_s"] < seconds]
    offered = sum(due) / seconds
    engine.reset()
    seen = serve_open.drive(engine, requests, seconds, 30.0)
    s = serve_open.summarise(seen, seconds)
    life = [r.finished_at - r.arrival_time for r in seen["all"]
            if r.finished_at is not None]
    q90 = float(np.percentile(due, 90))
    row = {"rate_per_s": rate, "preroll_s": preroll,
           "offered_tokens_per_s": offered,
           "delivered_tokens_per_s": s["serve_tokens_per_s"],
           "delivered_over_offered": s["serve_tokens_per_s"] / offered,
           "queued_at_open": s["queued_at_open"],
           "queued_at_close": s["queued_at_close"],
           "ttft_mean_ms": s["ttft_mean_ms"],
           "itl_mean_ms": s["itl_mean_ms"],
           "slots_occupied_mean": s["slots_occupied_mean"],
           "attempted": s["attempted"], "failed": s["failed"],
           "request_lifetime_mean_s": float(np.mean(life)) if life else None,
           "request_lifetime_p50_p90_max_s": [
               float(x) for x in np.percentile(life, [50, 90, 100])]
           if life else None,
           "requests_finished": len(life),
           "answer_tokens_q90": q90,
           "lifetime_at_q90_s": 1e-3 * (s["ttft_mean_ms"] +
                                        s["itl_mean_ms"] * q90)}
    row["sustained"] = bool(row["delivered_over_offered"] >= 0.97 and
                            row["queued_at_close"] <= row["queued_at_open"])
    return row


def main():
    from benchmark import harness
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sweep", action="append", required=True,
                    metavar="PREROLL:RATES",
                    help="a pre-roll in seconds, or `rule`, and the "
                         "rates to run at it")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--traffic-seed", type=int, default=None)
    args = ap.parse_args()
    seed = args.seed if args.traffic_seed is None else args.traffic_seed
    cell = harness.load_cell(harness.load_benchmark(), args.workload)
    info = harness.require_tpu(cell["chips"])
    harness.enable_compile_cache()
    kind = importlib.import_module("benchmark.kinds." + cell["mix"]["kind"])
    engine = kind.build_engine(cell, args.seed)[0]
    os.makedirs("chiprun_out", exist_ok=True)
    rows, knees = [], {}
    for group in args.sweep:
        at, rates = group.split(":")
        preroll = float(at) if at != "rule" else \
            5.0 * round(rows[0]["lifetime_at_q90_s"] / 5.0)
        for rate in (float(r) for r in rates.split(",")):
            row = one_row(engine, cell, rate, preroll, args.seconds, seed)
            rows.append(row)
            print("SWEEP " + json.dumps(row), flush=True)
            with open(f"chiprun_out/sweep_{seed}.jsonl", "a") as f:
                f.write(json.dumps(row) + "\n")
        held = [r["rate_per_s"] for r in rows
                if r["preroll_s"] == preroll and r["sustained"]]
        knees[str(preroll)] = max(held) if held else None
    print(json.dumps({"device": info, "knee_per_s_by_preroll": knees,
                      "rows": rows}), flush=True)


if __name__ == "__main__":
    main()
