#!/usr/bin/env python3
"""Find the highest rate a serve cell sustains: one open-loop window per
offered rate, in one process, reporting p50, p95 and the backlog.

    python bench/sweep.py --workload serve.bert_base --seed 5 \
        --rates 50,100,200,400 --seconds 10 --out chiprun_out/sweep.jsonl

The backlog is the share of requests still unanswered when the window
closed, and how much slower the last fifth of requests were answered than
the first fifth: both grow with the rate once the server falls behind.
The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    cell, config, traffic = common.load_cell(args.workload)
    common.use_program()
    if not args.cpu:
        common.require_chips(cell["chips"])
    from bench.drive_serve import ServeRun, percentile
    serve = ServeRun(args.workload, cell, config, traffic)
    with open(args.out, "a") as out:
        try:
            serve.start(args.seed)
            for rate in [float(r) for r in args.rates.split(",")]:
                win = serve.window(args.seed, rate, args.seconds)
                lat = win["latency_ms"]
                n = len(lat)
                fifth = max(1, n // 5)
                done_at = win["due"] + lat / 1e3
                line = dict(
                    rate=rate, requests=n,
                    p50_ms=percentile(lat, 50), p95_ms=percentile(lat, 95),
                    open_at_close=float(np.mean(done_at > args.seconds)),
                    last_over_first=float(np.nanmean(lat[-fifth:])
                                          / np.nanmean(lat[:fifth])),
                    generator_late_p95_ms=float(
                        np.percentile(win["lateness_ms"], 95)))
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")
        finally:
            serve.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
