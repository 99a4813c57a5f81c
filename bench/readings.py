#!/usr/bin/env python3
"""Readings that set a cell's limits: the compared numbers of the program
over many seeds, of the control, and of each planted fault.

    python bench/readings.py --workload search.bert_base \
        --seeds 11,12,13 --control 21,22,23 --faults 31,32,33 \
        --out chiprun_out/readings.jsonl

One process: the program's windows compile once and every seed reuses them.
Each reading is one JSON line: ``{"who", "seed", "numbers", "details"}``.
The benchmark's own runs never run this.
"""
import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import common  # noqa: E402


def seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control", type=seeds, default=[])
    ap.add_argument("--faults", type=seeds, default=[])
    ap.add_argument("--out", required=True)
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse on the CPU (no chip check)")
    ap.add_argument("--seconds", type=float, default=5.0,
                    help="window of each serve reading")
    args = ap.parse_args(argv)
    cell, config, traffic = common.load_cell(args.workload)
    common.use_program()
    if not args.cpu:
        common.require_chips(cell["chips"])
    if traffic["kind"] == "serve":
        return serve_readings(args, cell, config, traffic)
    from bench import compare_search, faults
    from bench.control import ControlRun
    from bench.drive_search import SearchRun, to_host

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    out = open(args.out, "a")

    def emit(who, seed, run, t0):
        best = (run.best_placement, run.best_latency)
        numbers, details = compare_search.compare(
            run.graph, run.platform, run.arrays, run.records, run.samples,
            best, run.params0 if isinstance(run.params0, dict) else
            to_host(run.params0), run.params_checked, run.z_checked,
            run.program_grads, config, block=traffic["reference_block"])
        line = dict(who=who, seed=seed, numbers=numbers, details=details,
                    seconds=time.perf_counter() - t0)
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()

    t0 = time.perf_counter()
    search = SearchRun(cell, config, traffic, (args.seeds or [0])[0])
    for seed in args.seeds:
        t0 = time.perf_counter()
        search.reset(seed)
        search.setup()
        emit("program", seed, search, t0)
    mesh = faults.MESH_FAULTS if traffic.get("mesh") else ()
    for name in faults.SEARCH_FAULTS + mesh:
        if name == "half_batch" and search.B < 2:
            continue            # one chain has no half to leave out
        for seed in args.faults:
            t0 = time.perf_counter()
            with faults.plant(name):
                search.reset(seed)
                search.setup()
                emit(f"fault:{name}", seed, search, t0)
    if args.control:
        control = ControlRun(search)
        for seed in args.control:
            t0 = time.perf_counter()
            control.reset(seed)
            control.setup()
            emit("control", seed, control, t0)
    out.close()
    return 0


def serve_readings(args, cell, config, traffic) -> int:
    from bench import compare_serve, faults
    from bench.drive_serve import ServeRun
    serve = ServeRun(args.workload, cell, config, traffic)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    plan = ([("program", s) for s in args.seeds]
            + [("fault:altered_serve", s) for s in args.faults]
            + [("control", s) for s in args.control])
    with open(args.out, "a") as out:
        try:
            for who, seed in plan:
                t0 = time.perf_counter()
                if who == "control":
                    serve.start(seed)
                    numbers = {"logit_gap": compare_serve.control_gap(serve)}
                    details = {}
                else:
                    fault = (faults.plant("altered_serve")
                             if who.startswith("fault")
                             else contextlib.nullcontext())
                    with fault:
                        serve.start(seed)
                        win = serve.window(seed, traffic["rate_per_s"],
                                           args.seconds)
                    numbers, details = compare_serve.compare(serve, win, seed)
                line = dict(who=who, seed=seed, numbers=numbers,
                            details=details,
                            seconds=time.perf_counter() - t0)
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")
                out.flush()
        finally:
            serve.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
