#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json``; it names a configuration
(``bench/configs/``) and a traffic mix (``bench/traffic/``), whose ``kind``
picks the driver.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, each read by ``bench/metrics/<name>.py``
from a profiler trace of part of the window.  The last line of standard
output is one JSON object; the numbers that decided ``correct`` come last in
it and, each beside its limit, last on standard error.  A run that finds no
TPU, or fewer chips than the cell asks for, exits 2 and prints no result.
"""
import argparse
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import common  # noqa: E402


def per_layer(cell_name, ctx, doc):
    values = {}
    for m in common.cell_metrics(cell_name, trace=True, doc=doc):
        v = common.metric_reader(m["name"])(ctx)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    return values


def run(args, *, require_tpu: bool = True) -> dict:
    """One run of one cell → the result dict (``checks`` last)."""
    t_start = time.perf_counter()
    doc = common.benchmark_doc()
    cell, config, traffic = common.load_cell(args.workload)
    if "limits" not in cell:
        raise common.BenchError(
            f"cell {args.workload!r} has no limits yet: set them from "
            f"readings (bench/readings.py) before it is run")
    common.use_program()
    import jax
    if require_tpu:
        devices = common.require_chips(cell["chips"])
        peaks = common.peaks_for(devices[0].device_kind)
    else:
        devices = jax.devices()[:cell["chips"]]
        peaks = {"bf16_flops_per_s": float("nan")}
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(common.CACHE, "trace",
                                 f"{args.workload}.{args.seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    if traffic["kind"] == "search":
        from bench import drive_search as driver
    elif traffic["kind"] == "serve":
        from bench import drive_serve as driver
    else:
        raise common.BenchError(f"unknown traffic kind {traffic['kind']!r}")
    out = driver.run(cell, config, traffic, args, devices=devices,
                     peaks=peaks, trace_dir=trace_dir, t_start=t_start)
    if args.trace:
        out["metrics"] = per_layer(args.workload, out.pop("ctx"), doc)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        out.pop("ctx", None)
        names = {m["name"] for m in common.cell_metrics(args.workload, False,
                                                        doc)}
        out["metrics"] = {k: v for k, v in out["metrics"].items()
                          if k in names}
    checks = out.pop("checks")
    out["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    ordered = {k: out[k] for k in ("correct", "attempted", "failed",
                                   "metrics", "device")}
    if "breakdown" in out and args.trace:
        ordered["breakdown"] = out["breakdown"]
    ordered["checks"] = checks
    return ordered


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except common.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    checks = result.pop("checks")
    common.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
