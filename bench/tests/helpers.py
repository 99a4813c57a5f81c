"""A throwaway checkout with tiny cells, for driving whole runs on the CPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_GRAPH = {"builder": "bert_base", "args": {"layers": 1, "seq_len": 8},
              "nodes": 94, "edges": 97}


def _dump(path, doc):
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)


def tiny_checkout(tmp_path) -> str:
    """Copy the benchmark, link the program, and add tiny cells as data:
    ``search.tiny``, ``search.tiny_fleet``, ``mesh.tiny`` (four virtual CPU
    devices) and ``serve.tiny``.  No code is edited."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "bert_base.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny", graph=TINY_GRAPH)
    _dump(os.path.join(b, "configs", "tiny.json"), cfg)
    # A graph of a corpus provider on a fleet of the platform registry: one
    # traced transformer layer (jaxpr primitives) on two NVLink islands of two.
    cfg = dict(cfg, name="tiny_fleet",
               graph={"workload": "traced:archs=qwen1.5-0.5b", "nodes": 40,
                      "edges": 47},
               platform="nvlink_island:islands=2:gpus_per_island=2",
               policy=dict(cfg["policy"], num_devices=4))
    _dump(os.path.join(b, "configs", "tiny_fleet.json"), cfg)
    with open(os.path.join(b, "traffic", "search.json")) as f:
        search = json.load(f)
    search.update(chains=8, window_steps=4, trace_windows=1,
                  reference_block=4)
    _dump(os.path.join(b, "traffic", "search_tiny.json"), search)
    with open(os.path.join(b, "traffic", "search_mesh4.json")) as f:
        mesh = json.load(f)
    mesh.update(window_steps=4, trace_windows=1)
    _dump(os.path.join(b, "traffic", "search_mesh_tiny.json"), mesh)
    with open(os.path.join(b, "traffic", "serve.json")) as f:
        serve = json.load(f)
    serve.update(rate_per_s=20.0, variants=[[1, 8], [2, 8], [1, 16]],
                 trace_seconds=1.0)
    _dump(os.path.join(b, "traffic", "serve_tiny.json"), serve)
    doc_path = os.path.join(root, "BENCHMARK.json")
    with open(doc_path) as f:
        doc = json.load(f)
    serve_limits = {"unanswered": 0.0, "logit_gap": 0.0}
    for name, config, traffic, model, chips in (
            ("search.tiny", "tiny", "search_tiny", "search_chain1.bert_base",
             1),
            ("search.tiny_fleet", "tiny_fleet", "search_tiny",
             "search_chain1.bert_base", 1),
            ("mesh.tiny", "tiny", "search_mesh_tiny",
             "search_chain1.bert_base", 4),
            ("serve.tiny", "tiny", "serve_tiny", "serve.bert_base", 1)):
        with open(os.path.join(b, "workloads", f"{model}.json")) as f:
            cell = json.load(f)
        cell.update(config=config, traffic=traffic, chips=chips)
        cell.setdefault("limits", serve_limits)
        _dump(os.path.join(b, "workloads", f"{name}.json"), cell)
        doc["workloads"].append({"name": name, "config": config,
                                 "traffic": traffic, "chips": chips,
                                 "why": cell["why"]})
        for m in doc["end_to_end"] + doc["per_layer"]:
            if model in m.get("workloads", []):
                m["workloads"].append(name)
    # The serve cell's metrics, added as data the way a later PR adds them.
    serve = ["serve.tiny"]
    doc["end_to_end"] += [
        {"name": n, "unit": "ms", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": serve}
        for n in ("serve_p50_ms", "serve_p95_ms")
        if n not in {m["name"] for m in doc["end_to_end"]}]
    doc["per_layer"] += [
        {"name": n, "unit": u, "better": b, "source": src, "layer": layer,
         "moves": "serve_p95_ms", "workloads": serve}
        for n, u, b, src, layer in (
            ("decode_ms.serve", "ms", "lower", "device_trace", "greedy decode"),
            ("batch_fill.serve", "%", "higher", "program_counter", "server"),
            ("idle_share.serve", "%", "lower", "device_trace", "device"))
        if n not in {m["name"] for m in doc["per_layer"]}]
    doc["configs"] += [{"name": n, "source": "test",
                        "file": f"bench/configs/{n}.json", "reduced": [],
                        "why": "test"} for n in ("tiny", "tiny_fleet")]
    _dump(doc_path, doc)
    return root


DRIVER = """
import argparse, json, sys
sys.path.insert(0, ".")
from bench import common, faults, run
common.use_program()
for spec in json.loads(sys.argv[1]):
    args = argparse.Namespace(workload=spec["workload"], seed=spec["seed"],
                              seconds=spec["seconds"], trace=spec["trace"])
    if spec.get("fault"):
        with faults.plant(spec["fault"]):
            res = run.run(args, require_tpu=False)
    else:
        res = run.run(args, require_tpu=False)
    print("RESULT " + json.dumps(dict(spec=spec, result=res), default=str),
          flush=True)
"""


def run_cells(root: str, specs, timeout: int = 900):
    """Run cells in one CPU process from ``root`` → list of results."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", DRIVER, json.dumps(specs)],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return [json.loads(line[len("RESULT "):])
            for line in proc.stdout.splitlines() if line.startswith("RESULT ")]
