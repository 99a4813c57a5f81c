"""What every cell shares: finding files by name, the compile cache, the
device, seeds, and the result line."""
from __future__ import annotations

import importlib.util
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(ROOT, ".bench_cache")


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, a missing file, a
    configuration whose graph or fleet does not resolve)."""


def load_json(kind: str, name: str) -> dict:
    path = os.path.join(BENCH, kind, f"{name}.json")
    if not os.path.exists(path):
        raise BenchError(f"no {kind[:-1]} named {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str):
    """→ (cell, config, traffic) dicts of one workload, found by name."""
    cell = load_json("workloads", name)
    return (cell, load_json("configs", cell["config"]),
            load_json("traffic", cell["traffic"]))


def build_graph(config: dict, args: dict | None = None):
    """The graph a configuration names in ``graph``, by exactly one of
    ``builder`` (a Table-2 builder of ``repro.graphs.PAPER_BENCHMARKS``,
    called with ``args``) or ``workload`` (a corpus spec of
    ``repro.graphs.build_corpus`` that yields one graph).  The graph has to
    have the ``nodes`` and ``edges`` the entry states.  ``args`` builds a
    variant of a builder's graph in place of the entry's own ``args``; the
    entry's counts are not the variant's, so a variant is not counted."""
    spec = config["graph"]
    forms = [k for k in ("builder", "workload") if k in spec]
    if len(forms) != 1:
        raise BenchError(f"graph {spec!r} names {forms or 'neither'}: give "
                         f"exactly one of 'builder' and 'workload'")
    if "builder" in spec:
        from repro.graphs import PAPER_BENCHMARKS
        if spec["builder"] not in PAPER_BENCHMARKS:
            raise BenchError(f"unknown graph builder {spec['builder']!r}; "
                             f"known: {sorted(PAPER_BENCHMARKS)}")
        try:
            graph = PAPER_BENCHMARKS[spec["builder"]](
                **(spec.get("args", {}) if args is None else args))
        except TypeError as e:
            raise BenchError(f"graph builder {spec['builder']!r}: {e}"
                             ) from None
    else:
        if args is not None:
            raise BenchError(f"graph workload {spec['workload']!r} has no "
                             f"variants")
        from repro.graphs import build_corpus
        try:
            graphs = build_corpus(spec["workload"], stream=False)
        except (KeyError, ValueError) as e:
            raise BenchError(f"graph workload {spec['workload']!r}: {e}"
                             ) from None
        if len(graphs) != 1:
            raise BenchError(f"graph workload {spec['workload']!r} yields "
                             f"{len(graphs)} graphs; a cell takes one")
        graph = graphs[0]
    if args is None:
        for what, have in (("nodes", graph.num_nodes),
                           ("edges", graph.num_edges)):
            if what not in spec:
                raise BenchError(f"graph {spec!r} states no {what!r}")
            if have != spec[what]:
                raise BenchError(f"graph {spec!r} built with {have} {what}, "
                                 f"not the {spec[what]} it states")
    return graph


def build_platform(config: dict):
    """The fleet a configuration names in ``platform``, a platform spec
    (``name:key=value:...``) built by the program's platform registry.  Its
    device count has to be the policy's ``num_devices``."""
    from repro.api.spec import (PlacementSpec, build_platform as build,
                                parse_platform_spec)
    try:
        name, args = parse_platform_spec(config["platform"])
        platform = build(PlacementSpec(workload="", platform=name,
                                       platform_args=args))
    except ValueError as e:
        raise BenchError(f"platform {config['platform']!r}: {e}") from None
    want = config["policy"]["num_devices"]
    if platform.num_devices != want:
        raise BenchError(f"platform {config['platform']!r} has "
                         f"{platform.num_devices} devices; the policy places "
                         f"on num_devices={want}")
    return platform


def benchmark_doc() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metric_reader(name: str):
    """The ``read(ctx)`` function of bench/metrics/<name>.py."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise BenchError(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(cell_name: str, trace: bool, doc: dict | None = None):
    """The metric entries of BENCHMARK.json this cell reports."""
    doc = doc if doc is not None else benchmark_doc()
    group = doc["per_layer"] if trace else doc["end_to_end"]
    return [m for m in group
            if cell_name in m.get("workloads", [cell_name])]


def use_program():
    """Put the program's sources on the path, and its compile cache and the
    TPU runtime's logs in the checkout (the cache where
    JAX_COMPILATION_CACHE_DIR says, if set)."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    # The TPU runtime logs under /tmp unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(CACHE, "tpu_logs"))
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CACHE, "jax")
    os.makedirs(cache, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def require_chips(chips: int):
    """The devices of a TPU run, or BenchError: never a CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found platform "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchError(f"cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json")
    return table[device_kind]


def seed_key(seed: int):
    """A JAX key and a NumPy generator from any whole-number seed."""
    import jax.numpy as jnp
    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(state, jnp.uint32), np.random.default_rng(int(seed))


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def emit(result: dict, checks: dict) -> None:
    """Print the compared numbers last on stderr and the result line last on
    stdout, with the numbers under their own key at the end.  A number that
    is not finite (a comparison that could not be made) is written as the
    string "inf" or "nan", so the line stays JSON."""
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    out = dict(result)
    out["checks"] = {
        k: dict(c, value=c["value"] if np.isfinite(c["value"])
                else str(c["value"]))
        for k, c in checks.items()}
    print(json.dumps(out, allow_nan=False), flush=True)
