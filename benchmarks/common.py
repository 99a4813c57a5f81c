"""Shared benchmark scaffolding.

Output convention (benchmarks/run.py): CSV rows ``name,us_per_call,derived``.
``REPRO_BENCH_EPISODES`` scales RL search effort (default 12 — CI-friendly;
the paper's Appendix-H setting is 100.  Results monotonically improve with
episodes; the table structure is identical).

Machine-readable output: ``run.py --json-out DIR`` captures every
:func:`emit` row and writes one ``BENCH_<table>.json`` file per table —
rows carry the benchmark name, the emitting config (when the table passes
one), the metric and the host's ``physical_cores``, so results from
different machines stay comparable.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.api import PlacementSession, PlacementSpec
from repro.core import (HSDAG, HSDAGConfig, extract_features, FeatureConfig,
                        paper_platform, simulate)
from repro.core.baselines import (BaselineConfig, PlacetoBaseline,
                                  RNNBaseline, cpu_only, gpu_only,
                                  openvino_auto)
from repro.compile_cache import use_compile_cache
from repro.graphs import PAPER_BENCHMARKS

# Every benchmark script imports this module before its first compile.
use_compile_cache()

EPISODES = int(os.environ.get("REPRO_BENCH_EPISODES", "12"))
UPDATE_TIMESTEP = int(os.environ.get("REPRO_BENCH_TIMESTEP", "10"))

# ------------------------------------------------------------- JSON capture
_JSON: Dict = {"dir": None, "table": None, "rows": []}


def physical_cores() -> int:
    """Physical core count (unique (physical id, core id) pairs from
    /proc/cpuinfo); falls back to the logical count off-Linux."""
    try:
        pairs, phys = set(), None
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("physical id"):
                    phys = line.split(":", 1)[1].strip()
                elif line.startswith("core id"):
                    pairs.add((phys, line.split(":", 1)[1].strip()))
        if pairs:
            return len(pairs)
    except OSError:
        pass
    return os.cpu_count() or 1


def set_json_dir(path: str) -> None:
    """Start capturing emit() rows; flush_json() writes them under ``path``."""
    _JSON["dir"] = path
    _JSON["rows"] = []


def begin_table(table: str) -> None:
    """Tag subsequent emit() rows with ``table`` (run.py calls this before
    each table module's main)."""
    _JSON["table"] = table


def flush_json() -> List[str]:
    """Write one ``BENCH_<table>.json`` per captured table → file paths."""
    if _JSON["dir"] is None:
        return []
    os.makedirs(_JSON["dir"], exist_ok=True)
    by_table: Dict[str, List[dict]] = {}
    for row in _JSON["rows"]:
        by_table.setdefault(row.pop("table"), []).append(row)
    paths = []
    for table, rows in sorted(by_table.items()):
        path = os.path.join(_JSON["dir"], f"BENCH_{table}.json")
        with open(path, "w") as f:
            json.dump(rows, f, indent=1, sort_keys=True)
        paths.append(path)
    _JSON["rows"] = []
    return paths


def emit(name: str, us_per_call: float, derived: str,
         config: Optional[Dict] = None) -> None:
    print(f"{name},{us_per_call:.3f},{derived}")
    if _JSON["dir"] is not None:
        _JSON["rows"].append({
            "table": _JSON["table"] or "misc",
            "benchmark": name,
            "config": dict(config or {}),
            "metric": {"us_per_call": float(us_per_call),
                       "derived": derived},
            "physical_cores": physical_cores(),
        })


def reward_fn_for(graph, platform=None):
    platform = platform or paper_platform()

    def reward_fn(placement):
        res = simulate(graph, placement, platform)
        return res.reward, res.latency

    return reward_fn, platform


def run_hsdag(graph, arrays=None, feature_cfg: FeatureConfig = None,
              episodes: int = None, seed: int = 0,
              platform=None, batch_chains: int = 1,
              num_devices: int = 2) -> Tuple[np.ndarray, float, float]:
    """→ (placement, latency_s, wall_s), through the v1 facade.

    One search-mode :class:`PlacementSpec` per table row (in-process graph
    objects ride the ``fit(graphs=/arrays=)`` escape hatch — the facade is
    equivalence-pinned against the direct ``HSDAG.search`` path).
    ``batch_chains > 1`` switches to the batched multi-chain engine with the
    fused in-jit cost model (rewards computed device-side by ``simulate_jax``
    — no host round-trip per rollout step).
    """
    fc = feature_cfg or FeatureConfig(d_pos=16)
    arrays = arrays if arrays is not None else extract_features(graph, fc)
    feature = {k: v for k, v in dataclasses.asdict(fc).items()
               if not k.endswith("_vocab")}
    session = PlacementSession(PlacementSpec(
        workload="", mode="search", feature=feature,
        config=HSDAGConfig(
            num_devices=num_devices, max_episodes=episodes or EPISODES,
            update_timestep=UPDATE_TIMESTEP, use_baseline=True,
            normalize_weights=True, seed=seed, batch_chains=batch_chains)))
    if batch_chains > 1:
        res = session.fit(graphs=[graph], arrays=[arrays],
                          platform=platform or paper_platform(),
                          rng=jax.random.PRNGKey(seed))
    else:
        reward_fn, _ = reward_fn_for(graph, platform)
        res = session.fit(graphs=[graph], arrays=[arrays],
                          reward_fn=reward_fn, rng=jax.random.PRNGKey(seed))
    return res.best_placement, res.best_latency, res.wall_time_s


def run_placeto(graph, episodes: int = None, seed: int = 0):
    arrays = extract_features(graph, FeatureConfig(d_pos=16))
    reward_fn, _ = reward_fn_for(graph)
    res = PlacetoBaseline(BaselineConfig(
        num_devices=2, episodes=episodes or EPISODES,
        samples_per_episode=UPDATE_TIMESTEP, seed=seed)).search(
        graph, arrays, reward_fn, rng=jax.random.PRNGKey(seed))
    return res.best_placement, res.best_latency, res.wall_time_s


def run_rnn(graph, episodes: int = None, seed: int = 0):
    arrays = extract_features(graph, FeatureConfig(d_pos=16))
    reward_fn, _ = reward_fn_for(graph)
    res = RNNBaseline(BaselineConfig(
        num_devices=2, episodes=episodes or EPISODES,
        samples_per_episode=UPDATE_TIMESTEP, seed=seed)).search(
        graph, arrays, reward_fn, rng=jax.random.PRNGKey(seed))
    return res.best_placement, res.best_latency, res.wall_time_s


def single_device_latencies(graph) -> Dict[str, float]:
    plat = paper_platform()
    return {
        "cpu_only": simulate(graph, cpu_only(graph), plat).latency,
        "gpu_only": simulate(graph, gpu_only(graph), plat).latency,
    }
