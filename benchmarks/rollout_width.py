"""Rollout-window time at 16 and 256 chains on BERT-base.

    PYTHONPATH=src python benchmarks/rollout_width.py

Builds the search path's rollout engine at each chain count, with the graph
and Appendix-H policy of ``bench/configs/bert_base.json`` and the engine and
window length of ``bench/traffic/search_chain1.json``.  It compiles both
``start_first`` variants, then times five carried windows, as the search
loop runs them: the host clock around each synced call, and the rollout
program's device time from a profiler trace (the ``XLA Modules`` events of
the device plane; none on the CPU).  Only the rollout runs, not the replay.
The ``repro`` package comes from ``PYTHONPATH``, so pointing it at another
checkout's ``src`` times that version with the same script.  One JSON line
per chain count on standard output.
"""
import glob
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHAINS = (16, 256)
CALLS = 5


def _load(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


def device_ms(path: str):
    """Device time per rollout call, ms, from one profiler file."""
    from jax.profiler import ProfileData
    times = [ev.duration_ns for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/device:")
             for line in plane.lines if line.name == "XLA Modules"
             for ev in line.events if "rollout_window" in ev.name]
    return [t * 1e-6 for t in times]


def time_width(B: int) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core import HSDAG, HSDAGConfig, paper_platform
    from repro.core.features import extract_features, shared_feature_config
    from repro.core.sim import RewardPipeline
    from repro.graphs import PAPER_BENCHMARKS

    config = _load("configs", "bert_base.json")
    traffic = _load("traffic", "search_chain1.json")
    T = traffic["window_steps"]
    spec = config["graph"]
    graph = PAPER_BENCHMARKS[spec["builder"]](**spec["args"])
    arrays = extract_features(graph, shared_feature_config([graph]))
    agent = HSDAG(HSDAGConfig(**config["policy"], batch_chains=B,
                              update_timestep=T, engine=traffic["engine"]))
    agent.init(jax.random.PRNGKey(0), arrays)
    engine = agent._engine_single(
        arrays, RewardPipeline.from_platform(graph, paper_platform(),
                                             traffic["engine"]))
    x0 = jnp.asarray(arrays.x)
    z = jnp.broadcast_to(x0, (1, B) + x0.shape)
    rngs = jax.random.split(jax.random.PRNGKey(1), B)[None]

    t0 = time.perf_counter()
    out = engine.rollout_window(agent.params, z, rngs, num_steps=T,
                                start_first=True)
    out = engine.rollout_window(agent.params, out[0], out[1], num_steps=T,
                                start_first=False)
    jax.block_until_ready(out)
    setup_s = time.perf_counter() - t0

    wall = []
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(CALLS):
                t0 = time.perf_counter()
                out = engine.rollout_window(agent.params, out[0], out[1],
                                            num_steps=T, start_first=False)
                jax.block_until_ready(out)
                wall.append((time.perf_counter() - t0) * 1e3)
        files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        dev = device_ms(files[0]) if len(files) == 1 else []
    return {"chains": B, "steps": T, "nodes": graph.num_nodes,
            "device": jax.devices()[0].device_kind,
            "setup_s": setup_s, "wall_ms": wall,
            "wall_ms_median": statistics.median(wall),
            "device_ms": dev,
            "device_ms_median": statistics.median(dev) if dev else None,
            "finite_latencies": bool(jnp.all(jnp.isfinite(out[6])))}


def main() -> int:
    import repro
    src = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
    for B in CHAINS:
        row = time_width(B)
        row["src"] = os.path.relpath(src, ROOT)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
