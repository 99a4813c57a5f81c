"""Table 6 (repo-local): rollout-engine throughput — placements evaluated/sec.

Two measurements per graph:

* ``rollout_throughput_sim_*``   — the reward source alone: host Python
  list-scheduler ``simulate`` vs the batched simulator backends (the
  ``backend=`` field of the derived column compares the jitted+vmapped
  ``scan`` kernel against the level-major ``level`` Pallas kernel; the
  row's ``mode=`` says whether the kernel was compiled (TPU) or ran under
  interpret=True (CPU backend), where its number is no device rate).
* ``rollout_throughput_search_*`` — the full RL loop (Alg. 1): per-step
  host-reward scalar engine vs the fused B-chain engine with in-jit rewards.
  Steady-state rate (first, compile-bearing episode dropped).

* ``rollout_window_*`` — chain-scale sweep: one jitted window
  (rollout + reward) per backend at B ∈ ``REPRO_BENCH_SWEEP_CHAINS``
  (default 16,64,256,1024), reporting evals/s and evals/s **per chain** —
  the number that shows where widening the population stops being free.

Rows land in ``BENCH_*.json`` so the scalar→batched speedup is
regression-checkable.  Env knobs: ``REPRO_BENCH_CHAINS`` (default 16),
``REPRO_BENCH_THROUGHPUT_GRAPHS`` (csv; default inception_v3 — the search
measurement is minutes-per-graph), ``REPRO_BENCH_THROUGHPUT_EPISODES``
(default 3), ``REPRO_BENCH_LEVEL_BACKEND`` (=0 skips the level rows),
``REPRO_BENCH_SWEEP_CHAINS`` (=empty skips the sweep),
``REPRO_BENCH_SWEEP_TIMESTEP`` / ``REPRO_BENCH_SWEEP_BUDGET``.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (HSDAG, HSDAGConfig, FeatureConfig, extract_features,
                        get_backend, paper_platform, simulate, simulate_batch)
from repro.core.costmodel import sim_arrays
from repro.core.sim import RewardPipeline
from repro.core.train import make_chain_rngs
from repro.graphs import PAPER_BENCHMARKS
from repro.kernels.ops import default_interpret

from common import emit

CHAINS = int(os.environ.get("REPRO_BENCH_CHAINS", "16"))
SEARCH_GRAPHS = os.environ.get(
    "REPRO_BENCH_THROUGHPUT_GRAPHS", "inception_v3").split(",")
SEARCH_EPISODES = int(os.environ.get("REPRO_BENCH_THROUGHPUT_EPISODES", "3"))
SEARCH_TIMESTEP = int(os.environ.get("REPRO_BENCH_THROUGHPUT_TIMESTEP", "10"))
LEVEL_ROWS = os.environ.get("REPRO_BENCH_LEVEL_BACKEND", "1") != "0"
SWEEP_CHAINS = [int(b) for b in os.environ.get(
    "REPRO_BENCH_SWEEP_CHAINS", "16,64,256,1024").split(",") if b]
SWEEP_TIMESTEP = int(os.environ.get("REPRO_BENCH_SWEEP_TIMESTEP", "4"))
SWEEP_BUDGET = float(os.environ.get("REPRO_BENCH_SWEEP_BUDGET", "1.0"))


def _sim_rates(graph, plat, budget_s: float = 2.0):
    rng = np.random.default_rng(0)
    batch = rng.integers(0, 2, size=(CHAINS, graph.num_nodes))
    # Prebuilt SimArrays threaded through every call — the cache-key
    # re-derivation (hashing edge/flops buffers) is off the measured path.
    sa = sim_arrays(graph, plat)
    simulate_batch(graph, batch, plat, sim=sa)      # warm the jit cache

    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < budget_s:
        simulate(graph, batch[n % CHAINS], plat)
        n += 1
    scalar = n / (time.perf_counter() - t0)

    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < budget_s:
        simulate_batch(graph, batch, plat, sim=sa)
        n += CHAINS
    batched = n / (time.perf_counter() - t0)

    level = None
    if LEVEL_ROWS:
        backend = get_backend("level")
        prep = backend.prepare(graph, plat)
        backend.simulate_batch(prep, batch)         # warm/compile
        t0 = time.perf_counter()
        n = 0
        while n == 0 or time.perf_counter() - t0 < budget_s:
            backend.simulate_batch(prep, batch)
            n += CHAINS
        level = n / (time.perf_counter() - t0)
    return scalar, batched, level


def _search_rate(graph, arrays, plat, batch_chains: int) -> float:
    """Steady-state placements/sec of one search (compile episode dropped)."""
    cfg = HSDAGConfig(num_devices=2, max_episodes=SEARCH_EPISODES,
                      update_timestep=SEARCH_TIMESTEP,
                      batch_chains=batch_chains)
    agent = HSDAG(cfg)
    if batch_chains > 1:
        res = agent.search(graph, arrays, platform=plat,
                           rng=jax.random.PRNGKey(0))
    else:
        def reward_fn(p):
            r = simulate(graph, p, plat)
            return r.reward, r.latency
        res = agent.search(graph, arrays, reward_fn,
                           rng=jax.random.PRNGKey(0), engine="scalar")
    walls = [h["wall_s"] for h in res.history[1:]] or \
        [h["wall_s"] for h in res.history]
    return SEARCH_TIMESTEP * batch_chains * len(walls) / sum(walls)


def _window_sweep(name, graph, arrays, plat) -> None:
    """evals/s (and per-chain) of one jitted window at each B × backend."""
    tsteps = SWEEP_TIMESTEP
    for backend in ["scan"] + (["level"] if LEVEL_ROWS else []):
        pipeline = RewardPipeline.from_platform(graph, plat, backend)
        for B in SWEEP_CHAINS:
            cfg = HSDAGConfig(num_devices=2, batch_chains=B,
                              update_timestep=tsteps)
            agent = HSDAG(cfg)
            agent.init(jax.random.PRNGKey(0), arrays)
            engine = agent._engine_single(arrays, pipeline)
            x0 = jnp.asarray(arrays.x)
            z = jnp.broadcast_to(x0, (1, B) + x0.shape)
            rngs = make_chain_rngs(jax.random.PRNGKey(0), 1, B)

            def one_window(z, rngs):
                z, rngs, _, fines, _, _, lat = engine.rollout_window(
                    agent.params, z, rngs, num_steps=tsteps,
                    start_first=True)
                if pipeline.fused:
                    jax.block_until_ready(lat)
                else:
                    pipeline.score_window(np.asarray(fines)[:, 0])
                return z, rngs

            z, rngs = one_window(z, rngs)           # compile + warm
            t0 = time.perf_counter()
            n = 0
            while n == 0 or time.perf_counter() - t0 < SWEEP_BUDGET:
                z, rngs = one_window(z, rngs)
                n += 1
            rate = n * tsteps * B / (time.perf_counter() - t0)
            emit(f"rollout_window_{name}_{backend}_b{B}", 1e6 / rate,
                 f"evals_per_s={rate:.1f};per_chain={rate / B:.2f};"
                 f"backend={backend}",
                 config={"graph": name, "backend": backend,
                         "batch_chains": B, "update_timestep": tsteps})


def main() -> None:
    plat = paper_platform()
    for name, build in PAPER_BENCHMARKS.items():
        graph = build()
        scalar, batched, level = _sim_rates(graph, plat)
        emit(f"rollout_throughput_sim_{name}_scalar", 1e6 / scalar,
             f"evals_per_s={scalar:.1f};backend=reference",
             config={"graph": name, "backend": "reference"})
        emit(f"rollout_throughput_sim_{name}_b{CHAINS}", 1e6 / batched,
             f"evals_per_s={batched:.1f};speedup={batched / scalar:.2f}x;"
             f"backend=scan",
             config={"graph": name, "backend": "scan",
                     "batch_chains": CHAINS})
        if level is not None:
            emit(f"rollout_throughput_sim_{name}_b{CHAINS}_level",
                 1e6 / level,
                 f"evals_per_s={level:.1f};speedup={level / scalar:.2f}x;"
                 f"backend=level;mode="
                 f"{'interpret' if default_interpret() else 'compiled'}",
                 config={"graph": name, "backend": "level",
                         "batch_chains": CHAINS})

    for name in SEARCH_GRAPHS:
        if name not in PAPER_BENCHMARKS:
            continue
        graph = PAPER_BENCHMARKS[name]()
        arrays = extract_features(graph, FeatureConfig(d_pos=16))
        scalar = _search_rate(graph, arrays, plat, 1)
        batched = _search_rate(graph, arrays, plat, CHAINS)
        emit(f"rollout_throughput_search_{name}_scalar", 1e6 / scalar,
             f"evals_per_s={scalar:.2f}",
             config={"graph": name, "batch_chains": 1,
                     "update_timestep": SEARCH_TIMESTEP})
        emit(f"rollout_throughput_search_{name}_b{CHAINS}", 1e6 / batched,
             f"evals_per_s={batched:.2f};speedup={batched / scalar:.2f}x",
             config={"graph": name, "batch_chains": CHAINS,
                     "update_timestep": SEARCH_TIMESTEP})
        _window_sweep(name, graph, arrays, plat)


if __name__ == "__main__":
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    print("name,us_per_call,derived")
    main()
