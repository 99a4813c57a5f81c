"""Search cells: the paper's training loop, window after window.

Set-up builds one object: the program's ``HSDAG`` with the seed's weights,
its rollout engine over the fused ``scan`` simulator, and the chain keys.
It drives that object through the first ``check_windows`` windows (which
compiles both ``start_first`` variants of the rollout and replay programs)
and records each of them (parameters and chain state before it, what it
sampled, its gradient) for the comparison.  The measured window then goes on with
the same object until ``--seconds`` have passed, in whole windows, and ends
with a sync on the updated parameters.

One window is the body of ``HSDAG._search_batched``: the rollout program
samples and scores T steps of B chains, the host copies the placements and
rewards back, updates the moving-average baseline and the best placement
chain step by chain step, computes the Eq.-14 step weights, and the replay
program's gradient goes through the program's Adam update.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from . import common


def hsdag_config(config: dict, traffic: dict):
    from repro.core import HSDAGConfig
    return HSDAGConfig(**config["policy"], batch_chains=traffic["chains"],
                       update_timestep=traffic["window_steps"],
                       engine=traffic["engine"])


def to_host(tree):
    import jax
    return jax.tree.map(lambda a: np.asarray(a), tree)


class SearchRun:
    """The program's search loop over one graph, from one seed."""

    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int):
        import jax
        import jax.numpy as jnp
        from repro.core import HSDAG
        from repro.core.features import extract_features, shared_feature_config
        from repro.core.sim import RewardPipeline
        from . import ref_policy

        self.cell, self.config, self.traffic = cell, config, traffic
        self.graph = common.build_graph(config)
        self.platform = common.build_platform(config)
        self.arrays = extract_features(
            self.graph, shared_feature_config([self.graph]))
        self.cfg = hsdag_config(config, traffic)
        self.B, self.T = self.cfg.batch_chains, self.cfg.update_timestep
        self._init = jax.jit(lambda k: ref_policy.init_params(
            k, self.arrays.x.shape[1], config["policy"]))
        self.agent = HSDAG(self.cfg)
        if traffic.get("mesh"):
            self.engine = MeshEngine(self.agent, self.graph, self.arrays,
                                     self.platform, traffic)
        else:
            pipeline = RewardPipeline.from_platform(
                self.graph, self.platform, traffic["engine"])
            self.engine = self.agent._engine_single(self.arrays, pipeline)
        x0 = jnp.asarray(self.arrays.x)
        self.z0 = jnp.broadcast_to(x0, (1, self.B) + x0.shape)
        self.reset(seed)

    def reset(self, seed: int):
        """Fresh weights, optimizer state, chains and records from a seed;
        the compiled programs stay."""
        import jax
        self.key, self.rng = common.seed_key(seed)
        k_params, k_chains = jax.random.split(self.key)
        self.params0 = self._init(k_params)
        self.agent.init(k_params, self.arrays)    # Adam state: zeros, step 0
        self.agent.params = self.params0
        self.chain_rngs = chain_keys(k_chains, self.B)
        self.first = True
        self.z = self.z0
        self.baseline = None
        self.best_latency = float("inf")
        self.best_placement = None
        self.failed_placements = 0
        self.samples = []       # (placement, latency) drawn in the window
        self.records = []       # the check windows, in full

    # ------------------------------------------------------------ one window
    def window(self, *, record: bool = False, sample: int = 0):
        import jax
        import jax.numpy as jnp
        from repro.core.reinforce import RunningBaseline, step_weights
        cfg, T, B = self.cfg, self.T, self.B
        ann = jax.profiler.TraceAnnotation
        params_before = self.agent.params
        with ann("bench.rollout"):
            (z, self.chain_rngs, keys, fines, ngroups, rewards,
             latencies) = self.engine.rollout_window(
                self.agent.params, self.z, self.chain_rngs, num_steps=T,
                start_first=self.first)
            fines_np = np.asarray(fines)[:, 0]
            rewards = np.asarray(rewards, dtype=np.float64)[:, 0]
            latencies = np.asarray(latencies, dtype=np.float64)[:, 0]
        with ann("bench.bookkeeping"):
            if cfg.use_baseline and self.baseline is None:
                self.baseline = RunningBaseline()
            self.failed_placements += int(np.sum(~np.isfinite(latencies)))
            for t in range(T):
                for b in range(B):
                    if self.baseline is not None:
                        self.baseline.update(rewards[t, b])
                    if latencies[t, b] < self.best_latency:
                        self.best_latency = float(latencies[t, b])
                        self.best_placement = fines_np[t, b].astype(np.int64)
        with ann("bench.step_weights"):
            weights_bt = step_weights(
                rewards.T, cfg.gamma, reward_to_go=cfg.reward_to_go,
                baseline=(self.baseline.value if self.baseline is not None
                          else None),
                normalize=cfg.normalize_weights)
            weights = jnp.asarray(weights_bt.T)[:, None]
        grads = None
        for _ in range(max(1, cfg.k_epochs)):
            with ann("bench.replay"):
                grads = self.engine.window_grads(
                    self.agent.params, self.z, keys, weights, num_steps=T,
                    start_first=self.first)
            with ann("bench.update"):
                self.agent.apply_grads(grads)
        if record:
            self.records.append(dict(
                z=self.z, first=self.first,
                keys=np.asarray(keys)[:, 0],
                fines=fines_np, ngroups=np.asarray(ngroups)[:, 0],
                latencies=latencies, weights=np.asarray(weights)[:, 0],
                grads=to_host(grads), params_before=params_before))
        for _ in range(sample):
            t, b = self.rng.integers(T), self.rng.integers(B)
            self.samples.append((fines_np[t, b].copy(), latencies[t, b]))
        self.z = z
        self.first = False

    # ---------------------------------------------------------------- phases
    def setup(self):
        for _ in range(self.traffic["check_windows"]):
            self.window(record=True, sample=self.traffic["sample_per_window"])
        import jax
        jax.block_until_ready(self.agent.params)
        self.params_checked = to_host(self.agent.params)
        self.z_checked = self.z

    def measure(self, seconds: float, tracer=None):
        """Whole windows until ``seconds`` have passed → (windows, s)."""
        import jax
        start, n = time.perf_counter(), 0
        self.failed_placements = 0
        traced = self.traffic["trace_windows"] if tracer is not None else 0
        while True:
            if tracer is not None and n == 0:
                tracer.start()
            self.window(sample=self.traffic["sample_per_window"])
            n += 1
            if tracer is not None and n == traced:
                jax.block_until_ready(self.agent.params)
                tracer.stop(windows=n)
            if n >= traced and time.perf_counter() - start >= seconds:
                break
        jax.block_until_ready(self.agent.params)
        return n, time.perf_counter() - start

    def program_grads(self, rec, weights):
        """The window's own compiled replay, on recorded inputs."""
        import jax.numpy as jnp
        return to_host(self.engine.window_grads(
            rec["params_before"], rec["z"], jnp.asarray(rec["keys"])[:, None],
            jnp.asarray(weights, jnp.float32)[:, None], num_steps=self.T,
            start_first=rec["first"]))


class MeshEngine:
    """The program's sharded engine (``ShardedRolloutEngine``) over one
    graph on a ("graphs", "chains") mesh of the traffic's shape, bound to the
    graph's operands so it is called like the one-chip engine.  Its replay
    reduces the gradient over the chips in-mesh."""

    def __init__(self, agent, graph, arrays, platform, traffic: dict):
        import jax
        import jax.numpy as jnp
        from repro.core.costmodel import sim_arrays_batch
        from repro.core.features import batch_graph_arrays
        from repro.core.sim import (GraphOperands, ShardedRolloutEngine,
                                    get_backend)
        self.engine = ShardedRolloutEngine(
            agent._step, agent.cfg, backend=get_backend(traffic["engine"]),
            mesh_shape=tuple(traffic["mesh"]))
        ga = batch_graph_arrays([arrays])
        sim = sim_arrays_batch([graph], platform)
        self.ops = GraphOperands(
            x0=jnp.asarray(ga.x), adj=jnp.asarray(ga.adj),
            edges=jnp.asarray(ga.edges), node_mask=jnp.asarray(ga.node_mask),
            edge_mask=jnp.asarray(ga.edge_mask),
            sim=jax.tree.map(jnp.asarray, sim.arrays))

    def rollout_window(self, params, z, rngs, *, num_steps, start_first):
        return self.engine.rollout_window(
            self.ops, params, z, rngs, num_steps=num_steps,
            start_first=start_first)

    def window_grads(self, params, z0, keys, weights, *, num_steps,
                     start_first):
        return self.engine.window_grads(
            self.ops, params, z0, keys, weights, num_steps=num_steps,
            start_first=start_first)


def chain_keys(key, num_chains: int):
    """(1, B, 2) chain keys: the run's key and B-1 keys folded from it."""
    import jax
    import jax.numpy as jnp
    folded = jax.jit(jax.vmap(jax.random.fold_in, in_axes=(None, 0)))(
        key, jnp.arange(1, num_chains, dtype=jnp.uint32))
    return jnp.concatenate([key[None], folded])[None]


def run(cell, config, traffic, args, *, devices, peaks, trace_dir, t_start):
    """One run of a search cell → the result dict before ``correct``."""
    import jax
    from . import compare_search, flops
    from .trace import Tracer

    search = SearchRun(cell, config, traffic, args.seed)
    search.setup()
    setup_s = time.perf_counter() - t_start
    tracer = Tracer(trace_dir) if trace_dir else None
    windows, seconds = search.measure(args.seconds, tracer)
    placements = windows * search.B * search.T
    device = common.device_info(devices)

    # The window is closed and the memory peak read; drop the chains' state
    # before the reference runs.
    search.z = None
    best = (search.best_placement, search.best_latency)
    t_ref = time.perf_counter()
    numbers, details = compare_search.compare(
        search.graph, search.platform, search.arrays, search.records,
        search.samples, best, to_host(search.params0), search.params_checked,
        search.z_checked, search.program_grads, config,
        block=traffic["reference_block"])
    print(f"bench: the comparison took {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr, flush=True)
    limits = cell["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    out = dict(attempted=placements, failed=search.failed_placements,
               device=device, checks=checks, details=details,
               metrics={"placements_per_s": {"value": placements / seconds,
                                             "unit": "placements/s"},
                        "setup_s": {"value": setup_s, "unit": "s"}})
    if tracer is not None:
        trace = tracer.load()
        busy = trace.busy_s()
        with open(os.path.join(common.CACHE, "last_trace.json"), "w") as f:
            json.dump(dict(summary=trace.summary(), details=details), f)
        out["device"].update(busy_s=busy, window_s=tracer.window_s)
        out["breakdown"] = trace.breakdown()
        per_window = flops.train_window(
            search.graph.num_nodes, len(search.arrays.edges),
            search.arrays.x.shape[1], config["policy"], chains=search.B,
            steps=search.T, start_first=False)
        out["ctx"] = dict(trace=trace, window_s=tracer.window_s,
                          windows=tracer.units["windows"],
                          model_ops=per_window * tracer.units["windows"],
                          chips=len(devices), peaks=peaks, busy_s=busy)
    return out
