"""RewardPipeline — one reward interface for every rollout path.

The RL engine samples placements in jitted windows; *something* must turn
them into rewards.  Before this layer each reward source had its own wiring
(`simulate_jax` hardcoded in the fused closures, host ``reward_fn`` loops in
the drivers).  A pipeline normalizes them to two hooks:

* ``fused`` pipelines expose :meth:`step_score` — the per-placement hook
  the jitted rollout window vmaps over all of its placements after the
  step scan, so rewards are computed device-side in one simulator walk per
  window (the ``scan`` backend; zero host round-trips per window).
* every pipeline exposes :meth:`score_window` — given the (T, B, V) or
  (T, G, B, V_max) placements a window produced, return (rewards,
  latencies).  ``jit_window`` backends (``level``) run one batched device
  call; the ``reference`` backend and user ``reward_fn`` callables
  (``MeasuredExecutor`` — the paper's wall-clock slot) loop on the host in
  the same (t, g, b) order the PR-1 scalar engine established.

The async-reward roadmap item slots in here: a double-buffered pipeline only
has to overlap :meth:`score_window` with the next window's rollout.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import jax
import numpy as np

from .base import SimulatorBackend, get_backend

__all__ = ["RewardPipeline"]


class RewardPipeline:
    """Scores rollout windows; see module docstring."""

    def __init__(self, *, backend: Optional[SimulatorBackend] = None,
                 prep=None, multi_prep=None,
                 reward_fn: Optional[Callable] = None,
                 num_nodes: Optional[Sequence[int]] = None):
        if (backend is None) == (reward_fn is None):
            raise ValueError("pass exactly one of backend= or reward_fn=")
        self.backend = backend
        self.prep = prep
        self.multi_prep = multi_prep
        self.reward_fn = reward_fn
        self._num_nodes = list(num_nodes) if num_nodes is not None else None

    # ------------------------------------------------------------ construction
    @classmethod
    def from_reward_fn(cls, reward_fn: Callable, *,
                       num_nodes: Optional[int] = None) -> "RewardPipeline":
        """Host callable ``fn(fine_placement) -> (reward, latency)``.

        ``num_nodes`` is the graph's true node count: a bucket-padded
        rollout produces (V_max,) placement rows, and the callable (the
        ``MeasuredExecutor`` slot) must see only the ``:num_nodes`` prefix —
        pad slots are policy noise, not ops.
        """
        nn = [int(num_nodes)] if num_nodes is not None else None
        return cls(reward_fn=reward_fn, num_nodes=nn)

    @classmethod
    def from_platform(cls, graph, platform,
                      backend: str = "scan") -> "RewardPipeline":
        """Single-graph pipeline over a registered simulator backend."""
        b = get_backend(backend) if isinstance(backend, str) else backend
        return cls(backend=b, prep=b.prepare(graph, platform),
                   num_nodes=[graph.num_nodes])

    @classmethod
    def from_graphs(cls, graphs: Sequence, platform, *,
                    backend: str = "scan",
                    v_max: Optional[int] = None) -> "RewardPipeline":
        """Multi-graph pipeline over a padded batch."""
        b = get_backend(backend) if isinstance(backend, str) else backend
        return cls(backend=b,
                   multi_prep=b.prepare_batch(graphs, platform, v_max=v_max),
                   num_nodes=[g.num_nodes for g in graphs])

    # ---------------------------------------------------------------- queries
    @property
    def fused(self) -> bool:
        return self.backend is not None and self.backend.jit_fused

    @property
    def sim_tree(self):
        """The pytree a fused pipeline threads into the jitted rollout.

        Single-graph preps contribute their dense arrays with a G=1 leading
        axis; multi-graph preps are already stacked (``SimArraysBatch``).
        """
        if not self.fused:
            return None
        if self.multi_prep is not None:
            return self.multi_prep.arrays
        import jax
        return jax.tree.map(lambda a: np.asarray(a)[None],
                            self.prep.arrays)

    def step_score(self, sim_tree, placement):
        """In-jit per-placement hook (fused pipelines only), under the
        ``repro.sim`` scope whatever the backend; the rollout window vmaps
        it over the window's placements (``rollout.fused_window_scores``)."""
        with jax.named_scope("repro.sim"):
            return self.backend.score(sim_tree, placement)

    # ---------------------------------------------------------------- scoring
    def score_window(self, fines: np.ndarray):
        """(T, B, V) or (T, G, B, V_max) placements → (rewards, latencies)
        with the same leading shape, float64 on the host."""
        fines = np.asarray(fines)
        if fines.ndim == 3:
            return self._score_single(fines)
        if fines.ndim == 4:
            return self._score_multi(fines)
        raise ValueError(f"expected (T, B, V) or (T, G, B, V) placements; "
                         f"got {fines.shape}")

    def _score_single(self, fines):
        T, B, V = fines.shape
        # Bucket-padded rollouts hand (V_max,) rows; only the ``:nn`` prefix
        # is real ops — the same trim _score_multi applies per graph.
        nn = self._num_nodes[0] if self._num_nodes else V
        if self.reward_fn is not None:
            rewards = np.empty((T, B))
            latencies = np.empty((T, B))
            for t in range(T):            # (t, b) order — scalar-engine order
                for b in range(B):
                    rewards[t, b], latencies[t, b] = self.reward_fn(
                        fines[t, b, :nn])
            return rewards, latencies
        res = self.backend.simulate_batch(self.prep,
                                          fines[:, :, :nn].reshape(T * B, nn))
        return (np.asarray(res.reward, np.float64).reshape(T, B),
                np.asarray(res.latency, np.float64).reshape(T, B))

    def _score_multi(self, fines):
        T, G, B, V = fines.shape
        if self.reward_fn is not None:
            rewards = np.empty((T, G, B))
            latencies = np.empty((T, G, B))
            for t in range(T):
                for g in range(G):
                    nn = self._num_nodes[g] if self._num_nodes else V
                    for b in range(B):
                        rewards[t, g, b], latencies[t, g, b] = \
                            self.reward_fn(fines[t, g, b, :nn])
            return rewards, latencies
        # (G, T·B, V) — one batched call per graph axis entry
        flat = np.transpose(fines, (1, 0, 2, 3)).reshape(G, T * B, V)
        res = self.backend.simulate_multi(self.multi_prep, flat)
        rewards = np.transpose(
            np.asarray(res.reward, np.float64).reshape(G, T, B), (1, 0, 2))
        latencies = np.transpose(
            np.asarray(res.latency, np.float64).reshape(G, T, B), (1, 0, 2))
        return rewards, latencies
