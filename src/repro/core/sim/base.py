"""Simulator-backend protocol and registry.

A :class:`SimulatorBackend` turns a (graph, platform) pair into a prepared,
placement-independent handle (``prepare``/``prepare_batch``) and scores
placements against it (``simulate`` / ``simulate_batch`` / ``simulate_multi``
— single, (B, V) batch, (G, B, V_max) padded multi-graph).  Backends register
under a name; ``HSDAGConfig.engine`` and the reward pipeline resolve them
through :func:`get_backend`, so adding a backend is::

    class MyBackend(SimulatorBackend):
        name = "mine"
        ...
    register_backend(MyBackend())

Two capability flags drive how the RL engine consumes a backend:

* ``jit_fused`` — ``score(prep_tree, placement)`` is jit/vmap-composable;
  the jitted rollout window vmaps it over all of its placements after the
  step scan (rewards computed device-side, one simulator call per window).
* ``jit_window`` — scoring is jit-compatible at *window* granularity (one
  batched device call over every placement a rollout window produced) but
  not as a vmappable per-placement hook (e.g. a Pallas kernel that batches
  internally instead of vmapping).

Backends with neither flag score on the host (the reference scheduler).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

__all__ = ["SimulatorBackend", "register_backend", "get_backend",
           "backend_names", "stack_batch_results", "single_from_batch"]


def stack_batch_results(results: Sequence):
    """Stack per-graph ``BatchSimResult`` rows onto a leading (G,) axis."""
    from ..costmodel import BatchSimResult
    return BatchSimResult(
        latency=np.stack([r.latency for r in results]),
        reward=np.stack([r.reward for r in results]),
        oom=np.stack([r.oom for r in results]),
        per_device_busy=np.stack([r.per_device_busy for r in results]),
        transfer_time=np.stack([r.transfer_time for r in results]),
    )


def single_from_batch(batch, i: int = 0):
    """Row ``i`` of a ``BatchSimResult`` as a host ``SimResult``."""
    from ..costmodel import SimResult
    return SimResult(float(batch.latency[i]), batch.per_device_busy[i],
                     float(batch.transfer_time[i]), bool(batch.oom[i]))


class SimulatorBackend:
    """Interface every simulation engine implements (see module docstring)."""

    name: str = "?"
    jit_fused: bool = False
    jit_window: bool = False

    # ------------------------------------------------------------ preparation
    def prepare(self, graph, platform) -> Any:
        """Placement-independent handle for one (graph, platform) pair."""
        raise NotImplementedError

    def prepare_batch(self, graphs: Sequence, platform, *,
                      v_max: Optional[int] = None,
                      p_max: Optional[int] = None) -> Any:
        """Handle for a padded multi-graph batch (pad slots must be inert).

        ``v_max``/``p_max`` pin the node/predecessor axes beyond the batch
        maximum so different graph subsets share one jit shape (the
        bucketed corpus trainer's recompile bound); backends whose scoring
        never traces on the predecessor axis may ignore ``p_max``.
        """
        raise NotImplementedError

    # --------------------------------------------------------------- scoring
    def score(self, prep_tree, placement):
        """In-jit per-placement hook → (reward, latency) — REQUIRED when
        ``jit_fused``.  ``prep_tree`` is the pytree of dense arrays the
        rollout threads through jit (the engine reads it off the prepared
        handle's ``.arrays`` attribute — fused backends must expose one);
        it may carry vmapped graph/chain axes.
        """
        raise NotImplementedError(
            f"backend {self.name!r} sets jit_fused but implements no "
            f"score() hook")

    def simulate(self, prep, placement):
        """One placement → host ``SimResult``-compatible result."""
        raise NotImplementedError

    def simulate_batch(self, prep, placements):
        """(B, V) placements → host ``BatchSimResult``."""
        raise NotImplementedError

    def simulate_multi(self, prep, placements):
        """(G, B, V_max) placements → ``BatchSimResult`` with (G, B) axes."""
        raise NotImplementedError

    # ------------------------------------------------------------- metadata
    def schedule_order(self, prep) -> np.ndarray:
        """The list-schedule retire order this backend simulates.

        Device queues make the schedule order-sensitive, so the order is part
        of each backend's cost model; parity across backends is defined on a
        common order.
        """
        raise NotImplementedError


_REGISTRY: Dict[str, SimulatorBackend] = {}


def register_backend(backend: SimulatorBackend) -> SimulatorBackend:
    """Register ``backend`` under ``backend.name`` (latest wins)."""
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> SimulatorBackend:
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown simulator backend {name!r}; registered backends: "
            f"{backend_names()}")
    return _REGISTRY[name]


def backend_names() -> List[str]:
    return sorted(_REGISTRY)
