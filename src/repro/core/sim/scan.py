"""``scan`` backend — the ``lax.scan`` node-scan kernel (``simulate_jax``).

Retires one node per scan step in the heap-Kahn topo order, reproducing the
reference scheduler's decisions exactly (≤1e-5 relative, typically ~1e-6 —
f32 vs f64 rounding only).  The backend is ``jit_fused``: the jitted
rollout window vmaps ``score`` over every placement it sampled, after its
step scan, so a whole REINFORCE window of rewards is computed device-side in
one node scan T·G·B lanes wide, with no host round-trips.  This is the
default RL engine backend.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

from ..costmodel import (SimArrays, SimArraysBatch, sim_arrays,
                         sim_arrays_batch, simulate_batch, simulate_jax,
                         simulate_multi)
from .base import SimulatorBackend, register_backend, single_from_batch

__all__ = ["ScanBackend", "ScanSim"]


class ScanSim(NamedTuple):
    """Prepared handle: graph/platform (for the public batch entry point,
    which validates device ids) plus the dense arrays the kernel consumes."""

    graph: object
    platform: object
    arrays: SimArrays


class ScanBackend(SimulatorBackend):
    name = "scan"
    jit_fused = True
    jit_window = True

    def prepare(self, graph, platform, *, schedule: str = "topo") -> ScanSim:
        return ScanSim(graph, platform,
                       sim_arrays(graph, platform, schedule=schedule))

    def prepare_batch(self, graphs: Sequence, platform, *,
                      v_max: Optional[int] = None,
                      p_max: Optional[int] = None) -> SimArraysBatch:
        return sim_arrays_batch(graphs, platform, v_max=v_max, p_max=p_max)

    # ------------------------------------------------------------ jit hooks
    @staticmethod
    def score(sim_tree, placement):
        """In-jit scoring hook: ``sim_tree`` is a :class:`SimArrays` pytree
        (possibly vmapped over graph/chain axes) → (reward, latency)."""
        res = simulate_jax(sim_tree, placement)
        return res.reward, res.latency

    # ---------------------------------------------------------- host entries
    def simulate(self, prep: ScanSim, placement):
        import numpy as np
        return single_from_batch(
            self.simulate_batch(prep, np.asarray(placement)[None]))

    def simulate_batch(self, prep: ScanSim, placements):
        # Threads the prebuilt SimArrays through — no cache-key re-derivation
        # (hashing the graph's edge/flops buffers) per call.
        return simulate_batch(prep.graph, placements, prep.platform,
                              sim=prep.arrays)

    def simulate_multi(self, prep: SimArraysBatch, placements):
        return simulate_multi(prep, placements)

    def schedule_order(self, prep: ScanSim):
        return prep.arrays.order


register_backend(ScanBackend())
