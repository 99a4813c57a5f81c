"""``level`` backend — the level-major Pallas makespan kernel.

One grid step retires one topological level for a lane-dense tile of
placements: each node's readiness (the P-wide predecessor max with
transfer costs) and its device-queue update are full-width row operations
over the tile.  The kernel batches over placements *internally*
(the B axis is a kernel dimension, not a ``vmap``), so the backend is
``jit_window``: it scores a whole rollout window in one device call rather
than fusing into the per-sample rollout step.

Order contract: simulates the **level-major** list schedule (see
``kernels/levelsim.py``) — a valid topological order, but a different cost
model than the scan backend's heap-Kahn order once device queues contend.
Parity is therefore asserted against the reference scheduler *on the same
order* (``simulate(..., order=prep.arrays.order)``), which this backend's
tests do for every Table-2 graph and for hypothesis-generated DAGs.

Compiles through Mosaic on a TPU; on the CPU backend (tests) the kernel runs
under ``interpret=True`` — ``kernels.ops.default_interpret`` decides.
"""
from __future__ import annotations

import weakref
from functools import partial
from typing import List, NamedTuple, Optional, Sequence

import numpy as np

from ...kernels.levelsim import (LevelArrays, build_level_arrays,
                                 level_makespan)
from ...kernels.ops import default_interpret
from ..costmodel import (BatchSimResult, SimArrays, SimResult, _cache_key,
                         pad_sim_arrays, sim_arrays)
from .base import (SimulatorBackend, register_backend, single_from_batch,
                   stack_batch_results)

__all__ = ["LevelBackend", "LevelSim"]


class LevelSim(NamedTuple):
    """Prepared handle: the level-schedule dense view + level tables."""

    graph: object            # CompGraph (None for padded batch members)
    platform: object         # Platform
    arrays: SimArrays        # built with schedule="level"
    levels: LevelArrays      # level-major tables over non-data nodes


def _simulate_level(sa: SimArrays, la: LevelArrays, placements, *,
                    interpret: bool):
    """Jit-compatible batched scorer → SimJaxResult-shaped (B,) results."""
    import jax.numpy as jnp
    from ..costmodel import SimJaxResult

    placements = jnp.asarray(placements, jnp.int32)
    B, n = placements.shape
    ndev = sa.op_time.shape[0]
    bytes_out = jnp.asarray(sa.bytes_out)
    op_time = jnp.asarray(sa.op_time)

    barange = jnp.arange(B)[:, None]
    dev_bytes = jnp.zeros((B, ndev)).at[barange, placements].add(
        jnp.broadcast_to(bytes_out[:n][None], (B, n)))
    oom = jnp.any(dev_bytes > jnp.asarray(sa.mem_capacity)[None], axis=1)

    dur_all = jnp.take_along_axis(
        jnp.broadcast_to(op_time.T[None], (B, n, ndev)),
        placements[:, :, None], axis=2)[:, :, 0]              # (B, V)
    busy = jnp.zeros((B, ndev)).at[barange, placements].add(dur_all)

    finish, transfer = level_makespan(
        la, placements, sa.queue_init, sa.inv_bw, sa.lat,
        interpret=interpret)
    latency = jnp.max(finish, axis=1)         # data/pad slots hold 0
    bad = oom | ~jnp.isfinite(latency)
    reward = jnp.where(bad, 0.0, 1.0 / jnp.where(bad, 1.0, latency))
    return SimJaxResult(latency, reward, oom, busy, transfer)


_LEVEL_BATCH_FN = None


def _level_batch_fn():
    """One jitted scorer shared by every prep (pytrees are arguments, so XLA
    compilations are reused across graphs with matching shapes)."""
    global _LEVEL_BATCH_FN
    if _LEVEL_BATCH_FN is None:
        import jax
        _LEVEL_BATCH_FN = jax.jit(partial(_simulate_level),
                                  static_argnames=("interpret",))
    return _LEVEL_BATCH_FN


class LevelBackend(SimulatorBackend):
    name = "level"
    jit_fused = False
    jit_window = True

    def __init__(self):
        # graph → {costmodel cache key: LevelSim}; mirrors the SimArrays
        # cache so repeated prepare() calls are free.
        self._cache: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

    def prepare(self, graph, platform) -> LevelSim:
        per_graph = self._cache.setdefault(graph, {})
        key = _cache_key(graph, platform)
        prep = per_graph.get(key)
        if prep is None:
            sa = sim_arrays(graph, platform, schedule="level")
            prep = per_graph[key] = LevelSim(graph, platform, sa,
                                             build_level_arrays(sa))
        return prep

    def prepare_batch(self, graphs: Sequence, platform, *,
                      v_max: Optional[int] = None,
                      p_max: Optional[int] = None) -> List[LevelSim]:
        """Per-graph handles padded to a common (V_max, P_max) shape.

        The kernel batches internally per graph, so a multi-graph batch is a
        list of padded handles rather than one stacked pytree; pad slots are
        data ops and drop out of the level tables entirely, keeping the
        padded makespan bitwise the unpadded one (incl. V_max ≫ V).
        ``p_max`` pins the predecessor axis (the kernel traces on it, so a
        corpus trainer must fix it per bucket or every subset retraces).
        """
        if not graphs:
            raise ValueError("prepare_batch needs at least one graph")
        sas = [sim_arrays(g, platform, schedule="level") for g in graphs]
        vm = max(sa.num_nodes for sa in sas)
        if v_max is not None:
            if v_max < vm:
                raise ValueError(f"v_max={v_max} < largest graph ({vm})")
            vm = v_max
        pm = max(sa.preds.shape[1] for sa in sas)
        if p_max is not None:
            if p_max < pm:
                raise ValueError(f"p_max={p_max} < largest in-degree ({pm})")
            pm = p_max
        out = []
        for g, sa in zip(graphs, sas):
            sap = pad_sim_arrays(sa, vm, pm)
            out.append(LevelSim(g, platform, sap, build_level_arrays(sap)))
        return out

    # ---------------------------------------------------------- host entries
    def _score(self, prep: LevelSim, placements) -> BatchSimResult:
        placements = np.asarray(placements)
        n = prep.arrays.num_nodes
        ndev = prep.arrays.num_devices
        if placements.ndim != 2 or placements.shape[1] != n:
            raise ValueError(f"expected (B, {n}) placements; got "
                             f"{placements.shape}")
        if placements.size and (placements.min() < 0
                                or placements.max() >= ndev):
            raise ValueError(f"placement device ids must be in [0, {ndev}); "
                             f"got [{placements.min()}, {placements.max()}]")
        res = _level_batch_fn()(prep.arrays, prep.levels,
                                placements.astype(np.int32),
                                interpret=default_interpret())
        return BatchSimResult(
            latency=np.asarray(res.latency),
            reward=np.asarray(res.reward),
            oom=np.asarray(res.oom),
            per_device_busy=np.asarray(res.per_device_busy),
            transfer_time=np.asarray(res.transfer_time),
        )

    def simulate(self, prep: LevelSim, placement) -> SimResult:
        return single_from_batch(self._score(prep,
                                             np.asarray(placement)[None]))

    def simulate_batch(self, prep: LevelSim, placements) -> BatchSimResult:
        return self._score(prep, placements)

    def simulate_multi(self, preps: List[LevelSim],
                       placements) -> BatchSimResult:
        placements = np.asarray(placements)
        if placements.ndim != 3 or placements.shape[0] != len(preps):
            raise ValueError(f"expected (G={len(preps)}, B, V_max) "
                             f"placements; got {placements.shape}")
        return stack_batch_results([self._score(prep, placements[i])
                                    for i, prep in enumerate(preps)])

    def schedule_order(self, prep: LevelSim) -> np.ndarray:
        return np.asarray(prep.arrays.order, np.int64)


register_backend(LevelBackend())
