"""Pluggable simulation-engine layer — placement scoring behind one seam.

The RL loop is bounded by how fast placements are scored, and different
deployments want different engines: a ground-truth host scheduler for
validation, a fused ``lax.scan`` kernel for device-resident training, a
level-parallel Pallas kernel for TPU-scale wide graphs, a wall-clock
``MeasuredExecutor`` for paper-faithful measurement.  This package gives
them one protocol, one registry, and one reward interface.

Backend matrix
--------------

===========  ==========  ===========  ================================
backend      scoring     schedule     notes
===========  ==========  ===========  ================================
reference    host        any order    Python list-scheduler — ground
                                      truth; takes an explicit retire
                                      order for cross-backend parity.
scan         jit, fused  heap-Kahn    ``simulate_jax`` vmapped over a
             per window  ("topo")     window's placements inside the
                                      jitted rollout, after its step
                                      scan; the RL default.
level        jit, per    level-major  Pallas kernel, one topological
             window      ("level")    level per grid step (segment-max
                                      readiness over the padded pred
                                      table); batches internally.
===========  ==========  ===========  ================================

Device queues make the list schedule sensitive to retire order (~20%
makespan shifts measured on Inception-V3), so the order is part of each
backend's cost model and cross-backend parity is asserted on a *common*
order: ``sim_arrays(g, p, schedule="level")`` + ``simulate(..., order=...)``
lets the reference and scan engines replay exactly the schedule the level
kernel retires.

Registering a new backend::

    from repro.core.sim import SimulatorBackend, register_backend

    class MeasuredBackend(SimulatorBackend):
        name = "measured"          # → HSDAGConfig(engine="measured")
        def prepare(self, graph, platform): ...
        def simulate_batch(self, prep, placements): ...

    register_backend(MeasuredBackend())

Layered on top:

* :class:`RewardPipeline` — normalizes in-jit simulator rewards and host
  ``reward_fn`` callables to one window-scoring interface.
* :class:`RolloutEngine` — the single parameterized (G, B)-chain window
  rollout + Eq.-14 replay that ``search``, the batched search and
  ``train_multi`` all drive (plus the scalar reference loop).
"""
from .base import (SimulatorBackend, backend_names, get_backend,
                   register_backend, single_from_batch, stack_batch_results)
from .level import LevelBackend, LevelSim
from .pipeline import RewardPipeline
from .reference import RefSim, ReferenceBackend
from .rollout import (DynamicRolloutEngine, GraphOperands, RolloutEngine,
                      build_window_fns, split_multi_keys)
from .scan import ScanBackend, ScanSim
from .sharded import ShardedRolloutEngine, make_rollout_mesh

__all__ = [
    "SimulatorBackend", "register_backend", "get_backend", "backend_names",
    "ReferenceBackend", "RefSim", "ScanBackend", "ScanSim",
    "LevelBackend", "LevelSim",
    "RewardPipeline", "RolloutEngine", "DynamicRolloutEngine",
    "ShardedRolloutEngine", "make_rollout_mesh", "build_window_fns",
    "GraphOperands", "split_multi_keys",
    "stack_batch_results", "single_from_batch",
]
