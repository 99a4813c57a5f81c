"""Faults planted in the program's timed path, to show the comparison fails.

Each is a context manager that patches the program's engines while it is
open (the one-chip ``RolloutEngine`` and the mesh's
``ShardedRolloutEngine`` alike):

``unchanged``     the update leaves the parameters as they were;
``half_batch``    the replay's gradient is the mean over the first half of
                  the chains only;
``altered``       one node's device is changed (device 0 to 1, any other to
                  0) in every placement the rollout returns, after the
                  simulator scored it;
``replay_z``      the replay of a window that goes on from carried chains
                  starts them from zeros instead of the carried state;
``rollout_z``     the rollout hands on a zeroed chain state instead of the
                  one it computed;
``no_exchange``   (mesh cells) the replay's reduction over the chips is left
                  out, so each chip keeps its own chains' share;
``altered_serve`` one node's device is flipped in every placement served.
"""
from __future__ import annotations

import contextlib

SEARCH_FAULTS = ("unchanged", "half_batch", "altered", "replay_z",
                 "rollout_z")
MESH_FAULTS = ("no_exchange",)


def _engines():
    from repro.core.sim import RolloutEngine, ShardedRolloutEngine
    return RolloutEngine, ShardedRolloutEngine


@contextlib.contextmanager
def plant(name: str):
    import jax
    import jax.numpy as jnp
    saved = []

    def patch(owner, attr, new):
        saved.append((owner, attr, owner.__dict__[attr]))
        setattr(owner, attr, new)

    def wrap_grads(change):
        """Patch ``window_grads`` of every engine: ``change(z0, keys,
        weights, start_first)`` → new (z0, keys, weights).  The one-chip
        engine takes (params, z0, keys, weights); the mesh's takes the graph
        operands first."""
        for cls in _engines():
            def make(orig):
                def grads(self, *args, num_steps, start_first):
                    *head, z0, keys, weights = args
                    z0, keys, weights = change(z0, keys, weights, start_first)
                    return orig(self, *head, z0, keys, weights,
                                num_steps=num_steps, start_first=start_first)
                return grads
            patch(cls, "window_grads", make(cls.window_grads))

    if name == "unchanged":
        from repro.core import HSDAG
        patch(HSDAG, "apply_grads", lambda self, grads: None)
    elif name == "half_batch":
        def half(z0, keys, weights, start_first):
            h = weights.shape[-1] // 2
            keep = (jnp.arange(weights.shape[-1]) < h).astype(weights.dtype)
            return z0, keys, weights * keep * (weights.shape[-1] / h)
        wrap_grads(half)
    elif name == "replay_z":
        def zeroed(z0, keys, weights, start_first):
            return (z0 if start_first else jnp.zeros_like(z0)), keys, weights
        wrap_grads(zeroed)
    elif name == "no_exchange":
        from repro.core.sim import ShardedRolloutEngine
        # The reduction is traced into the replay program: trace a fresh
        # build of it while psum is switched off, kept apart from the sound
        # build, which comes back when the fault closes.
        patch(jax.lax, "psum", lambda x, axis_name, **kw: x)
        patch(ShardedRolloutEngine, "_built", property(
            lambda self: self.__dict__.setdefault("_fault_fns",
                                                  self._build())))
    elif name in ("altered", "rollout_z"):
        def change(out):
            if name == "rollout_z":
                out[0] = jnp.zeros_like(out[0])
            else:
                fines = out[3]
                node = fines.shape[-1] // 2
                out[3] = fines.at[..., node].set(
                    jnp.where(fines[..., node] == 0, 1, 0))
        for cls in _engines():
            def make(orig):
                def rollout(self, *args, num_steps, start_first):
                    out = list(orig(self, *args, num_steps=num_steps,
                                    start_first=start_first))
                    change(out)
                    return tuple(out)
                return rollout
            patch(cls, "rollout_window", make(cls.rollout_window))
    elif name == "altered_serve":
        from repro.api import PlacementService
        orig = PlacementService.decode_bucket

        def altered_decode(self, bucket, members, out):
            orig(self, bucket, members, out)
            for i, _ in members:
                node = out[i].shape[0] // 2
                out[i][node] = 1 - out[i][node]
        patch(PlacementService, "decode_bucket", altered_decode)
    else:
        raise ValueError(f"unknown fault {name!r}")
    try:
        yield
    finally:
        for owner, attr, old in reversed(saved):
            setattr(owner, attr, old)
