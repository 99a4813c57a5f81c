"""Pipeline parallelism over a mesh axis (shard_map + ppermute).

The pipeline is written as a *differentiable program*: a scan over
``M + S − 1`` ticks where every stage, in parallel, (1) consumes either a
fresh microbatch (stage 0) or its neighbor's activation, (2) applies its
layer slice, (3) ships the result one hop with ``lax.ppermute``.  Because
ppermute has a transpose rule, ``jax.grad`` through this function *is* the
backward pipeline (GPipe schedule; per-stage remat keeps activation memory at
O(microbatch)).

Stage assignment comes from the HSDAG planner (core/planner.py): the paper's
placement policy decides which layer-graph partition lands on which pod —
this module is the execution substrate for that placement.

The pod axis doubles as the stage axis on the production mesh
(2 pods = 2 stages); on CI the same code runs on a host-device mesh.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["pipeline_apply", "make_pipeline_fn"]


def _tick(stage_fn, axis: str, num_stages: int, carry, xs_t):
    """One pipeline tick on every stage simultaneously."""
    stage_params, act_in, t = carry["params"], carry["act"], carry["t"]
    idx = jax.lax.axis_index(axis)
    # stage 0 ingests the fresh microbatch; others use the incoming activation
    inject = xs_t
    x = jnp.where(idx == 0, inject, act_in)
    y = stage_fn(stage_params, x)
    # ship to the next stage (ring; last stage's output falls off the end and
    # is collected below before the permute overwrites it)
    out_tail = y                                   # last stage's product
    shifted = jax.lax.ppermute(
        y, axis, [(i, (i + 1) % num_stages) for i in range(num_stages)])
    carry = {"params": stage_params, "act": shifted, "t": t + 1}
    return carry, out_tail


def pipeline_apply(stage_fn: Callable, stage_params, microbatches: jnp.ndarray,
                   *, mesh: Mesh, axis: str = "pod",
                   remat_stage: bool = True) -> jnp.ndarray:
    """Run ``stage_fn`` as a pipeline over mesh axis ``axis``.

    stage_fn(stage_params, x) -> y  — one stage's compute; all stages share
      the same program with different params (layer slices).
    stage_params: pytree whose leaves have a leading ``num_stages`` dim
      (sharded over ``axis``).
    microbatches: (M, ...) — M microbatches sharded over remaining axes.

    Returns (M, ...) outputs of the final stage.
    """
    num_stages = mesh.shape[axis]
    m = microbatches.shape[0]
    ticks = m + num_stages - 1
    if remat_stage:
        stage_fn = jax.checkpoint(stage_fn)

    # pad the schedule: stage 0 reads microbatch t for t < M, zeros after
    pad = jnp.zeros((num_stages - 1,) + microbatches.shape[1:],
                    microbatches.dtype)
    feed = jnp.concatenate([microbatches, pad], axis=0)       # (ticks, ...)

    def per_stage(params_slice, feed_local):
        # params_slice: this stage's layer slice (leading dim removed)
        params_slice = jax.tree.map(lambda a: a[0], params_slice)
        init = {"act": jnp.zeros_like(feed_local[0]), "t": jnp.int32(0)}

        def scan_body(c, x):
            carry = {"params": params_slice, "act": c["act"], "t": c["t"]}
            new_c, out = _tick(stage_fn, axis, num_stages, carry, x)
            return {"act": new_c["act"], "t": new_c["t"]}, out

        _, outs = jax.lax.scan(scan_body, init, feed_local)    # (ticks, ...)
        # the final stage's outputs for ticks ≥ S−1 are the pipeline outputs;
        # broadcast them from the last stage to all ranks (loss reduction
        # follows anyway; ppermute is point-to-point so use all_gather+take).
        outs = jax.lax.all_gather(outs, axis, axis=0)[num_stages - 1]
        return outs[num_stages - 1:]

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    fn = shard_map(
        per_stage, mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
        check_vma=False)
    return fn(stage_params, feed)


def make_pipeline_fn(stage_fn: Callable, mesh: Mesh, axis: str = "pod"):
    """Convenience: returns f(stage_params, microbatches) → outputs."""
    return partial(pipeline_apply, stage_fn, mesh=mesh, axis=axis)
