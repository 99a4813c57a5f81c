"""Level-major Pallas makespan kernel — the ``level`` simulator backend.

The node-scan kernel (``core/costmodel.simulate_jax``) retires one node per
``lax.scan`` step for one placement, and the rollout ``vmap``s it over the
placement batch.  This kernel instead holds the whole placement batch in one
lane-dense block: placements and finish times are stored node-major,
``(V+1, B)``, with the B placements on the 128-wide lane axis.  Retiring a
node is then a handful of full-width row operations — read the node's
device row and each predecessor's device and finish rows, add the transfer
costs, take the readiness max, update the device queues — shared by every
placement in the tile.  The grid walks the topological levels (one level per
grid step, ``"arbitrary"``) over tiles of the B axis (``"parallel"``).

Every gather is by a node id that is the same for the whole tile, so it is a
dynamic *sublane* row read from VMEM; the ids live in SMEM (scalar prefetch).
Per-device constants are picked with compare-and-select over the D devices:
exactly one term is selected, so results are bit-exact with a gather.

Scheduling-order contract
-------------------------
Device queues make the list schedule sensitive to retire order (measured:
up to ~20% makespan shift on Inception-V3 under reordering), so the retire
order is part of the cost model.  This kernel simulates the **level-major**
schedule: nodes sorted by topological level, ties in the base topo order —
a valid topological order, and closer to the BFS wavefront a real runtime
dispatches than the node-scan kernel's heap-Kahn order.  It is therefore NOT
bit-compatible with ``simulate_jax`` on the default ``schedule="topo"``
arrays; parity is defined against the same order — build the arrays with
``sim_arrays(g, platform, schedule="level")`` and compare against
``simulate(g, p, platform, order=sa.order)`` (the reference scheduler takes
the order explicitly) or ``simulate_jax`` on the same arrays.

"data"-class ops (weights/inputs resident on the consumer device) never
enter the tables: they cost nothing, their finish time is pinned to 0 by the
initial state, and their out-edges pay no transfer — exactly the reference
scheduler's behavior.

On a TPU the kernel compiles through Mosaic; on the CPU backend (tests) it
runs with ``interpret=True``, chosen by ``ops.default_interpret``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["LevelArrays", "build_level_arrays", "level_makespan"]

#: VMEM the (V+1, B-tile) blocks may take: placements and finish times,
#: each double-buffered.  Stays under v5e's 16 MiB default scoped limit.
_VMEM_BLOCK_BUDGET = 12 * 2 ** 20


class LevelArrays(NamedTuple):
    """Level-major tables over the *schedulable* (non-data) nodes.

    Shapes: L levels, W = max nodes per level, P = max in-degree, D devices.
    The node-id sentinel is V (one past the last real slot) — guaranteed to
    index an inert pad entry of the (V+1,)-shaped per-node vectors.  A
    predecessor that is a data op (or a pad entry) is stored as -1: it
    finishes at 0 and costs no transfer.  All fields are arrays, so the
    tuple is a pytree (safe as a jit argument).
    """

    nodes: np.ndarray       # (L, W) i32 — node ids per level, pad = V
    preds: np.ndarray       # (L, W*P) i32 — slot w's preds at [w*P:(w+1)*P]
    dur: np.ndarray         # (L, W*D) f32 — slot w's durations at [w*D:...]
    pred_bytes: np.ndarray  # (L, W*P) f32 — bytes emitted by each pred
    order: np.ndarray       # (V,) i32 — full level-major retire order

    @property
    def num_levels(self) -> int:
        return int(self.nodes.shape[0])

    @property
    def max_width(self) -> int:
        return int(self.nodes.shape[1])


def build_level_arrays(sa) -> LevelArrays:
    """Regroup a ``SimArrays`` into per-level tables.

    ``sa`` is any ``core.costmodel.SimArrays`` (padded ones included — pad
    slots are data ops and drop out of the tables).  The kernel retires nodes
    in level-major order regardless of ``sa.order``'s schedule; pass arrays
    built with ``schedule="level"`` so ``sa.order`` matches what the kernel
    simulates (the returned ``order`` is always the level-major one).
    """
    order = np.asarray(sa.order, np.int64)
    levels = np.asarray(sa.levels, np.int64)
    is_data = np.asarray(sa.is_data)
    n = order.shape[0]
    p_max = sa.preds.shape[1]
    ndev = sa.op_time.shape[0]

    # preds are stored per order-position; re-index them per node id.  Rows
    # of padded arrays may carry the *unpadded* sentinel — every sentinel
    # points at some data slot, so they are interchangeable here.
    pred_by_node = np.full((n + 1, p_max), n, dtype=np.int64)
    pred_by_node[order] = np.asarray(sa.preds, np.int64)

    lvl_order = order[np.argsort(levels[order], kind="stable")]
    sched = [int(v) for v in lvl_order if not is_data[v]]
    by_level: dict = {}
    for v in sched:
        by_level.setdefault(int(levels[v]), []).append(v)
    rows = [by_level[k] for k in sorted(by_level)]

    L = max(len(rows), 1)
    W = max((len(r) for r in rows), default=1) or 1
    nodes = np.full((L, W), n, dtype=np.int32)
    preds = np.full((L, W, p_max), -1, dtype=np.int32)
    dur = np.zeros((L, W, ndev), dtype=np.float32)
    pbytes = np.zeros((L, W, p_max), dtype=np.float32)
    bytes_out = np.asarray(sa.bytes_out, np.float32)
    op_time = np.asarray(sa.op_time, np.float32)
    for l, row in enumerate(rows):
        w = len(row)
        nodes[l, :w] = row
        pv = pred_by_node[row]                          # (w, P)
        preds[l, :w] = np.where(is_data[pv], -1, pv)
        dur[l, :w] = op_time[:, row].T
        pbytes[l, :w] = bytes_out[pv]
    return LevelArrays(nodes=nodes, preds=preds.reshape(L, W * p_max),
                       dur=dur.reshape(L, W * ndev),
                       pred_bytes=pbytes.reshape(L, W * p_max),
                       order=lvl_order.astype(np.int32))


def _level_kernel(nodes_ref, preds_ref, dur_ref, pbytes_ref, invbw_ref,
                  lat_ref, qinit_ref, place_ref, finish_ref, transfer_ref,
                  queues_ref, *, width: int, npred: int, ndev: int, nq: int,
                  sentinel: int):
    """One grid step = one level for one tile of placements.

    ``place_ref``/``finish_ref`` are (V+1, Bt) node-major blocks whose block
    index does not move along the level axis, so ``finish_ref`` stays
    resident in VMEM and accumulates over the levels.  ``queues_ref`` holds
    the D*Q device-queue free times, one (1, Bt) row per queue.
    """
    lvl = pl.program_id(1)

    @pl.when(lvl == 0)
    def _init():
        finish_ref[...] = jnp.zeros_like(finish_ref)
        transfer_ref[...] = jnp.zeros_like(transfer_ref)
        for k in range(ndev * nq):
            queues_ref[pl.ds(k, 1), :] = jnp.full(
                (1, queues_ref.shape[1]), qinit_ref[k // nq, k % nq],
                jnp.float32)

    def retire(w, carry):
        slot = lvl * width + w
        v = nodes_ref[slot]
        pad = v == sentinel
        d_n = place_ref[pl.ds(v, 1), :]                    # (1, Bt) i32

        # readiness: max over preds of finish + transfer (all earlier levels)
        ready = jnp.zeros(d_n.shape, jnp.float32)
        txsum = jnp.zeros(d_n.shape, jnp.float32)
        for p in range(npred):
            u = preds_ref[slot * npred + p]
            is_data = u < 0
            u = jnp.where(is_data, sentinel, u)
            pd = place_ref[pl.ds(u, 1), :]
            fpred = finish_ref[pl.ds(u, 1), :]
            inv_bw = jnp.zeros(d_n.shape, jnp.float32)
            lat = jnp.zeros(d_n.shape, jnp.float32)
            for a in range(ndev):
                for c in range(ndev):
                    hit = (pd == a) & (d_n == c)
                    inv_bw = jnp.where(hit, invbw_ref[a, c], inv_bw)
                    lat = jnp.where(hit, lat_ref[a, c], lat)
            tx = jnp.where(is_data | (pd == d_n), 0.0,
                           pbytes_ref[0, 0, w * npred + p] * inv_bw + lat)
            ready = jnp.maximum(ready, fpred + tx)
            txsum = txsum + tx
        dur = jnp.zeros(d_n.shape, jnp.float32)
        for d in range(ndev):
            dur = jnp.where(d_n == d, dur_ref[0, 0, w * ndev + d], dur)

        # earliest-free queue on the node's device (first index on ties)
        rows = [queues_ref[pl.ds(k, 1), :] for k in range(ndev * nq)]
        free = []
        for q in range(nq):
            col = jnp.zeros(d_n.shape, jnp.float32)
            for d in range(ndev):
                col = jnp.where(d_n == d, rows[d * nq + q], col)
            free.append(col)
        q_free, q_arg = free[0], jnp.zeros(d_n.shape, jnp.int32)
        for q in range(1, nq):
            take = free[q] < q_free
            q_free = jnp.where(take, free[q], q_free)
            q_arg = jnp.where(take, q, q_arg)

        f = jnp.where(pad, 0.0, jnp.maximum(ready, q_free) + dur)
        new_free = jnp.where(pad, q_free, f)
        for d in range(ndev):
            for q in range(nq):
                k = d * nq + q
                queues_ref[pl.ds(k, 1), :] = jnp.where(
                    (d_n == d) & (q_arg == q), new_free, rows[k])
        finish_ref[pl.ds(v, 1), :] = f
        transfer_ref[...] += jnp.where(pad, 0.0, txsum)
        return carry

    jax.lax.fori_loop(0, width, retire, 0)


def _tile_b(batch: int, rows: int) -> int:
    """Lane tile over the placement batch: the widest multiple of 128 that
    divides ``batch`` and keeps the (rows, tile) blocks in budget; the whole
    batch when it is not a multiple of 128 (a block may span a full dim)."""
    if batch % 128:
        return batch
    padded_rows = -(-rows // 8) * 8
    for tile in (1024, 512, 256, 128):
        if batch % tile == 0 and 4 * padded_rows * tile * 4 \
                <= _VMEM_BLOCK_BUDGET:
            return tile
    return 128


def level_makespan(la: LevelArrays, placements, queue_init, inv_bw, lat, *,
                   interpret: bool = False):
    """Run the level kernel → (finish (B, V+1) f32, transfer (B,) f32).

    ``placements``: (B, V) device ids; ``queue_init``: (D, Q) with +inf at
    masked queue slots; ``inv_bw``/``lat``: (D, D) link constants.  Finish
    times of data ops (and the V sentinel slot) are 0.
    """
    placements = jnp.asarray(placements, jnp.int32)
    B, n = placements.shape
    L, W = la.nodes.shape
    P = la.preds.shape[1] // W
    D, Q = queue_init.shape
    vp = n + 1
    place_t = jnp.concatenate(
        [placements, jnp.zeros((B, 1), jnp.int32)], axis=1).T   # (V+1, B)
    bt = _tile_b(B, vp)

    kernel = functools.partial(_level_kernel, width=W, npred=P, ndev=D,
                               nq=Q, sentinel=n)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # node ids, pred ids
        grid=(B // bt, L),
        in_specs=[
            smem((1, 1, W * D), lambda b, l, *_: (l, 0, 0)),  # durations
            smem((1, 1, W * P), lambda b, l, *_: (l, 0, 0)),  # pred bytes
            smem((D, D), lambda b, l, *_: (0, 0)),       # 1/bw
            smem((D, D), lambda b, l, *_: (0, 0)),       # latency
            smem((D, Q), lambda b, l, *_: (0, 0)),       # queue init
            pl.BlockSpec((vp, bt), lambda b, l, *_: (0, b)),  # placements
        ],
        out_specs=[
            pl.BlockSpec((vp, bt), lambda b, l, *_: (0, b)),  # finish
            pl.BlockSpec((1, bt), lambda b, l, *_: (0, b)),   # transfer
        ],
        scratch_shapes=[pltpu.VMEM((D * Q, bt), jnp.float32)],
    )
    finish, transfer = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((vp, B), jnp.float32),
            jax.ShapeDtypeStruct((1, B), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(jnp.asarray(la.nodes, jnp.int32).reshape(-1),
      jnp.asarray(la.preds, jnp.int32).reshape(-1),
      jnp.asarray(la.dur, jnp.float32).reshape(L, 1, W * D),
      jnp.asarray(la.pred_bytes, jnp.float32).reshape(L, 1, W * P),
      jnp.asarray(inv_bw, jnp.float32), jnp.asarray(lat, jnp.float32),
      jnp.asarray(queue_init, jnp.float32), place_t)
    return finish.T, transfer[0]
