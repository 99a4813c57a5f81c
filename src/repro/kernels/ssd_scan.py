"""SSD cross-chunk state scan kernel (Mamba-2 inter-chunk recurrence).

The sequential part of the SSD algorithm: h_{c} = h_{c-1}·decay_c + dbx_c,
emitting the state *entering* every chunk.  XLA's lax.scan round-trips the
(H, P, N) state through HBM each step; this kernel pins the state in VMEM
scratch and walks chunks with the grid's innermost "arbitrary" dimension,
so the recurrence is latency- not bandwidth-bound.

Grid: (B, H, C).  Per-program VMEM: state (P, N) f32 + one dbx tile — at
P=64, N=128 that is 32 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["ssd_scan"]


def _ssd_scan_kernel(decay_ref, dbx_ref, before_ref, final_ref, h_scr):
    c = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    before_ref[0, 0, 0] = h_scr[...].astype(before_ref.dtype)
    dec = decay_ref[0, 0, 0]                       # scalar decay for chunk c
    h_scr[...] = h_scr[...] * dec + dbx_ref[0, 0, 0].astype(jnp.float32)

    @pl.when(c == nc - 1)
    def _fin():
        final_ref[0, 0] = h_scr[...].astype(final_ref.dtype)


def ssd_scan(chunk_decay: jnp.ndarray, dbx: jnp.ndarray, *,
             interpret: bool = False):
    """chunk_decay: (B, C, H); dbx: (B, C, H, P, N) →
    (h_before (B, C, H, P, N) f32, h_final (B, H, P, N) f32)."""
    b, c, h = chunk_decay.shape
    _, _, _, p, n = dbx.shape
    # reshape decay to (B, H, C) scalar-per-step layout
    dec = jnp.moveaxis(chunk_decay.astype(jnp.float32), 1, 2)   # (B,H,C)
    dbx_t = jnp.moveaxis(dbx, 1, 2)                             # (B,H,C,P,N)

    before, final = pl.pallas_call(
        _ssd_scan_kernel,
        grid=(b, h, c),
        in_specs=[
            pl.BlockSpec((1, 1, 1), lambda b_, h_, c_: (b_, h_, c_)),
            pl.BlockSpec((1, 1, 1, p, n), lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, p, n), lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, c, p, n), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(dec, dbx_t)
    return jnp.moveaxis(before, 2, 1), final
