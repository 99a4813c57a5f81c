"""Fused RMSNorm kernel (pl.pallas_call + BlockSpec).

One pass over HBM: read a (block_rows × d) tile into VMEM, compute the f32
row-wise rms and apply the scale in-register, write the tile back — vs. the
unfused XLA sequence (square → mean → rsqrt → mul → mul) which re-touches
the activation several times.  Memory-bound ⇒ the win is pure bytes; tile
rows chosen so 2·block·d·4B stays ≪ VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["rmsnorm"]


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                 # (block, d)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) *
                  s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, *, eps: float = 1e-6,
            block_rows: int = 256, interpret: bool = False) -> jnp.ndarray:
    """x: (..., d) — flattened to rows; scale: (d,)."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = int(np.prod(orig_shape[:-1])) if orig_shape[:-1] else 1
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    grid = (pl.cdiv(rows, br),)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(x2, scale.reshape(1, d))
    return out.reshape(orig_shape)


import numpy as np  # noqa: E402  (used above in rows computation)
