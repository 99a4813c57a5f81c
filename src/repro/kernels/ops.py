"""Jit'd public wrappers for the Pallas kernels.

TPU is the compile target.  On the CPU backend (the test suite, with
``JAX_PLATFORMS=cpu``) kernels run in ``interpret=True`` mode — the kernel
body executes on the CPU, which validates the TPU program against the
ref.py oracles.  Any other backend is an error, so a run that lost its chip
fails instead of interpreting.  The wrappers pick the mode from the actual
backend so model code can call one symbol.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention as _flash
from .gcn_spmm import gcn_aggregate as _gcn
from .rmsnorm import rmsnorm as _rmsnorm
from .ssd_scan import ssd_scan as _ssd_scan

__all__ = ["flash_attention_op", "rmsnorm_op", "gcn_aggregate_op",
           "ssd_scan_op", "default_interpret"]


def default_interpret() -> bool:
    """False on a TPU (compile), True on the CPU backend (interpret)."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels compile for a TPU or interpret on the CPU; "
            f"the default backend is {backend!r}")
    return backend == "cpu"


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention_op(q, k, v, *, causal: bool = True, window: int = 0,
                       block_q: int = 128, block_k: int = 128):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, interpret=default_interpret())


@partial(jax.jit, static_argnames=("block_rows",))
def rmsnorm_op(x, scale, *, block_rows: int = 256):
    return _rmsnorm(x, scale, block_rows=block_rows,
                    interpret=default_interpret())


@partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def gcn_aggregate_op(adj, h, *, block_m: int = 128, block_n: int = 128,
                     block_k: int = 128):
    return _gcn(adj, h, block_m=block_m, block_n=block_n, block_k=block_k,
                interpret=default_interpret())


@jax.jit
def ssd_scan_op(chunk_decay, dbx):
    return _ssd_scan(chunk_decay, dbx, interpret=default_interpret())
