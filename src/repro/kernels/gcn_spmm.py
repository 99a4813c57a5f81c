"""Fused GCN aggregation kernel — the HSDAG encoder hot spot (Eq. 6).

Computes  Z = D̂^{-1/2}(Â)D̂^{-1/2} · H  in one pass without materializing the
normalized adjacency: each program loads an (bm × bk) tile of A, applies the
self-loop + symmetrization + degree scaling *in VMEM*, and accumulates the
(bm × bn) output tile on the MXU across k-steps.  Saves writing/re-reading
the V×V normalized matrix to HBM (2·V²·4B per RL step at V≈1k, ×20 rollout
steps ×100 episodes in the search loop).

TPU adaptation note: the paper's PyG implementation uses CSR SpMM on GPU;
TPUs favor dense tiles at these graph sizes (V ≤ ~1k, Table 1), so the
kernel is a dense fused-normalization matmul — same math, MXU-shaped.

Grid: (V/bm, F/bn, V/bk), k innermost ("arbitrary") with a VMEM accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["gcn_aggregate"]


def _gcn_kernel(a_ref, at_ref, inv_ref, invt_ref, h_ref, o_ref, acc_scr, *,
                block_m: int, block_k: int, num_nodes: int):
    i = pl.program_id(0)
    kk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    a = a_ref[...].astype(jnp.float32)            # (bm, bk) tile of A
    at = at_ref[...].astype(jnp.float32)          # (bm, bk) tile of Aᵀ
    # symmetrize + self loops (diagonal only on diagonal tiles)
    row = i * block_m + jax.lax.broadcasted_iota(
        jnp.int32, a.shape, 0)
    col = kk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, a.shape, 1)
    diag = (row == col).astype(jnp.float32)
    sym = a + at - a * diag + diag                # A + Aᵀ − diag(A) + I
    # degree scaling
    sym = inv_ref[...].astype(jnp.float32) * sym * \
        invt_ref[...].astype(jnp.float32)
    # mask padded columns with where (padding may be NaN: NaN·0 ≠ 0)
    sym = jnp.where(col < num_nodes, sym, 0.0)
    h = h_ref[...].astype(jnp.float32)            # (bk, bn)
    h_row = kk * block_k + jax.lax.broadcasted_iota(jnp.int32, h.shape, 0)
    h = jnp.where(h_row < num_nodes, h, 0.0)
    acc_scr[...] += jax.lax.dot_general(
        sym, h, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _fin():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def gcn_aggregate(adj: jnp.ndarray, h: jnp.ndarray, *,
                  block_m: int = 128, block_n: int = 128,
                  block_k: int = 128,
                  interpret: bool = False) -> jnp.ndarray:
    """adj: (V, V) binary; h: (V, F) → (V, F)."""
    v, f = h.shape
    a32 = adj.astype(jnp.float32)
    # degrees of Â = A + I with symmetrized counting (matches gnn.py)
    deg = a32.sum(1) + a32.sum(0) + 1.0 - jnp.diag(a32)
    inv = jnp.where(deg > 0, jax.lax.rsqrt(deg), 0.0)

    bm = min(block_m, v)
    bn = min(block_n, f)
    bk = min(block_k, v)
    grid = (pl.cdiv(v, bm), pl.cdiv(f, bn), pl.cdiv(v, bk))

    return pl.pallas_call(
        functools.partial(_gcn_kernel, block_m=bm, block_k=bk, num_nodes=v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # A tile
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # Aᵀ tile
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),    # row scaling
            pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),    # col scaling
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),   # H tile
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((v, f), h.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a32, a32.T, inv[:, None], inv[None, :], h)
