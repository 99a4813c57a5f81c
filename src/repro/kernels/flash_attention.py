"""Flash attention for TPU (pl.pallas_call + BlockSpec VMEM tiling).

Blockwise online-softmax attention with GQA head grouping and optional
causal/sliding-window masking.  TPU-native design decisions (vs. the CUDA
original): block shapes are MXU-aligned multiples of 128; the K loop is the
*innermost grid dimension* with "arbitrary" semantics so the accumulator
lives in VMEM scratch across K steps; masking uses 2-D broadcasted iota
(TPU requires ≥2-D iota); fully-masked K blocks are skipped with pl.when
(causal schedule wastes no MXU cycles above the diagonal).

Grid: (batch, q_heads, q_blocks, k_blocks); each program computes a
(block_q × head_dim) output tile.  VMEM working set per program:
  q (bq×d) + k (bk×d) + v (bk×d) + acc (bq×d) + m,l (bq)  ≈ 4·bq·d·4B
at bq=bk=128, d=128 ⇒ ~260 KiB — comfortably inside the 16 MiB VMEM budget,
leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention"]

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int, seq_len: int,
                  causal: bool, window: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # Block-level schedule skip: causal ⇒ blocks strictly above the diagonal
    # contribute nothing; SWA ⇒ blocks older than the window likewise.
    run = True
    if causal:
        run = (kj * block_k) <= (qi * block_q + block_q - 1)
    if window:
        run = jnp.logical_and(
            run, (kj + 1) * block_k - 1 > qi * block_q - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, d)
        # zero padded K rows (pad may be NaN; p=0 there wouldn't save NaN·0)
        v_row = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(v_row < seq_len, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        mask = k_pos < seq_len
        if causal:
            mask &= k_pos <= q_pos
            if window:
                mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                            # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                         # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                # (bq, 1)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(kj == nk - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, S, D); k, v: (B, KV, S, D); GQA via H//KV grouping."""
    b, h, s, d = q.shape
    kv = k.shape[1]
    group = h // kv
    scale = 1.0 / np.sqrt(d)
    bq = min(block_q, s)
    bk = min(block_k, s)
    nq = pl.cdiv(s, bq)
    nk = pl.cdiv(s, bk)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=bq, block_k=bk, seq_len=s,
        causal=causal, window=window)

    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),      # m: running max
            pltpu.VMEM((bq, 1), jnp.float32),      # l: running denominator
            pltpu.VMEM((bq, d), jnp.float32),      # acc: unnormalized output
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(q, k, v)
