"""Pallas TPU kernel: blockwise online-softmax (flash) GQA attention.

Causal and sliding-window masking; grouped-query head sharing via the k/v
BlockSpec index map (q head h reads kv head h // group_size — no materialized
K/V replication).  Grid = (batch, q_heads, Sq/blk_q, Sk/blk_k) with the kv
axis innermost; running max / denominator / accumulator live in VMEM scratch
and the output tile is written on the last kv step.

Block shapes default to 128×128 — MXU-aligned, and the working set
(q 128×hd + k/v 2×128×hd + acc 128×hd + s 128×128, f32) ≈ 0.4 MB for hd=128,
far inside the ~16 MB VMEM budget; larger blk_k amortizes loop overhead for
long-context prefill.

The sliding-window variant is the sub-quadratic path that makes dense-arch
``long_500k`` decode admissible (DESIGN §2): FLOPs scale with window, not
context, and fully-masked blocks are skipped entirely.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel_call"]

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  blk_q: int, blk_k: int, n_kv_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    k_pos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    mask = jnp.ones((blk_q, blk_k), dtype=jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window

    # skip fully-masked blocks (the flash win for causal/sliding-window)
    block_live = True
    if causal:
        block_live = ki * blk_k <= qi * blk_q + blk_q - 1
    if window:
        block_live = jnp.logical_and(
            block_live, (ki + 1) * blk_k - 1 > qi * blk_q - window)

    @pl.when(block_live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)          # (blk_q, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (blk_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())))
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        # zero out rows that are entirely masked (exp(NEG_INF-NEG_INF)=1 trap)
        row_live = jnp.any(mask, axis=1, keepdims=True)
        p = jnp.where(row_live, p, 0.0)
        alpha = jnp.where(row_live | (m_prev > NEG_INF / 2),
                          jnp.exp(m_prev - m_new), 0.0)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot(p, v)
        m_scr[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_kernel_call(q, k, v, *, causal: bool = True,
                                window: int = 0, blk_q: int = 128,
                                blk_k: int = 128, interpret: bool = False):
    """q: (B, H, Sq, hd); k, v: (B, K, Sk, hd); H % K == 0.
    Returns (B, H, Sq, hd).  Sq % blk_q == 0, Sk % blk_k == 0."""
    B, H, Sq, hd = q.shape
    K, Sk = k.shape[1], k.shape[2]
    assert H % K == 0 and Sq % blk_q == 0 and Sk % blk_k == 0
    G = H // K
    n_kv = Sk // blk_k
    grid = (B, H, Sq // blk_q, n_kv)

    q_spec = pl.BlockSpec((1, 1, blk_q, hd), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, blk_k, hd), lambda b, h, i, j: (b, h // G, j, 0))
    o_spec = pl.BlockSpec((1, 1, blk_q, hd), lambda b, h, i, j: (b, h, i, 0))

    kernel = functools.partial(
        _flash_kernel, scale=hd ** -0.5, causal=causal, window=window,
        blk_q=blk_q, blk_k=blk_k, n_kv_blocks=n_kv)

    return pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),     # running max m
            pltpu.VMEM((blk_q, 1), jnp.float32),     # running denom l
            pltpu.VMEM((blk_q, hd), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
