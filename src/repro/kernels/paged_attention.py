"""Pallas TPU kernel: paged decode-attention over a page-table KV cache.

One query token per slot (decode), keys/values gathered **directly from the
page pool** — no dense cache materialization.  The page table and per-slot
valid-row counts ride in as scalar-prefetch operands
(``pltpu.PrefetchScalarGridSpec``), so the k/v BlockSpec index maps can
compute the physical page for grid step ``(b, k, j)`` before the DMA is
issued: logical page ``j`` of slot ``b`` reads physical page
``page_table[b, j]``.  GQA head sharing mirrors the flash kernel — the
grid walks kv heads and each step processes that head's whole ``G``-query
group from one gathered page.

The pools are head-major, ``(K, num_pages, page_size, hd)``: one grid step
fetches the ``(page_size, hd)`` tile of one head of one page, whose two
minor dims are what Mosaic tiles (a page-major pool would put the kv-head
axis second-to-last with a block of 1, which Mosaic refuses).

Grid = (slots, kv_heads, pages_per_slot) with the page axis innermost;
running max / denominator / accumulator live in VMEM scratch exactly as in
:mod:`repro.kernels.flash_attention`, and the output tile is written on the
last page step.

Safety contract (the masked-tail property, DESIGN §10):

* page-table entries past ``ceil(kv_len / page_size)`` are never read —
  the index map clamps the logical page index to the last *used* entry,
  so the DMA only ever touches pages the allocator assigned to this slot;
* rows past ``kv_len`` inside the last used page are masked to -inf
  before the online softmax (and fully-dead pages are skipped via
  ``pl.when``), so pool garbage can never leak into the output.

A slot with ``kv_len == 0`` (idle) produces a zero output tile — the
denominator clamp handles the all-masked case, no NaNs.

The dense oracle is :func:`repro.kernels.ref.paged_attention_ref` (gather
pages → ``sdpa_ref``); the jit'd public entry with interpret-mode fallback
is :func:`repro.kernels.ops.paged_attention`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_kernel_call"]

NEG_INF = -1e30


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, page_size: int,
                  n_pages: int):
    b = pl.program_id(0)
    ji = pl.program_id(2)

    @pl.when(ji == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[b]
    # a page is live iff it holds at least one valid row
    block_live = ji * page_size < kv_len

    @pl.when(block_live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (page_size, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q * scale, k,
                                (((1,), (1,)), ((), ())))  # (G, page_size)
        r = ji * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        s = jnp.where(r < kv_len, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        row_live = jnp.any(r < kv_len, axis=1, keepdims=True)
        p = jnp.where(row_live, p, 0.0)
        alpha = jnp.where(row_live | (m_prev > NEG_INF / 2),
                          jnp.exp(m_prev - m_new), 0.0)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot(p, v)
        m_scr[...] = m_new

    @pl.when(ji == n_pages - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def paged_attention_kernel_call(q, k_pool, v_pool, page_table, kv_len, *,
                                page_size: int, interpret: bool = False):
    """q: (B, K, G, hd) — slot-batched single-token queries, grouped by kv
    head; k_pool, v_pool: (K, num_pages, page_size, hd) page pools;
    page_table: (B, n_pages) int32 physical-page ids; kv_len: (B,) int32
    valid KV rows per slot (ring mode: ``min(length, window)``).
    Returns (B, K, G, hd)."""
    B, K, G, hd = q.shape
    n_pages = page_table.shape[1]
    assert k_pool.shape[0] == K and k_pool.shape[2:] == (page_size, hd), \
        (k_pool.shape, K, page_size, hd)
    assert page_table.shape[0] == B and kv_len.shape == (B,), \
        (page_table.shape, kv_len.shape, B)

    def used(pt, ln, b, j):
        # clamp to the last USED page-table entry: entries past the valid
        # prefix are NULL and must never be fetched (masked-tail contract)
        last = jnp.maximum(pl.cdiv(ln[b], page_size) - 1, 0)
        return pt[b, jnp.minimum(j, last)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd),
                         lambda b, k, j, pt, ln: (b, k, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, k, j, pt, ln: (k, used(pt, ln, b, j), 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, k, j, pt, ln: (k, used(pt, ln, b, j), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, k, j, pt, ln: (b, k, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),      # running max m
            pltpu.VMEM((G, 1), jnp.float32),      # running denom l
            pltpu.VMEM((G, hd), jnp.float32),     # output accumulator
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=hd ** -0.5,
                               page_size=page_size, n_pages=n_pages)
    return pl.pallas_call(
        kernel,
        name="paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(page_table, kv_len, q, k_pool, v_pool)
