"""Pallas TPU kernel: paged decode-attention over a page-table KV cache.

One query token per slot (decode), keys/values read **directly from the
page pool** — no dense cache materialization.  The page table and per-slot
valid-row counts ride in as scalar-prefetch operands
(``pltpu.PrefetchScalarGridSpec``).  GQA is native: q arrives
``(B, K, G, hd)`` and each kv head's pages serve its ``G``-query group.

The pools are head-major, ``(K, num_pages, page_size, hd)``: page ``p`` of
every kv head is the slice ``pool[:, p]``, whose two minor dims are the
``(page_size, hd)`` tile Mosaic lays out.

Walk.  The kernel walks each slot's pages in blocks of ``ppb`` pages
(:func:`pages_per_block`), up to ``cdiv(kv_len, page_size · ppb)`` blocks,
and folds each block into every head's online softmax — running max,
denominator and f32 accumulator in VMEM scratch, as in
:mod:`repro.kernels.flash_attention`.  An idle slot (``kv_len == 0``) walks
no block and writes a zero tile (the denominator clamp, no NaNs).

* Head dims that fill whole 128-lane tiles (every production config):
  grid = ``(slots,)``, all ``K`` heads in the step's block.  The pools stay
  in HBM (``memory_space=pl.ANY``); a ``fori_loop`` over the slot's live
  blocks copies each block's pages, one ``make_async_copy`` a page for all
  heads, into a double-buffered VMEM scratch, starting block ``i + 1``'s
  copies before block ``i`` is computed.  The walk's length follows the
  slot's live pages.
* Narrower head dims (smollm's 64): Mosaic refuses any copy of a slice of a
  pool whose minor dim is padded to the lane tile ("Slice shape along
  dimension 3 must be aligned to tiling (128)"), so the pages come through
  the grid's own pipeline instead: grid = ``(slots, cdiv(n_pages, ppb))``,
  each page of a block an operand of its own (at most
  :data:`GRID_MAX_PAGES`).  Steps past the live blocks repeat the last live
  block's page ids, so the pipeline copies nothing for them, and skip the
  compute.

``ppb`` follows the shape: as many pages of k and v, all heads, as fill
half of :data:`BLOCK_BUDGET` (the other half is the second buffer), at most
a slot's pages.  At page 64, hd 128, K 4 in bf16 a page of k and v is
128 KiB, so 8 pages a block.

Safety contract (the masked-tail property, DESIGN §10):

* page-table entries past ``ceil(kv_len / page_size)`` are never read —
  the copying walk copies a block's used pages only; the grid
  walk clamps each page id to the last used entry, and fetches page 0,
  which every pool has, for an idle slot;
* rows past ``kv_len`` are masked: scores to -inf before the online
  softmax, values to 0 (a buffer past a block's used pages holds what an
  earlier block left there), so pool or buffer garbage never reaches the
  output.

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

__all__ = ["BLOCK_BUDGET", "GRID_MAX_PAGES", "pages_per_block",
           "paged_attention_kernel_call"]

NEG_INF = -1e30
LANES = 128
# VMEM bytes of the double-buffered k/v page blocks (two blocks)
BLOCK_BUDGET = 2 << 20
# pages a block on the grid walk, where each page is a pipelined operand
GRID_MAX_PAGES = 8


def pages_per_block(*, page_size: int, hd: int, n_kv_heads: int,
                    n_pages: int, itemsize: int) -> int:
    """Pages a block: one block of k and v pages, all kv heads, fills half
    of :data:`BLOCK_BUDGET`; at least 1, at most a slot's ``n_pages``."""
    page_bytes = 2 * n_kv_heads * page_size * hd * itemsize
    return max(1, min(n_pages, BLOCK_BUDGET // (2 * page_bytes)))


def _init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _finish(o_ref, l_scr, acc_scr):
    denom = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _fold_block(q_ref, k_of, v_of, base, kv_len, m_scr, l_scr, acc_scr, *,
                scale: float, rows: int):
    """Fold one block of ``rows`` keys/values, rows ``base ..`` of the slot,
    into every head's online softmax; ``k_of(h)`` / ``v_of(h)`` give head
    h's ``(rows, hd)`` block.  Rows at or past ``kv_len`` count for
    nothing."""
    live = base + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1) < kv_len
    live_v = base + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < kv_len
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(jnp.float32)                  # (G, hd)
        k = k_of(h).astype(jnp.float32)                      # (rows, hd)
        v = jnp.where(live_v, v_of(h).astype(jnp.float32), 0.0)
        s = jax.lax.dot_general(q * scale, k,
                                (((1,), (1,)), ((), ())))    # (G, rows)
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_scr[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[h] = alpha * acc_scr[h] + jax.lax.dot(p, v)
        m_scr[h] = m_new


def _rows(pages):
    return pages.astype(jnp.float32).reshape(-1, pages.shape[-1])


def _copy_walk_kernel(pt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                      k_buf, v_buf, sems, m_scr, l_scr, acc_scr, *,
                      scale: float, page_size: int, ppb: int):
    b = pl.program_id(0)
    kv_len = len_ref[b]
    n_used = pl.cdiv(kv_len, page_size)               # pages holding rows
    n_blocks = pl.cdiv(n_used, ppb)
    rows = ppb * page_size
    _init(m_scr, l_scr, acc_scr)

    def each_page(i, slot, op):
        """``op`` on the copies of block i's used pages into buffer
        ``slot``: k and v, all heads of a page in one copy."""
        first = i * ppb

        def body(j, c):
            page = pt_ref[b, first + j]
            for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                op(pltpu.make_async_copy(pool.at[:, page], buf.at[slot, j],
                                         sems.at[slot]))
            return c

        jax.lax.fori_loop(0, jnp.minimum(ppb, n_used - first), body, 0)

    @pl.when(n_blocks > 0)
    def _prime():
        each_page(0, 0, lambda cp: cp.start())

    def block(i, c):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            each_page(i + 1, 1 - slot, lambda cp: cp.start())

        each_page(i, slot, lambda cp: cp.wait())
        # (ppb, page_size, hd) of head h -> (rows, hd), cast first so the
        # merge of pages lands on f32's 8-row tiles
        _fold_block(q_ref,
                    lambda h: _rows(k_buf[slot, :, h]),
                    lambda h: _rows(v_buf[slot, :, h]),
                    i * rows, kv_len, m_scr, l_scr, acc_scr,
                    scale=scale, rows=rows)
        return c

    jax.lax.fori_loop(0, n_blocks, block, 0)
    _finish(o_ref, l_scr, acc_scr)


def _grid_walk_kernel(pt_ref, len_ref, q_ref, *refs, scale: float,
                      page_size: int, ppb: int, n_blocks: int):
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * ppb:]
    b, i = pl.program_id(0), pl.program_id(1)
    kv_len = len_ref[b]
    rows = ppb * page_size

    @pl.when(i == 0)
    def _():
        _init(m_scr, l_scr, acc_scr)

    def rows_of(refs, h):
        return jnp.concatenate([r[h, 0].astype(jnp.float32) for r in refs])

    @pl.when(i * rows < kv_len)
    def _():
        _fold_block(q_ref, lambda h: rows_of(k_refs, h),
                    lambda h: rows_of(v_refs, h), i * rows, kv_len,
                    m_scr, l_scr, acc_scr, scale=scale, rows=rows)

    @pl.when(i == n_blocks - 1)
    def _():
        _finish(o_ref, l_scr, acc_scr)


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
    ppb = pages_per_block(page_size=page_size, hd=hd, n_kv_heads=K,
                          n_pages=n_pages, itemsize=k_pool.dtype.itemsize)
    stats = [pltpu.VMEM((K, G, 1), jnp.float32),   # running max m
             pltpu.VMEM((K, G, 1), jnp.float32),   # running denom l
             pltpu.VMEM((K, G, hd), jnp.float32)]  # output accumulator
    common = dict(scale=hd ** -0.5, page_size=page_size)

    if hd % LANES == 0:
        buf = (2, ppb, K, page_size, hd)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, K, G, hd), lambda b, pt, ln: (b, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, K, G, hd),
                                   lambda b, pt, ln: (b, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM(buf, k_pool.dtype),   # 2 k blocks
                            pltpu.VMEM(buf, v_pool.dtype),   # 2 v blocks
                            pltpu.SemaphoreType.DMA((2,)),   # one a buffer
                            *stats],
        )
        kernel = functools.partial(_copy_walk_kernel, ppb=ppb, **common)
        operands = (k_pool, v_pool)
    else:
        ppb = min(ppb, GRID_MAX_PAGES)
        n_blocks = pl.cdiv(n_pages, ppb)

        def page_spec(j):
            def index(b, i, pt, ln):
                # past the live blocks, repeat the last live block's ids
                # (no copy); past the used prefix, the last used entry; an
                # idle slot reads page 0, which every pool has
                n_used = pl.cdiv(ln[b], page_size)
                i = jnp.minimum(i, jnp.maximum(pl.cdiv(n_used, ppb) - 1, 0))
                last = jnp.maximum(n_used - 1, 0)
                page = pt[b, jnp.minimum(i * ppb + j, last)]
                return (0, jnp.where(n_used > 0, page, 0), 0, 0)
            return pl.BlockSpec((K, 1, page_size, hd), index)

        pages = [page_spec(j) for j in range(ppb)]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_blocks),
            in_specs=[pl.BlockSpec((1, K, G, hd),
                                   lambda b, i, pt, ln: (b, 0, 0, 0)),
                      *pages, *pages],
            out_specs=pl.BlockSpec((1, K, G, hd),
                                   lambda b, i, pt, ln: (b, 0, 0, 0)),
            scratch_shapes=stats,
        )
        kernel = functools.partial(_grid_walk_kernel, ppb=ppb,
                                   n_blocks=n_blocks, **common)
        operands = (k_pool,) * ppb + (v_pool,) * ppb

    return pl.pallas_call(
        kernel,
        name="paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(page_table, kv_len, q, *operands)
