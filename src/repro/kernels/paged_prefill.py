"""Pallas TPU kernel: paged prefill-attention for chunked prefill.

One fixed-size prompt chunk of ONE slot (DESIGN §11): ``C`` query tokens
starting at absolute position ``chunk_start`` attend causally to (a) the
slot's **previously-filled pages**, read straight from the page pool
through page-table indirection exactly as in
:mod:`repro.kernels.paged_attention`, and (b) the **in-flight chunk's own
keys/values**, which at kernel time have not been scattered into the pool
yet (attend-then-write — in ring mode the chunk overwrites ring rows that
earlier chunk queries must still see) and therefore ride in as dense
``(K, C, hd)`` operands.

Grid = (kv_heads, n_pages + 1) with the kv axis innermost: steps
``j < n_pages`` are pool pages, the extra last step is the chunk block.
The online-softmax loop (running max / denominator / accumulator in VMEM
scratch) is the one from :mod:`repro.kernels.flash_attention`; the output
tile is written on the chunk step.

Masking:

* pool rows map to absolute key positions — identity in linear mode, the
  ring formula ``pos(r) = (start-1) - ((start-1-r) mod window)`` in ring
  mode — and a row is valid iff ``0 <= pos < chunk_start`` (the occupied
  ring prefix is ``[0, min(start, window))``);
* sliding-window masking ``pos > q_pos - window`` is applied
  **per element** — unlike the contiguous flash kernel it is NOT implied
  by block order, because a ring page mixes positions from two windows;
* chunk keys ``jk`` are causal within the chunk (``jk <= qi``) and
  ragged-masked by the traced ``chunk_len`` (the last chunk of a prompt
  is padded to the static width ``C``);
* fully-dead page blocks (``j*page_size >= min(start, window or inf)``)
  are skipped via ``pl.when``, and the k/v index map clamps the logical
  page index to the last *used* page-table entry, so the DMA never
  touches a page the allocator didn't assign to this slot (the
  masked-tail contract of DESIGN §10 — NaN-poison tested).

``chunk_start`` / ``chunk_len`` are scalar-prefetch data, not part of the
jit key: the whole serving trace reuses ONE compiled kernel regardless of
prompt-length distribution.

The dense oracle is :func:`repro.kernels.ref.paged_prefill_attention_ref`
(gather pages → positional sdpa); the jit'd public entry with
interpret-mode fallback is :func:`repro.kernels.ops.paged_prefill_attention`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_prefill_kernel_call"]

NEG_INF = -1e30


def _prefill_kernel(pt_ref, meta_ref, q_ref, kc_ref, vc_ref, kp_ref, vp_ref,
                    o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                    page_size: int, n_pages: int, chunk: int, group: int,
                    window: int):
    ji = pl.program_id(1)
    start = meta_ref[0]
    clen = meta_ref[1]
    prev = jnp.minimum(start, window) if window else start

    @pl.when(ji == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    rows = chunk * group

    def _online(s, mask, v):
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        # zero rows that are entirely masked (exp(NEG_INF-NEG_INF)=1 trap)
        row_live = jnp.any(mask, axis=1, keepdims=True)
        p = jnp.where(row_live, p, 0.0)
        alpha = jnp.where(row_live | (m_prev > NEG_INF / 2),
                          jnp.exp(m_prev - m_new), 0.0)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot(p, v)
        m_scr[...] = m_new

    @pl.when(jnp.logical_and(ji < n_pages, ji * page_size < prev))
    def _page_step():
        q = q_ref[0].astype(jnp.float32)                # (C*G, hd)
        k = kp_ref[0, 0].astype(jnp.float32)            # (page_size, hd)
        v = vp_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q * scale, k,
                                (((1,), (1,)), ((), ())))  # (C*G, page_size)
        qi = jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 0) // group
        r = ji * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        if window:
            # ring row r holds the NEWEST pre-chunk position congruent to
            # r mod window; unoccupied rows resolve to pos < 0
            kpos = (start - 1) - jnp.mod(start - 1 - r, window)
        else:
            kpos = r
        mask = (kpos >= 0) & (kpos < start) & (r < prev)
        if window:
            mask &= kpos > (start + qi) - window
        # zero never-written value rows: their probs are exactly 0, but
        # 0·NaN = NaN in the accumulator dot would leak pool poison.  A
        # row is live iff some chunk query sees it — query 0 sees the most
        # under the window — recomputed on the (page_size, hd) grid of v
        # itself, as Mosaic cannot relayout a reduced mask into a column.
        rv = ji * page_size + jax.lax.broadcasted_iota(
            jnp.int32, v.shape, 0)
        kv_pos = (start - 1) - jnp.mod(start - 1 - rv, window) if window \
            else rv
        live = (kv_pos >= 0) & (kv_pos < start) & (rv < prev)
        if window:
            live &= kv_pos > start - window
        v = jnp.where(live, v, 0.0)
        _online(s, mask, v)

    @pl.when(ji == n_pages)
    def _chunk_step():
        q = q_ref[0].astype(jnp.float32)                # (C*G, hd)
        k = kc_ref[0].astype(jnp.float32)               # (C, hd)
        v = vc_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q * scale, k,
                                (((1,), (1,)), ((), ())))  # (C*G, C)
        qi = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 0) // group
        jk = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)
        mask = (jk <= qi) & (jk < clen)
        if window:
            mask &= jk > qi - window
        _online(s, mask, v)

    @pl.when(ji == n_pages)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def paged_prefill_kernel_call(q, k_chunk, v_chunk, k_pool, v_pool, pt_row,
                              meta, *, page_size: int, window: int = 0,
                              interpret: bool = False):
    """q: (K, C·G, hd) — chunk queries grouped by kv head, row ``i·G + g``
    is chunk token i, group member g; k_chunk, v_chunk: (K, C, hd) the
    in-flight chunk's keys/values (NOT yet in the pool); k_pool, v_pool:
    (K, num_pages, page_size, hd) head-major page pools (see
    :mod:`repro.kernels.paged_attention`); pt_row: (n_pages,) int32 —
    ONE slot's page-table row; meta: (2,) int32 ``[chunk_start,
    chunk_len]``.  Returns (K, C·G, hd)."""
    K, CG, hd = q.shape
    C = k_chunk.shape[1]
    assert CG % C == 0, (q.shape, k_chunk.shape)
    G = CG // C
    n_pages = pt_row.shape[0]
    assert k_pool.shape[0] == K and k_pool.shape[2:] == (page_size, hd), \
        (k_pool.shape, K, page_size, hd)
    assert meta.shape == (2,), meta.shape

    def used(pt, meta_, j):
        # clamp to the last USED page-table entry (masked-tail contract):
        # pages past ceil(min(start, window)/page_size) were never written
        # by this slot and must not be fetched.  pt[0] is always a real
        # page — pages are reserved at admission (serve/paged_cache.py).
        prev = meta_[0] if not window else jnp.minimum(meta_[0], window)
        last = jnp.maximum(pl.cdiv(prev, page_size) - 1, 0)
        return pt[jnp.minimum(jnp.minimum(j, n_pages - 1), last)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(K, n_pages + 1),
        in_specs=[
            pl.BlockSpec((1, CG, hd), lambda k, j, pt, meta_: (k, 0, 0)),
            pl.BlockSpec((1, C, hd), lambda k, j, pt, meta_: (k, 0, 0)),
            pl.BlockSpec((1, C, hd), lambda k, j, pt, meta_: (k, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda k, j, pt, meta_: (k, used(pt, meta_, j), 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda k, j, pt, meta_: (k, used(pt, meta_, j), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, CG, hd), lambda k, j, pt, meta_: (k, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((CG, 1), jnp.float32),      # running max m
            pltpu.VMEM((CG, 1), jnp.float32),      # running denom l
            pltpu.VMEM((CG, hd), jnp.float32),     # output accumulator
        ],
    )
    kernel = functools.partial(_prefill_kernel, scale=hd ** -0.5,
                               page_size=page_size, n_pages=n_pages,
                               chunk=C, group=G, window=window)
    return pl.pallas_call(
        kernel,
        name="paged_prefill",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(pt_row, meta, q, k_chunk, v_chunk, k_pool, v_pool)
