"""jit'd public wrappers around the Pallas kernels.

On the TPU backend these compile for Mosaic; on any other backend (the
CPU test suite) they run in Pallas interpret mode, functionally identical.
``chip_smoke.py`` checks that a chip run compiled them for Mosaic.
``edm_update_tree`` is the pytree-level entry the EDM optimizer uses when
``use_fused_kernel=True``.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from .edm_update import (BLOCK_ROWS, LANE, bus_consensus_flat,
                         edm_update_flat, edm_update_ef_flat,
                         gossip_axpy_flat, gossip_axpy_q8_flat)
from .flash_attention import flash_attention_kernel_call
from .paged_attention import paged_attention_kernel_call
from .paged_prefill import paged_prefill_kernel_call

__all__ = ["bus_consensus", "edm_update", "edm_update_tree",
           "edm_update_bus", "edm_update_bus_ef", "gossip_axpy",
           "gossip_axpy_rolled", "gossip_axpy_wire",
           "flash_attention", "paged_attention", "paged_prefill_attention",
           "padded_size"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def padded_size(n: int, block_rows: int | None = None) -> int:
    """Elements ``_pack`` actually streams for an ``n``-element array: padded
    up to a whole number of (block_rows, 128) grid tiles.  This is the
    per-leaf pad waste the packed bus amortizes (DESIGN §5) and the number
    the benchmarks' modeled-bytes columns must use — modeling with the
    logical ``n`` undercounts kernel HBM traffic per leaf."""
    if block_rows is None:
        block_rows = BLOCK_ROWS
    tile = block_rows * LANE
    return -(-n // tile) * tile


def _pack(leaf, block_rows, dtype=jnp.float32):
    """Flatten to (rows, LANE), padded; ``dtype=None`` keeps the leaf dtype
    (bf16 gossip payloads stay bf16 on the wire and in VMEM)."""
    flat = leaf.reshape(-1)
    if dtype is not None:
        flat = flat.astype(dtype)
    n = flat.size
    pad = padded_size(n, block_rows) - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, LANE), n


def _unpack(packed, n, shape, dtype):
    return packed.reshape(-1)[:n].reshape(shape).astype(dtype)


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "block_rows",
                                             "interpret"))
def edm_update(x, g, m, psi, *, alpha: float, beta: float,
               block_rows: int | None = None, interpret: bool | None = None):
    """Array-level fused EDM update.  Any shape; returns (m', ψ', φ).

    ``block_rows`` defaults to the REPRO_BLOCK_ROWS-tunable
    :data:`~repro.kernels.edm_update.BLOCK_ROWS` (the real-TPU sweep knob).
    """
    if block_rows is None:
        block_rows = BLOCK_ROWS
    if interpret is None:
        interpret = not _on_tpu()
    xp, n = _pack(x, block_rows)
    gp, _ = _pack(g, block_rows)
    mp, _ = _pack(m, block_rows)
    pp, _ = _pack(psi, block_rows)
    m2, psi2, phi = edm_update_flat(xp, gp, mp, pp, alpha=alpha, beta=beta,
                                    block_rows=block_rows, interpret=interpret)
    return (_unpack(m2, n, x.shape, m.dtype),
            _unpack(psi2, n, x.shape, psi.dtype),
            _unpack(phi, n, x.shape, x.dtype))


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "block_rows",
                                             "interpret"))
def edm_update_bus(x, g, m, psi, *, alpha: float, beta: float,
                   block_rows: int | None = None,
                   interpret: bool | None = None):
    """Bus-resident fused EDM update: ONE ``pallas_call`` over the whole
    ``(A, rows, 128)`` superbuffer (DESIGN §5), vs one per leaf for
    :func:`edm_update_tree`.  The bus layout already pads ``rows`` to a
    multiple of ``block_rows`` and aligns every leaf to the 8×128 tile, so
    no packing happens here — the buffers are griddable as-is.
    Returns ``(m', ψ', φ)`` in bus layout."""
    if block_rows is None:
        block_rows = BLOCK_ROWS
    if interpret is None:
        interpret = not _on_tpu()
    A, rows, lane = x.shape
    assert lane == LANE and (A * rows) % block_rows == 0, (x.shape, block_rows)
    flat = lambda b: b.reshape(A * rows, LANE)
    m2, psi2, phi = edm_update_flat(flat(x), flat(g), flat(m), flat(psi),
                                    alpha=alpha, beta=beta,
                                    block_rows=block_rows,
                                    interpret=interpret)
    return (m2.reshape(x.shape), psi2.reshape(x.shape), phi.reshape(x.shape))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bus_consensus(bus, *, block_rows: int | None = None,
                  interpret: bool | None = None):
    """Consensus distance ‖X − X̄‖²_F of an ``(A, rows, 128)`` f32 bus in
    ONE ``pallas_call`` that reads the bus once; each tile holds every
    agent's copy of its rows, so the whole bus must be on the device that
    runs it.  The bus layout pads ``rows`` to a multiple of ``block_rows``
    with zeros, which deviate by 0."""
    if block_rows is None:
        block_rows = BLOCK_ROWS
    if interpret is None:
        interpret = not _on_tpu()
    return jnp.sum(bus_consensus_flat(bus, block_rows=block_rows,
                                      interpret=interpret))


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "fmt",
                                             "block_rows", "interpret"))
def edm_update_bus_ef(x, g, m, psi, e, *, alpha: float, beta: float,
                      fmt: str, block_rows: int | None = None,
                      interpret: bool | None = None):
    """Bus-resident fused EDM update **with error-feedback quantization**
    (DESIGN §9): one pallas_call computes m', ψ', the wire payload
    ``Q(φ + e)`` and the next residual ``e' = (φ + e) − decode(Q(φ + e))``
    in a single pass over the ``(A, rows, 128)`` superbuffer — quantize and
    residual-update share the VMEM tile, no extra HBM round trips.

    Returns ``(m', ψ', payload, e')`` where ``payload`` is the wire-format
    pytree of :class:`repro.core.wire.WireCodec`: a bf16 bus for
    ``fmt="bf16"``, ``(q int8 bus, (A, rows // block_rows) f32 scales)``
    for ``fmt="int8"``.  The bus layout quantizes rows to a multiple of
    ``block_rows × shards``, so under ``agents="pod"`` each shard's row
    block holds whole scale blocks and this runs shard-locally unchanged.
    """
    if block_rows is None:
        block_rows = BLOCK_ROWS
    if interpret is None:
        interpret = not _on_tpu()
    A, rows, lane = x.shape
    assert lane == LANE and rows % block_rows == 0, (x.shape, block_rows)
    flat = lambda b: b.reshape(A * rows, LANE)
    outs = edm_update_ef_flat(flat(x), flat(g), flat(m), flat(psi), flat(e),
                              alpha=alpha, beta=beta, fmt=fmt,
                              block_rows=block_rows, interpret=interpret)
    if fmt == "bf16":
        m2, psi2, q, e2 = outs
        payload = q.reshape(x.shape)
    else:
        m2, psi2, q, scale, e2 = outs
        payload = (q.reshape(x.shape),
                   scale.reshape(A, rows // block_rows))
    return (m2.reshape(x.shape), psi2.reshape(x.shape), payload,
            e2.reshape(x.shape))


def edm_update_tree(params: Any, grads: Any, m: Any, psi: Any, *,
                    alpha: float, beta: float) -> Tuple[Any, Any, Any]:
    """Pytree-level fused update: returns (m', φ, ψ') trees (optimizer order)."""
    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(m)
    flat_s = treedef.flatten_up_to(psi)
    outs = [edm_update(x, g, mm, ss, alpha=alpha, beta=beta)
            for x, g, mm, ss in zip(flat_p, flat_g, flat_m, flat_s)]
    m_new = treedef.unflatten([o[0] for o in outs])
    psi_new = treedef.unflatten([o[1] for o in outs])
    phi = treedef.unflatten([o[2] for o in outs])
    return m_new, phi, psi_new


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret",
                                             "out_dtype"))
def _gossip_axpy_jit(operands, weights, block_rows, interpret,
                     out_dtype=None):
    first = operands[0]
    packed = [_pack(o, block_rows, dtype=None)[0] for o in operands]
    n = first.size
    out = gossip_axpy_flat(packed, weights, block_rows=block_rows,
                           interpret=interpret, out_dtype=out_dtype)
    return _unpack(out, n, first.shape,
                   first.dtype if out_dtype is None else out_dtype)


def gossip_axpy(operands, weights, *, block_rows: int | None = None,
                interpret: bool | None = None):
    """n-ary fused gossip combine  Σₖ wₖ·operandₖ  for arbitrary-shape arrays.

    All operands must share one shape and dtype (f32 or bf16).  This is the
    array-level entry the ppermute mixing engine calls once per leaf after
    its collective-permutes (DESIGN §3).  ``weights`` are traced data, not
    part of the jit key: a time-varying schedule whose rounds share an arity
    reuses one compiled kernel across rounds (DESIGN §4), and distinct
    arities each compile exactly once.  ``block_rows`` (default: env-tunable
    :data:`~repro.kernels.edm_update.BLOCK_ROWS`) is the TPU tuning knob.
    """
    if block_rows is None:
        block_rows = BLOCK_ROWS
    if interpret is None:
        interpret = not _on_tpu()
    return _gossip_axpy_jit(tuple(operands),
                            jnp.asarray(weights, jnp.float32),
                            block_rows, interpret)


@functools.partial(jax.jit, static_argnames=("shifts", "block_rows",
                                             "interpret"))
def _gossip_axpy_rolled_jit(x, weights, shifts, block_rows, interpret):
    tiles = x[0].size // (block_rows * LANE)
    out = gossip_axpy_flat([x.reshape(-1, LANE)] * len(shifts), weights,
                           block_rows=block_rows, interpret=interpret,
                           tile_shifts=tuple(s * tiles for s in shifts))
    return out.reshape(x.shape)


def gossip_axpy_rolled(x, shifts, weights, *, block_rows: int | None = None,
                       interpret: bool | None = None):
    """Σₖ wₖ·roll(x, shiftsₖ, axis=0): the combine of gossip terms that are
    all cyclic shifts of one device-local agent block ``x`` (A, ...) — the
    blocked engine with every agent on one device.  Each roll is read
    through the kernel's index map when an agent's slice is whole
    ``(block_rows, 128)`` tiles (the packed bus), so no rolled copy of the
    bus is made; other shapes roll in XLA first."""
    if block_rows is None:
        block_rows = BLOCK_ROWS
    if interpret is None:
        interpret = not _on_tpu()
    if x[0].size % (block_rows * LANE):
        return gossip_axpy([jnp.roll(x, s, axis=0) for s in shifts], weights,
                           block_rows=block_rows, interpret=interpret)
    return _gossip_axpy_rolled_jit(x, jnp.asarray(weights, jnp.float32),
                                   tuple(int(s) % x.shape[0] for s in shifts),
                                   block_rows, interpret)


@functools.partial(jax.jit, static_argnames=("fmt", "block_rows",
                                             "interpret"))
def _gossip_axpy_wire_jit(payloads, weights, fmt, block_rows, interpret):
    if fmt in ("f32", "bf16"):
        # bf16 wire: accumulate f32 in-kernel, store the mixed bus f32 —
        # the decode is the astype the axpy kernel already performs.
        return _gossip_axpy_jit(payloads, weights, block_rows, interpret,
                                out_dtype=jnp.float32)
    qs, scales = zip(*payloads)
    first = qs[0]
    flat_qs = tuple(q.reshape(-1, LANE) for q in qs)
    # (n, n_tiles) weight × per-tile-scale products: scales flatten in the
    # same (agent-major) order the flattened bus tiles do, because rows is
    # a multiple of block_rows per agent.
    coefs = (jnp.asarray(weights, jnp.float32)[:, None]
             * jnp.stack([s.reshape(-1) for s in scales]))
    out = gossip_axpy_q8_flat(flat_qs, coefs, block_rows=block_rows,
                              interpret=interpret)
    return out.reshape(first.shape)


def gossip_axpy_wire(payloads, weights, *, fmt: str,
                     block_rows: int | None = None,
                     interpret: bool | None = None):
    """Fused decode-and-combine for wire-format gossip payloads
    (DESIGN §9): ``Σₖ wₖ · decode(payloadₖ)`` with the dequantize folded
    into the n-ary combine — int8/bf16 payloads widen to f32 exactly once,
    inside the kernel, and the mixed bus comes out f32.

    ``payloads`` are post-permute :class:`~repro.core.wire.WireCodec`
    payloads of one arity: f32/bf16 arrays, or ``(q, scale)`` pairs whose
    ``scale`` carries one f32 per ``(block_rows, 128)`` block in tile
    order.  ``weights`` are traced data, as in :func:`gossip_axpy`.
    """
    if block_rows is None:
        block_rows = BLOCK_ROWS
    if interpret is None:
        interpret = not _on_tpu()
    return _gossip_axpy_wire_jit(tuple(payloads),
                                 jnp.asarray(weights, jnp.float32),
                                 fmt, block_rows, interpret)


@functools.partial(jax.jit, static_argnames=("causal", "window", "blk_q",
                                             "blk_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    blk_q: int = 128, blk_k: int = 128,
                    interpret: bool | None = None):
    """Flash GQA attention, (B, H, S, hd) layout."""
    if interpret is None:
        interpret = not _on_tpu()
    return flash_attention_kernel_call(q, k, v, causal=causal, window=window,
                                       blk_q=blk_q, blk_k=blk_k,
                                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def paged_attention(q, k_pool, v_pool, page_table, kv_len, *,
                    page_size: int, interpret: bool | None = None):
    """Paged decode-attention (DESIGN §10): q (B, K, G, hd) slot-batched
    single-token queries against (K, num_pages, page_size, hd) page pools,
    gathered through a (B, n_pages) page table with per-slot ``kv_len``
    masking.  Oracle: :func:`repro.kernels.ref.paged_attention_ref`."""
    if interpret is None:
        interpret = not _on_tpu()
    return paged_attention_kernel_call(q, k_pool, v_pool, page_table, kv_len,
                                       page_size=page_size,
                                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("page_size", "window",
                                             "interpret"))
def paged_prefill_attention(q, k_chunk, v_chunk, k_pool, v_pool, pt_row,
                            chunk_start, chunk_len, *, page_size: int,
                            window: int = 0, interpret: bool | None = None):
    """Paged prefill-attention for one chunk of one slot (DESIGN §11).

    Model layout in and out: q (1, C, H, hd) chunk queries, k_chunk /
    v_chunk (1, C, K, hd) the in-flight chunk's keys/values (not yet
    scattered into the pool), pools (K, num_pages, page_size, hd),
    pt_row (n_pages,) the slot's page-table row.  ``chunk_start`` /
    ``chunk_len`` are traced int32 scalars — NOT part of the jit key, so
    every chunk of every prompt length reuses one compiled kernel.
    Oracle: :func:`repro.kernels.ref.paged_prefill_attention_ref`."""
    if interpret is None:
        interpret = not _on_tpu()
    _, C, H, hd = q.shape
    K = k_chunk.shape[2]
    G = H // K
    # (1, C, H, hd) -> (K, C*G, hd), row i*G + g = (token i, group member g)
    qk = (q.reshape(C, K, G, hd).transpose(1, 0, 2, 3).reshape(K, C * G, hd))
    kc = k_chunk[0].transpose(1, 0, 2)           # (K, C, hd)
    vc = v_chunk[0].transpose(1, 0, 2)
    meta = jnp.stack([jnp.asarray(chunk_start, jnp.int32),
                      jnp.asarray(chunk_len, jnp.int32)])
    out = paged_prefill_kernel_call(qk, kc, vc, k_pool, v_pool,
                                    jnp.asarray(pt_row, jnp.int32), meta,
                                    page_size=page_size, window=window,
                                    interpret=interpret)
    return (out.reshape(K, C, G, hd).transpose(1, 0, 2, 3)
            .reshape(1, C, H, hd))
