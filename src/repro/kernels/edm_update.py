"""Pallas TPU kernel: fused EDM optimizer update (+ ring-gossip combine).

The EDM hot loop is memory-bound: the unfused jnp chain

    m'  = β m + (1-β) g
    ψ'  = x − α m'
    φ   = ψ' + x − ψ

reads x, g, m, ψ and writes m', ψ', φ as ~7 separate HBM-stream kernels
(XLA fuses some, but the optimizer-state round trip still dominates at
multi-billion-parameter scale).  This kernel performs the whole chain in one
pass over VMEM tiles: 4 reads + 3 writes = 7 HBM touches of N elements total,
the information-theoretic minimum.

``gossip_axpy`` fuses the post-permute combine  Σₖ wₖ·payloadₖ  (one payload
per gossip term — center/left/right in the ring case, more for exp graphs
and hierarchical topologies) into one pass — applied after the explicit
``ppermute``s of the production gossip engine (DESIGN §3).  n-ary, with a
bf16 payload path that accumulates in f32.

Layout: parameters are flattened and tiled to (rows, 128) f32; one grid step
processes a (BLOCK_ROWS, 128) tile — 8×128-aligned for the VPU, comfortably
inside the ~16 MB VMEM budget at the default 512×128×4 B×7 buffers ≈ 1.8 MB.

Memory: the EDM kernels write m' over m, ψ' over ψ and φ over g (and the
EF residual e' over e) through ``input_output_aliases``; the gossip
combine writes its sum over its first operand.  With the caller donating
its state, the update then needs no bus-sized buffer beyond its inputs —
at full width a two-agent bus is ~3.3 GB per buffer, and three fresh
outputs do not fit one 16 GB chip next to the state.  φ takes g's buffer
because the train step's g is a temporary (the packed gradients), while
x may be returned as the new iterate (the overlapped step); an input
still read after the kernel costs XLA a defensive copy.

``bus_consensus`` reads the whole ``(A, rows, 128)`` bus once and returns
the consensus distance ‖X − X̄‖²_F as one lane-dense partial per tile: a
tile holds every agent's copy of the same rows, so the agent mean, the
deviations and their squares never leave VMEM (XLA cannot fuse the reduce
over the agent axis into the reduce that consumes it, and makes several
bus-sized passes of the same expression).

Each ``pallas_call`` carries a stable ``name`` (``edm_update``,
``edm_update_ef_<fmt>``, ``gossip_axpy``, ``gossip_axpy_q8``,
``bus_consensus``); it shows in the compiled HLO's ``op_name`` and in
profiler traces.

Two callers feed these kernels (kernels/ops.py): the per-leaf wrappers
(``edm_update`` / ``gossip_axpy``) pack each pytree leaf independently —
one pallas_call and one pad-to-grid per leaf — while the packed parameter
bus (``repro.core.bus``, DESIGN §5) presents the whole per-agent tree as a
single pre-aligned (rows, 128) buffer, so ``edm_update_bus`` runs the grid
exactly once per train step regardless of leaf count.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["edm_update_flat", "edm_update_ef_flat", "gossip_axpy_flat",
           "gossip_axpy_q8_flat", "bus_consensus_flat", "BLOCK_ROWS", "LANE"]

def _env_block_rows() -> int:
    """Grid-tile height: the knob the real-TPU tuning sweep turns.  Read
    once at import from REPRO_BLOCK_ROWS (benchmarks/gossip_micro.py
    --block-rows and the per-call ``block_rows=`` args override it); must
    be a multiple of 8 for the 8×128 VPU tile."""
    raw = os.environ.get("REPRO_BLOCK_ROWS", "")
    if not raw:
        return 512
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(f"REPRO_BLOCK_ROWS must be an integer, got {raw!r}")
    if n <= 0 or n % 8:
        raise ValueError(
            f"REPRO_BLOCK_ROWS must be a positive multiple of 8, got {n}")
    return n


BLOCK_ROWS = _env_block_rows()
LANE = 128


def _edm_kernel(x_ref, g_ref, m_ref, psi_ref, m_out, psi_out, phi_out, *,
                alpha: float, beta: float):
    x = x_ref[...]
    g = g_ref[...]
    m = m_ref[...]
    psi = psi_ref[...]
    m_new = beta * m + (1.0 - beta) * g
    psi_new = x - alpha * m_new
    phi = psi_new + x - psi
    m_out[...] = m_new
    psi_out[...] = psi_new
    phi_out[...] = phi


def edm_update_flat(x, g, m, psi, *, alpha: float, beta: float,
                    block_rows: int = BLOCK_ROWS, interpret: bool = False):
    """All inputs: (rows, 128) f32 with rows % block_rows == 0.
    Returns (m_new, psi_new, phi), aliased onto (m, psi, g)."""
    rows, lane = x.shape
    assert lane == LANE and rows % block_rows == 0, (x.shape, block_rows)
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    out_sds = jax.ShapeDtypeStruct(x.shape, x.dtype)
    return pl.pallas_call(
        functools.partial(_edm_kernel, alpha=alpha, beta=beta),
        name="edm_update",
        grid=grid,
        in_specs=[spec] * 4,
        out_specs=[spec] * 3,
        out_shape=[out_sds] * 3,
        input_output_aliases={2: 0, 3: 1, 1: 2},
        interpret=interpret,
    )(x, g, m, psi)


def _edm_ef_bf16_kernel(x_ref, g_ref, m_ref, psi_ref, e_ref,
                        m_out, psi_out, q_out, e_out, *,
                        alpha: float, beta: float):
    # EDM chain + error-feedback bf16 quantize in ONE pass: the corrected
    # payload c = φ + e rounds to bf16 on the wire, and the rounding error
    # stays behind as the next residual.  5 reads + 4 writes — no extra HBM
    # round trip vs the uncompressed kernel's 4+3 (e in, e out, φ→q swap).
    x = x_ref[...]
    m_new = beta * m_ref[...] + (1.0 - beta) * g_ref[...]
    psi_new = x - alpha * m_new
    c = psi_new + x - psi_ref[...] + e_ref[...]
    q = c.astype(jnp.bfloat16)
    m_out[...] = m_new
    psi_out[...] = psi_new
    q_out[...] = q
    e_out[...] = c - q.astype(jnp.float32)


def _edm_ef_int8_kernel(x_ref, g_ref, m_ref, psi_ref, e_ref,
                        m_out, psi_out, q_out, s_out, e_out, *,
                        alpha: float, beta: float):
    # int8 variant: the grid tile IS the scale block (block_rows, 128) — one
    # symmetric absmax scale per tile, broadcast over a lane-dense (1, 1,
    # 128) output block (a (1, 1) block over (n_tiles, 1) is not one Mosaic
    # can tile).  Guards
    # mirror core/wire.py: non-finite values are masked out of absmax, NaN
    # encodes to 0, ±Inf saturates to ±127; an all-zero tile (the bus pad
    # tail) gets scale 0 and q 0 — no 0/0.
    x = x_ref[...]
    m_new = beta * m_ref[...] + (1.0 - beta) * g_ref[...]
    psi_new = x - alpha * m_new
    c = psi_new + x - psi_ref[...] + e_ref[...]
    mag = jnp.where(jnp.isfinite(c), jnp.abs(c), 0.0)
    absmax = jnp.max(mag)
    scale = absmax / 127.0
    inv = jnp.where(absmax > 0.0, 127.0 / jnp.maximum(absmax, 1e-30), 0.0)
    q = jnp.clip(jnp.round(c * inv), -127.0, 127.0)
    q = jnp.where(jnp.isnan(c), 0.0, q)
    m_out[...] = m_new
    psi_out[...] = psi_new
    q_out[...] = q.astype(jnp.int8)
    s_out[...] = jnp.broadcast_to(scale, s_out.shape)
    e_out[...] = c - q * scale


def edm_update_ef_flat(x, g, m, psi, e, *, alpha: float, beta: float,
                       fmt: str, block_rows: int = BLOCK_ROWS,
                       interpret: bool = False):
    """Fused EDM + error-feedback quantize over (rows, 128) f32 buffers.

    Returns ``(m', ψ', q, e')`` for ``fmt="bf16"`` and
    ``(m', ψ', q, scale, e')`` for ``fmt="int8"`` with ``scale`` shaped
    ``(rows // block_rows, 1)`` f32 (one per grid tile).  m', ψ' and e' are
    aliased onto m, ψ and e.  ``fmt="f32"`` has no quantize to fuse —
    callers use :func:`edm_update_flat`.
    """
    rows, lane = x.shape
    assert lane == LANE and rows % block_rows == 0, (x.shape, block_rows)
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    f32 = jax.ShapeDtypeStruct(x.shape, jnp.float32)
    kern = functools.partial(
        {"bf16": _edm_ef_bf16_kernel, "int8": _edm_ef_int8_kernel}[fmt],
        alpha=alpha, beta=beta)
    if fmt == "bf16":
        out_specs = [spec, spec, spec, spec]
        out_shape = [f32, f32,
                     jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), f32]
    else:
        if not interpret:
            # int8 VMEM tiles are (32, 128) minimum on TPU.
            assert block_rows % 32 == 0, block_rows
        s_spec = pl.BlockSpec((1, 1, LANE), lambda i: (i, 0, 0))
        out_specs = [spec, spec, spec, s_spec, spec]
        out_shape = [f32, f32,
                     jax.ShapeDtypeStruct(x.shape, jnp.int8),
                     jax.ShapeDtypeStruct((rows // block_rows, 1, LANE),
                                          jnp.float32), f32]
    outs = pl.pallas_call(
        kern,
        name=f"edm_update_ef_{fmt}",
        grid=grid,
        in_specs=[spec] * 5,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={2: 0, 3: 1, 4: len(out_shape) - 1},
        interpret=interpret,
    )(x, g, m, psi, e)
    if fmt == "int8":
        m2, psi2, q, scale, e2 = outs
        return m2, psi2, q, scale[:, 0, :1], e2
    return outs


def _axpy_kernel(w_ref, *refs):
    # refs = (in_0, ..., in_{n-1}, out); w_ref = (1, n) weights in SMEM —
    # runtime values, so one compiled kernel serves every weight set of one
    # arity (time-varying schedules swap rounds without retracing).
    # Accumulate in f32 so a bf16 gossip payload only rounds once, on the
    # final store.
    o_ref = refs[-1]
    acc = w_ref[0, 0] * refs[0][...].astype(jnp.float32)
    for k, r in enumerate(refs[1:-1], start=1):
        acc += w_ref[0, k] * r[...].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def gossip_axpy_flat(operands, weights, *, block_rows: int | None = None,
                     interpret: bool = False, out_dtype=None,
                     tile_shifts=None):
    """Fused n-ary gossip combine  Σₖ wₖ·operandₖ  over (rows, 128) tiles.

    ``operands`` are the post-permute neighbor payloads of one gossip step
    (one per :class:`~repro.core.topology.ShiftTerm`); ``weights`` the
    matching mixing weights — floats or a traced (n,) array; they enter the
    kernel as an SMEM operand, so the compiled kernel is keyed on the
    *arity* n, not the weight values.  All operands share one shape/dtype
    (f32 or bf16); accumulation is f32, output dtype follows the operands
    unless ``out_dtype`` overrides it (the wire-decode combine stores f32
    from bf16 payloads so the mixed iterate never re-rounds).  The ring
    case of the paper's experiments is the 3-ary instance
    (center/left/right).

    ``tile_shifts`` (static ints, one per operand) read operand k rolled by
    ``tile_shifts[k]`` whole tiles: output tile i takes tile
    ``(i - shift) mod n_tiles`` — a roll done by the index map, so the
    rolled copy never exists in HBM.  Without shifts the sum is written
    over operand 0 (``input_output_aliases``); with them it is not, since
    other operands may still read the tiles it would overwrite.
    """
    if block_rows is None:
        block_rows = BLOCK_ROWS
    operands = tuple(operands)
    w = jnp.asarray(weights, jnp.float32).reshape(1, -1)
    assert operands and w.shape[1] == len(operands), (len(operands), w.shape)
    rows, lane = operands[0].shape
    assert lane == LANE and rows % block_rows == 0, (operands[0].shape,
                                                     block_rows)
    assert all(o.shape == operands[0].shape and o.dtype == operands[0].dtype
               for o in operands)
    n_tiles = rows // block_rows
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    if tile_shifts is None:
        in_specs = [spec] * len(operands)
    else:
        assert len(tile_shifts) == len(operands), (tile_shifts, operands)
        in_specs = [pl.BlockSpec((block_rows, LANE),
                                 lambda i, s=s: ((i - s) % n_tiles, 0))
                    for s in tile_shifts]
    if out_dtype is None:
        out_dtype = operands[0].dtype
    aliases = ({1: 0} if tile_shifts is None
               and jnp.dtype(out_dtype) == operands[0].dtype else {})
    return pl.pallas_call(
        _axpy_kernel,
        name="gossip_axpy",
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(operands[0].shape, out_dtype),
        input_output_aliases=aliases,
        interpret=interpret,
    )(w, *operands)


def _axpy_q8_kernel(coef_ref, *refs):
    # refs = (q_0, ..., q_{n-1}, out); coef_ref = (n, n_tiles) f32 in SMEM
    # holding wₖ · scaleₖ[tile] — the wire decode is FOLDED into the
    # combine: int8 payloads widen to f32 exactly once, already weighted
    # and dequantized, and the mixed bus stores f32.
    o_ref = refs[-1]
    i = pl.program_id(0)
    acc = coef_ref[0, i] * refs[0][...].astype(jnp.float32)
    for k, r in enumerate(refs[1:-1], start=1):
        acc += coef_ref[k, i] * r[...].astype(jnp.float32)
    o_ref[...] = acc


def gossip_axpy_q8_flat(operands, coefs, *, block_rows: int | None = None,
                        interpret: bool = False):
    """Fused dequantize-and-combine  Σₖ wₖ·scaleₖ·qₖ  for int8 wire payloads.

    ``operands`` are (rows, 128) int8 post-permute payloads; ``coefs`` is a
    traced (n, rows // block_rows) f32 array of per-operand per-tile
    ``weight × scale`` products (computed outside: both are tiny).  Output
    is the decoded f32 mix.  Like :func:`gossip_axpy_flat`, the compiled
    kernel is keyed on arity and shape only.
    """
    if block_rows is None:
        block_rows = BLOCK_ROWS
    operands = tuple(operands)
    rows, lane = operands[0].shape
    n_tiles = rows // block_rows
    coefs = jnp.asarray(coefs, jnp.float32).reshape(len(operands), n_tiles)
    assert lane == LANE and rows % block_rows == 0, (operands[0].shape,
                                                     block_rows)
    if not interpret:
        assert block_rows % 32 == 0, block_rows  # int8 min tile (32, 128)
    assert all(o.shape == operands[0].shape and o.dtype == jnp.int8
               for o in operands)
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        _axpy_q8_kernel,
        name="gossip_axpy_q8",
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [spec] * len(operands),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(operands[0].shape, jnp.float32),
        interpret=interpret,
    )(coefs, *operands)


def _consensus_kernel(x_ref, o_ref):
    # x_ref: (A, block_rows, 128), every agent's copy of the same rows.
    # Deviations are taken from agent 0's copy before the mean: the sum of
    # squares is the same, identical copies read exactly 0 for any A, and
    # agents near consensus lose no digits to the rounding of the mean.
    x = x_ref[...]
    d = x - x[:1]
    dev = d - jnp.mean(d, axis=0, keepdims=True)
    sq = jnp.sum(dev * dev, axis=0)                    # (block_rows, 128)
    o_ref[0] = jnp.sum(sq, axis=0, keepdims=True)     # (1, 128) partial


def bus_consensus_flat(bus, *, block_rows: int = BLOCK_ROWS,
                       interpret: bool = False):
    """Per-tile partials of ‖X − X̄‖²_F over an ``(A, rows, 128)`` f32 bus
    with ``rows % block_rows == 0``: one pass, grid ``rows // block_rows``.

    Returns ``(rows // block_rows, 1, 128)`` f32 lane partials (the
    lane-dense block shape the int8 scales use); their sum is the
    consensus distance.  Pad rows are zero in every agent and add 0."""
    A, rows, lane = bus.shape
    assert lane == LANE and rows % block_rows == 0, (bus.shape, block_rows)
    assert bus.dtype == jnp.float32, bus.dtype
    n_tiles = rows // block_rows
    return pl.pallas_call(
        _consensus_kernel,
        name="bus_consensus",
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((A, block_rows, LANE), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((1, 1, LANE), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, 1, LANE), jnp.float32),
        interpret=interpret,
    )(bus)
