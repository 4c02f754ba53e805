"""Pallas TPU ring-collective gossip: remote-DMA permute fused into the
n-ary combine.

The §3 production path materializes every gossip term: each ``ppermute``
writes its neighbor payload to HBM, then ``gossip_axpy`` streams all of
them back in for the weighted combine — for a 3-term ring that is 2 full
extra HBM round-trips of the bus per step.  This kernel removes them for
the flat ±1 ring (the paper's experimental topology): each device streams
its own bus shard chunk-by-chunk through VMEM, ships each chunk to both
ring neighbors with ``pltpu.make_async_remote_copy`` (the guide's
ring-collective RDMA pattern), and accumulates

    out = w_c · x  +  w_l · x_left  +  w_r · x_right

directly in VMEM as chunks arrive — the permuted payloads never exist in
HBM, and the chunk (c+1) wire transfer overlaps the chunk-c combine.

Buffering/synchronization scheme (double-buffered, ack-gated):

* ``comm[dir, slot]`` — two VMEM landing slots per direction; chunk c
  lands in slot ``c % 2``.
* a chunk's RDMA for both directions is started one iteration ahead of
  its combine (prologue starts chunk 0 and 1), so one transfer is always
  in flight behind the compute;
* before re-using a landing slot (chunk c+2 overwrites chunk c's slot), a
  device must know BOTH neighbors consumed the chunk they received from
  it two iterations ago: after combining chunk c every device acks each
  neighbor on a **per-direction** semaphore (``ack[0]`` counts acks from
  the right neighbor for my dir-0 sends, ``ack[1]`` from the left for my
  dir-1 sends), and ``start(c+2)`` first waits ONE ack on each — by
  induction the cumulative count then proves that specific neighbor
  consumed through chunk c.  A single shared counter could not attribute
  acks to a neighbor (a fast right neighbor's two acks would unblock a
  send into the slow left neighbor's busy slot — the classic 2-slot ring
  race);
* a barrier semaphore handshake with both neighbors runs once at kernel
  entry so no device issues an RDMA into a peer that has not yet entered
  the kernel.

This is TPU-only by construction (remote DMA does not exist off-TPU and
is not interpretable on CPU): :func:`ring_dma_supported` returns False
unless the backend is a real TPU, and ``core/mixing.py`` then falls back
to the shard_map + ``ppermute`` + ``gossip_axpy`` path, which this kernel
is pinned against (same math, :func:`ring_combine_reference`).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .edm_update import BLOCK_ROWS, LANE

__all__ = ["ring_plan", "ring_dma_supported", "ring_combine_shard",
           "ring_combine_reference", "on_tpu"]


def on_tpu() -> bool:
    """Real-TPU check: remote DMA has no CPU interpret path."""
    return jax.default_backend() == "tpu"


def ring_plan(topo) -> Optional[Tuple[float, float, float]]:
    """Collapse ``topo``'s shift terms into ring-combine weights
    ``(w_center, w_from_left, w_from_right)`` — or None when the topology
    is not a flat ±1 ring (any grid-level term or a longer-range shift
    disqualifies it; the shifts are normalized mod n, so n−1 ≡ −1).

    Roll semantics map shifts to wire directions: a ``+1`` term is
    ``x_new[i] = x[i−1]`` — device i *receives from its left neighbor* —
    and ``−1`` receives from the right.
    """
    n = topo.n_agents
    w = {0: 0.0, 1: 0.0, -1: 0.0}
    for t in topo.terms:
        if t.level != "flat":
            return None
        s = t.shift % n
        if s == 0:
            w[0] += t.weight
        elif s == 1:
            w[1] += t.weight
        elif s == n - 1:
            w[-1] += t.weight
        else:
            return None
    return (float(w[0]), float(w[1]), float(w[-1]))


def ring_dma_supported(topo, *, n_axes: int = 1, B: int = 1,
                       backend: Optional[str] = None) -> bool:
    """True iff the remote-DMA ring kernel can carry ``topo``'s gossip:
    flat ±1 ring, one agent per device (B = 1) on a single flat mesh axis,
    ≥ 2 devices, and a real TPU backend (see module docstring — off-TPU
    the engine falls back to ppermute)."""
    if backend is None:
        backend = jax.default_backend()
    return (backend == "tpu" and n_axes == 1 and B == 1
            and topo.n_agents >= 2 and ring_plan(topo) is not None)


def ring_combine_reference(x, plan, axis_name: str):
    """jnp oracle for one shard (inside shard_map): the same combine via
    ``lax.ppermute`` — the fallback path and the kernel's allclose target."""
    w_c, w_l, w_r = plan
    n = jax.lax.psum(1, axis_name)
    from_left = jax.lax.ppermute(
        x, axis_name, [((d - 1) % n, d) for d in range(n)])
    from_right = jax.lax.ppermute(
        x, axis_name, [((d + 1) % n, d) for d in range(n)])
    return w_c * x + w_l * from_left + w_r * from_right


# ---------------------------------------------------------------------------
# the kernel (TPU only — pragma: no cover in this CPU container)
# ---------------------------------------------------------------------------

def _ring_kernel(w_ref, x_ref, o_ref, xbuf, obuf, comm, load_sem, store_sem,
                 send_sem, recv_sem, ack_sem, *, axis_name: str, n_dev: int,
                 n_chunks: int, chunk_rows: int):  # pragma: no cover - TPU
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    my = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(my + 1, n_dev)
    left = jax.lax.rem(my + n_dev - 1, n_dev)

    # entry barrier: both neighbors are inside the kernel before any RDMA
    # may land in their comm buffers.
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, inc=1, device_id=(left,),
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_signal(barrier, inc=1, device_id=(right,),
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(barrier, 2)

    def load(c):
        """HBM → VMEM staging of my chunk c (src of both outgoing RDMAs)."""
        slot = jax.lax.rem(c, 2)
        cp = pltpu.make_async_copy(
            x_ref.at[pl.ds(c * chunk_rows, chunk_rows), :],
            xbuf.at[slot], load_sem.at[slot])
        cp.start()
        cp.wait()

    def start(c):
        """Ship my staged chunk c to both neighbors' landing slots."""
        slot = jax.lax.rem(c, 2)
        # to my right neighbor, landing as THEIR from-left payload (dir 0)
        pltpu.make_async_remote_copy(
            src_ref=xbuf.at[slot], dst_ref=comm.at[0, slot],
            send_sem=send_sem.at[0, slot], recv_sem=recv_sem.at[0, slot],
            device_id=(right,),
            device_id_type=pltpu.DeviceIdType.LOGICAL).start()
        # to my left neighbor, landing as THEIR from-right payload (dir 1)
        pltpu.make_async_remote_copy(
            src_ref=xbuf.at[slot], dst_ref=comm.at[1, slot],
            send_sem=send_sem.at[1, slot], recv_sem=recv_sem.at[1, slot],
            device_id=(left,),
            device_id_type=pltpu.DeviceIdType.LOGICAL).start()

    load(0)
    start(0)

    @pl.when(n_chunks > 1)
    def _():
        load(1)
        start(1)

    def body(c, _):
        slot = jax.lax.rem(c, 2)
        # my outgoing chunk c left the staging buffer, and both neighbor
        # payloads of chunk c have landed (SPMD symmetry: my recv_sem is
        # signaled by the matching remote sends of my neighbors).
        pltpu.semaphore_wait(send_sem.at[0, slot], 1)
        pltpu.semaphore_wait(send_sem.at[1, slot], 1)
        pltpu.semaphore_wait(recv_sem.at[0, slot], 1)
        pltpu.semaphore_wait(recv_sem.at[1, slot], 1)
        acc = (w_ref[0, 0] * xbuf[slot].astype(jnp.float32)
               + w_ref[0, 1] * comm[0, slot].astype(jnp.float32)
               + w_ref[0, 2] * comm[1, slot].astype(jnp.float32))
        obuf[slot] = acc.astype(obuf.dtype)
        st = pltpu.make_async_copy(
            obuf.at[slot], o_ref.at[pl.ds(c * chunk_rows, chunk_rows), :],
            store_sem.at[slot])
        st.start()
        # tell each neighbor its chunk c landed AND was consumed — my
        # landing slot c%2 for that direction is free for its chunk c+2.
        # My comm[0] receives the LEFT neighbor's dir-0 sends → ack its
        # ack[0]; my comm[1] receives the RIGHT neighbor's dir-1 sends.
        pltpu.semaphore_signal(ack_sem.at[0], inc=1, device_id=(left,),
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_signal(ack_sem.at[1], inc=1, device_id=(right,),
                               device_id_type=pltpu.DeviceIdType.LOGICAL)

        @pl.when(c + 2 < n_chunks)
        def _():
            # EACH neighbor must have consumed chunk c before chunk c+2
            # may overwrite its slot c%2: one ack per direction here makes
            # the cumulative per-direction count c+1 = chunks 0..c — and
            # my own staging / output slots must have drained.
            pltpu.semaphore_wait(ack_sem.at[0], 1)
            pltpu.semaphore_wait(ack_sem.at[1], 1)
            pltpu.semaphore_wait(store_sem.at[slot], 1)
            load(c + 2)
            start(c + 2)
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)
    # final drain: every chunk acked by both neighbors (semaphores must end
    # at zero across pallas_calls sharing a collective_id); stores done.
    pltpu.semaphore_wait(ack_sem.at[0], min(2, n_chunks))
    pltpu.semaphore_wait(ack_sem.at[1], min(2, n_chunks))
    pltpu.semaphore_wait(store_sem.at[jax.lax.rem(n_chunks - 1, 2)], 1)

    @pl.when(n_chunks > 1)
    def _():
        pltpu.semaphore_wait(store_sem.at[jax.lax.rem(n_chunks, 2)], 1)


def ring_combine_shard(x, plan, *, axis_name: str, n_devices: int,
                       chunk_rows: int | None = None,
                       collective_id: int = 7):
    """Fused permute+combine of one bus shard — call INSIDE a shard_map
    body whose mesh axis ``axis_name`` carries one agent per device.

    ``x``: this shard's ``(1, rows, 128)`` (or ``(rows, 128)``) bus block;
    ``plan``: :func:`ring_plan` weights.  Returns the combined shard with
    the same shape.  TPU only (:func:`ring_dma_supported`).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w_c, w_l, w_r = plan
    lead = x.ndim == 3
    xs = x.reshape(x.shape[-2:]) if lead else x
    rows, lane = xs.shape
    assert lane == LANE, xs.shape
    if chunk_rows is None:
        # largest divisor of rows that fits the kernel tile budget: both
        # rows (bus layout contract) and BLOCK_ROWS are multiples of 8, so
        # gcd >= 8 always divides rows — a retuned REPRO_BLOCK_ROWS can
        # never strand the transport on a valid bus.
        chunk_rows = math.gcd(rows, BLOCK_ROWS)
    assert chunk_rows % 8 == 0 and rows % chunk_rows == 0, (rows, chunk_rows)
    n_chunks = rows // chunk_rows
    w = jnp.asarray([[w_c, w_l, w_r]], jnp.float32)

    out = pl.pallas_call(  # pragma: no cover - requires TPU
        functools.partial(_ring_kernel, axis_name=axis_name,
                          n_dev=n_devices, n_chunks=n_chunks,
                          chunk_rows=chunk_rows),
        name="ring_dma_combine",
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.ANY),
        out_shape=jax.ShapeDtypeStruct((rows, lane), xs.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, chunk_rows, lane), xs.dtype),   # xbuf staging
            pltpu.VMEM((2, chunk_rows, lane), xs.dtype),   # obuf staging
            pltpu.VMEM((2, 2, chunk_rows, lane), xs.dtype),  # comm[dir,slot]
            pltpu.SemaphoreType.DMA((2,)),                 # load_sem
            pltpu.SemaphoreType.DMA((2,)),                 # store_sem
            pltpu.SemaphoreType.DMA((2, 2)),               # send_sem
            pltpu.SemaphoreType.DMA((2, 2)),               # recv_sem
            pltpu.SemaphoreType.REGULAR((2,)),             # ack_sem per dir
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id, has_side_effects=True),
    )(w, xs)
    return out.reshape(x.shape) if lead else out
