"""Mixing engines: apply W to a pytree with a leading agent axis.

Three interchangeable engines (tests assert they agree to float tolerance):

* :func:`mix_dense`    — explicit ``einsum('ij,j...->i...', W, x)``.  Used for
  paper-scale simulation and as the oracle.
* :func:`mix_shifts`   — weighted sum of ``jnp.roll`` terms.  On a sharded
  agent axis XLA lowers every roll to a ``collective-permute``, but the
  schedule is GSPMD's to choose.
* :func:`mix_ppermute` — the production gossip path (DESIGN §3):
  ``shard_map`` + one explicit ``jax.lax.ppermute`` per gossip term, with the
  weighted accumulation optionally fused into a single n-ary Pallas combine
  (:func:`repro.kernels.ops.gossip_axpy`).  Hierarchical topologies decompose
  per term onto the matching mesh sub-axis, so intra-pod permutes never leave
  the pod's ICI domain.  When the topology has more agents than the mesh has
  devices (A = B·M, B > 1) the engine runs *blocked*: each device carries a
  contiguous block of B agents and every roll term decomposes into a local
  shift plus at most two boundary permutes (DESIGN §4).

All engines take one gossip *round* — a :class:`Topology`; time-varying
schedules hand the engines a different round per step through
:func:`make_schedule_mixer` (DESIGN §4).

All engines operate leaf-wise on arbitrary pytrees whose leaves have leading
dim ``A = n_agents``.  The packed parameter bus (DESIGN §5) exploits exactly
this: an ``(A, rows, 128)`` superbuffer is a one-leaf tree, so the ppermute
engine ships ONE payload per gossip term for the whole parameter set
(L·T permutes → T) and the fused combine runs once — no engine changes,
the leaf-count factor just disappears from the wire schedule.

Shard-resident gossip (DESIGN §7): with ``shard_axes`` set, leaf dim 1 (the
bus row axis) is additionally sharded over a pod-internal mesh axis (FSDP).
Gossip is agent-axis-pointwise in the row dim, so every permute stays
**shard-local**: each FSDP shard permutes only its own row block along the
agent axes and combines locally — per-device wire bytes drop by the shard
factor and no all-gather ever feeds a gossip permute.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .topology import Topology

__all__ = ["mix_dense", "mix_shifts", "mix_ppermute", "mix_dense_sharded",
           "make_mixer", "make_schedule_mixer", "make_overlap_mixer",
           "build_mixer", "GroupPlan", "make_group_mixer",
           "accumulate_f32"]


def accumulate_f32(fn):
    """Wrap a tree→tree op so sub-f32 leaves accumulate in f32 and round
    once on the way out.

    The single cast-and-restore helper behind both the dense engine's bf16
    matmul path and the trainer's low-precision gossip payload
    (``RunConfig.gossip_dtype``): inputs are upcast to f32 where they are
    low-precision, ``fn`` runs, and the result is cast back to the input
    leaves' dtypes — so precision is lost exactly once, on the final store.
    """

    def wrapped(tree):
        up = jax.tree.map(
            lambda x: x.astype(jnp.float32)
            if x.dtype in (jnp.bfloat16, jnp.float16) else x, tree)
        out = fn(up)
        return jax.tree.map(lambda o, x: o.astype(x.dtype), out, tree)

    return wrapped


def _mix_leaf_dense(W: jax.Array, x: jax.Array) -> jax.Array:
    # x: (A, ...) -> contract over agent axis (f32 by accumulate_f32).
    flat = x.reshape(x.shape[0], -1)
    return (W.astype(flat.dtype) @ flat).reshape(x.shape)


def mix_dense(topo: Topology, tree: Any) -> Any:
    """Oracle engine: explicit dense W matmul over the agent axis."""
    W = jnp.asarray(topo.dense_matrix(), dtype=jnp.float32)
    return accumulate_f32(
        functools.partial(jax.tree.map, functools.partial(_mix_leaf_dense, W))
    )(tree)


def _is_masked(topo: Topology) -> bool:
    """Liveness-masked round (:class:`repro.core.elastic.MaskedTopology`)?
    Duck-typed on the per-agent weight column API so core.mixing never
    imports core.elastic."""
    return hasattr(topo, "term_weights")


def _local_roll_shifts(topo: Topology):
    """Per-term agent shifts ``s`` (term = ``roll(x, s, axis=0)``) when every
    term of ``topo`` is a cyclic shift of the whole agent axis, else None."""
    idx = np.arange(topo.n_agents)
    shifts = []
    for t in topo.terms:
        s = (idx - topo.term_sources(t)) % topo.n_agents
        if np.any(s != s[0]):
            return None
        shifts.append(int(s[0]))
    return tuple(shifts)


def _masked_tables(topo: Topology):
    """(srcs, wcols) as (T, A) int / f32 numpy tables for a masked round."""
    srcs = np.stack([topo.term_sources(t) for t in topo.terms]).astype(np.int32)
    wcols = np.stack([topo.term_weights(t)
                      for t in topo.terms]).astype(np.float32)
    return srcs, wcols


def _mix_leaf_shifts(topo: Topology, x: jax.Array) -> jax.Array:
    A = x.shape[0]
    assert A == topo.n_agents, (A, topo.n_agents)
    if _is_masked(topo):
        # masked rounds have per-agent sources/weights — gather route
        srcs, wcols = _masked_tables(topo)
        acc = None
        for src, w in zip(srcs, wcols):
            wb = jnp.asarray(w, x.dtype).reshape((A,) + (1,) * (x.ndim - 1))
            term = x[jnp.asarray(src)] * wb
            acc = term if acc is None else acc + term
        return acc
    P, D = topo.grid_shape()
    acc = None
    for t in topo.terms:
        if t.shift == 0 or (t.level == "flat" and A == 1):
            term = x * t.weight
        elif t.level == "flat":
            term = jnp.roll(x, t.shift, axis=0) * t.weight
        else:
            # reshape agent axis to the (P, D) grid; roll the right sub-axis.
            g = x.reshape((P, D) + x.shape[1:])
            axis = 0 if t.level == "inter" else 1
            term = (jnp.roll(g, t.shift, axis=axis) * t.weight).reshape(x.shape)
        acc = term if acc is None else acc + term
    return acc


def mix_shifts(topo: Topology, tree: Any) -> Any:
    """Compiler-scheduled engine: W as a weighted sum of agent-axis rolls
    (→ collective-permute on a sharded mesh, scheduled by GSPMD)."""
    return jax.tree.map(functools.partial(_mix_leaf_shifts, topo), tree)


def _agent_axis_info(topo: Topology, mesh, agent_axes):
    """Resolve agent_axes against the mesh; returns (names, sizes, split, B).

    ``B`` is the number of agents per device (blocked mode when > 1: the
    topology's A agents live as contiguous blocks of B on M = A/B devices).
    ``split`` is True when the topology's (P, D) agent grid maps 1:1 onto two
    mesh sub-axes — then inter/intra terms become single sub-axis ppermutes.
    """
    names = (tuple(agent_axes) if isinstance(agent_axes, (tuple, list))
             else (agent_axes,))
    sizes = tuple(mesh.devices.shape[mesh.axis_names.index(n)] for n in names)
    M = math.prod(sizes)
    assert topo.n_agents % M == 0, \
        f"agent count {topo.n_agents} must be a multiple of the mesh agent " \
        f"extent {M} (axes {names})"
    B = topo.n_agents // M
    assert B == 1 or len(names) == 1, \
        "blocked gossip (agents > devices) needs a single flat agent axis"
    split = (B == 1 and len(names) == 2 and topo.grid is not None
             and sizes == topo.grid_shape())
    return names, sizes, split, B


def _flat_device_index(names, sizes):
    """This shard's flat device index along the agent axes (mixed-radix
    over multi-axis agent meshes; ``lax.axis_index`` takes one name)."""
    idx = jax.lax.axis_index(names[0])
    for n, s in zip(names[1:], sizes[1:]):
        idx = idx * s + jax.lax.axis_index(n)
    return idx


def _blocked_roll(x, shift: int, bloc: int, n_ring: int, n_dev: int,
                  axis_name):
    """Blocked circulant roll: the device-local slice of
    ``roll(x_global, shift)`` where each of ``n_ring`` consecutive devices
    holds ``bloc`` consecutive elements of one ring (rings tile the ``n_dev``
    devices contiguously — one ring per pod, or one global ring).

    Decompose shift = q·bloc + r: local rows [0, bloc−r) come from the
    device q hops back, the r boundary rows from q+1 hops back — at most two
    permutes, and parts whose hop count is ≡ 0 (mod ring) stay local, so a
    sub-block shift ships only its r boundary rows.
    """
    n_elems = bloc * n_ring
    s = shift % n_elems
    if s == 0:
        return x
    q, r = divmod(s, bloc)

    def perm(hops):
        hops %= n_ring
        pairs = []
        for d in range(n_dev):
            g, c = divmod(d, n_ring)
            pairs.append((g * n_ring + (c - hops) % n_ring, d))
        return pairs

    p1 = x[:bloc - r] if r else x
    if q % n_ring:
        p1 = jax.lax.ppermute(p1, axis_name, perm(q))
    if not r:
        return p1
    p2 = x[bloc - r:]
    if (q + 1) % n_ring:
        p2 = jax.lax.ppermute(p2, axis_name, perm(q + 1))
    return jnp.concatenate([p2, p1], axis=0)


def _make_permute_term(topo: Topology, names, sizes, split: bool, B: int):
    """The per-term wire plan of the ppermute engine: returns
    ``permute_term(x, t) -> x_permuted`` for one shard's agent block — the
    single closure behind both the synchronous ``mix_ppermute`` combine and
    the overlap pipeline's issue phase (DESIGN §6), so the two paths cannot
    drift in what they put on the wire."""
    axis_flat = names if len(names) > 1 else names[0]
    A = topo.n_agents
    M = A // B
    Pn, Dn = topo.grid_shape()

    def permute_term_blocked(x, t):
        if t.level == "flat":
            return _blocked_roll(x, t.shift, B, M, M, axis_flat)
        if t.level == "inter":
            # an inter roll by s pods is the flat roll by s·D agents
            return _blocked_roll(x, t.shift * Dn, B, M, M, axis_flat)
        if B % Dn == 0:          # whole pods per device: local roll
            g = x.reshape((B // Dn, Dn) + x.shape[1:])
            return jnp.roll(g, t.shift, axis=1).reshape(x.shape)
        assert Dn % B == 0, \
            f"blocked intra gossip needs pod size {Dn} and block {B} aligned"
        return _blocked_roll(x, t.shift, B, Dn // B, M, axis_flat)

    def permute_term(x, t):
        if t.shift == 0 or A == 1:
            return x
        if B > 1:
            return permute_term_blocked(x, t)
        if split and t.level != "flat":
            ax, size = ((names[0], Pn) if t.level == "inter"
                        else (names[1], Dn))
            if size == 1:
                return x
            perm = [((i - t.shift) % size, i) for i in range(size)]
            return jax.lax.ppermute(x, ax, perm)
        src = topo.term_sources(t)
        perm = [(int(s), d) for d, s in enumerate(src)]
        return jax.lax.ppermute(x, axis_flat, perm)

    return permute_term


def mix_ppermute(topo: Topology, mesh, agent_axes, tree: Any, *,
                 use_fused_kernel: bool = False,
                 interpret: bool | None = None,
                 transport: str = "auto",
                 shard_axes: str | None = None,
                 wire=None) -> Any:
    """Production gossip engine: ``shard_map`` + ``jax.lax.ppermute``.

    The agent axis is *consumed* by the mesh (a block of A/M agents per mesh
    slice along ``agent_axes``); every gossip term becomes at most two
    ppermutes with literal source→target lists, so the communication
    schedule is pinned rather than left to GSPMD's roll lowering.

    * One agent per device (B = 1): each term is one ppermute straight from
      :meth:`Topology.term_sources`; hierarchical topologies decompose onto
      split ``(pod, data)`` mesh axes, or linearize onto one flat axis.
    * Blocked (B > 1, the A > device-count mode): flat and inter terms run
      the blocked-roll decomposition (:func:`_blocked_roll` — local shift +
      boundary permutes, sub-block shifts ship only boundary rows); intra
      terms are fully local when each device holds whole pods, else run the
      blocked roll on the pod's device sub-ring.

    With ``use_fused_kernel=True`` the per-term weighted accumulation runs as
    one n-ary Pallas ``gossip_axpy`` combine per leaf instead of a chain of
    mul/add HBM round-trips (DESIGN §3).  When one device holds every
    agent, each term is a roll of its block, and the combine reads the
    rolls through its index map (:func:`repro.kernels.ops.gossip_axpy_rolled`)
    instead of materializing a rolled copy per term.

    ``transport`` selects the wire mechanism (DESIGN §6 fallback matrix):
    ``"ppermute"`` forces the shard_map + ``lax.ppermute`` path above;
    ``"ring_dma"`` forces the Pallas remote-DMA ring kernel
    (:mod:`repro.kernels.ring_dma` — fuses the permute into the combine so
    payloads never round-trip HBM between the two; flat ±1 rings on a real
    TPU only); ``"auto"`` picks ring_dma when it is supported for this
    topology/mesh/payload, the fused combine was requested AND the
    operator opted in with ``REPRO_RING_DMA=1`` (the kernel follows the
    guide's RDMA pattern but is not yet validated on hardware — auto must
    not silently swap it into a production run), else ppermute.  Off-TPU
    (this container) every selection falls back to ppermute.

    ``shard_axes`` names the mesh axis FSDP-sharding leaf dim 1 (the bus
    row axis, DESIGN §7).  The permutes are unchanged — they run along the
    agent axes only — but each mesh slice now holds ``rows/S`` rows, so
    every permute and the combine operate on the shard's own row block
    (shard-local gossip; the ring_dma transport does not compose with row
    sharding and is excluded).

    ``wire`` (a :class:`repro.core.wire.WireCodec`, DESIGN §9) switches the
    engine to wire-coded payloads: ``tree`` is then the codec's *encoded*
    payload of a single ``(A, rows, 128)`` bus — a bf16 bus, or an
    ``(int8 bus, per-block scales)`` pair — whose components permute
    leaf-wise through the SAME per-term wire plan (scales travel with their
    blocks), and the decode is folded into the combine
    (:func:`repro.kernels.ops.gossip_axpy_wire` when fused, an f32
    decode-then-accumulate chain otherwise).  The result is the decoded f32
    mixed bus; since permutes commute with the elementwise decode, it
    equals the f32 engine applied to ``wire.quantize(bus)`` exactly.  The
    ring_dma transport ships raw f32 blocks and is excluded; a masked
    blocked round (B > 1) falls back to decode-then-gather (correct, but
    the gathered hop is f32 — see the §6 fallback matrix).
    """
    import os

    from jax.sharding import PartitionSpec as P

    names, sizes, split, B = _agent_axis_info(topo, mesh, agent_axes)
    axis_flat = names if len(names) > 1 else names[0]
    A = topo.n_agents
    permute_term = _make_permute_term(topo, names, sizes, split, B)
    if wire is not None and wire.fmt == "f32":
        wire = None     # f32 wire IS the legacy path — byte-identical
    if wire is not None:
        tree = tuple(wire.payload_leaves(tree))
    if shard_axes is not None:
        assert shard_axes not in names, (shard_axes, names)
        assert B == 1, "shard-resident gossip needs one agent per mesh slice"
        for l in jax.tree.leaves(tree):
            assert getattr(l, "ndim", 0) >= 2, \
                "shard_axes shards leaf dim 1 — leaves need >= 2 dims"

    masked = _is_masked(topo)
    assert transport in ("auto", "ppermute", "ring_dma"), transport
    ring_plan = None
    if transport != "ppermute":
        from repro.kernels import ring_dma
        eligible = (shard_axes is None and not masked and wire is None
                    and ring_dma.ring_dma_supported(topo, n_axes=len(names),
                                                    B=B)
                    and all(getattr(l, "ndim", 0) == 3 and l.shape[-1] == 128
                            for l in jax.tree.leaves(tree)))
        if transport == "ring_dma":
            assert eligible, (
                "transport='ring_dma' needs a flat ±1-ring topology, one "
                "agent per device on a single mesh axis, (A, rows, 128) "
                "payloads and a real TPU backend")
        opted_in = os.environ.get("REPRO_RING_DMA", "") == "1"
        if eligible and (transport == "ring_dma"
                         or (use_fused_kernel and opted_in)):
            ring_plan = ring_dma.ring_plan(topo)

    weights = tuple(float(t.weight) for t in topo.terms)
    if masked:
        srcs_np, wcols_np = _masked_tables(topo)
    # every agent on one device: each term is a roll of the local block,
    # which the fused combine reads through its index map (no rolled copy)
    roll_shifts = (_local_roll_shifts(topo)
                   if use_fused_kernel and B == A > 1 and not masked
                   else None)

    def combine(payloads, ws):
        if use_fused_kernel:
            from repro.kernels.ops import gossip_axpy
            return gossip_axpy(payloads, ws, interpret=interpret)
        acc = None
        for w, p in zip(ws, payloads):
            term = w * p
            acc = term if acc is None else acc + term
        return acc

    def masked_gather_mix(x):
        # blocked masked fallback (DESIGN §8): per-agent source maps do not
        # decompose into blocked rolls, so gather the agent axis and index.
        xg = jax.lax.all_gather(x, axis_flat, axis=0, tiled=True)  # (A, ...)
        agents = _flat_device_index(names, sizes) * B + jnp.arange(B)
        acc = None
        for src, w in zip(jnp.asarray(srcs_np), jnp.asarray(wcols_np)):
            wb = w[agents].reshape((B,) + (1,) * (x.ndim - 1))
            term = xg[src[agents]] * wb.astype(x.dtype)
            acc = term if acc is None else acc + term
        return acc

    def body(*leaves):
        # each leaf arrives as (B, *shape) — this shard's agent block
        if ring_plan is not None:
            from repro.kernels import ring_dma
            return tuple(
                ring_dma.ring_combine_shard(x, ring_plan,
                                            axis_name=axis_flat, n_devices=A)
                for x in leaves)
        if masked and B > 1:
            return tuple(masked_gather_mix(x) for x in leaves)
        if masked:
            # B = 1: the permutes come straight from the masked source maps
            # (the generic term_sources branch of the wire plan); only the
            # weights become per-agent — this device's weight column.
            i = _flat_device_index(names, sizes)
            wcols = jnp.asarray(wcols_np)
            ws = [wcols[k, i] for k in range(len(topo.terms))]
            return tuple(
                combine([permute_term(x, t) for t in topo.terms], ws)
                for x in leaves)
        if roll_shifts is not None:
            from repro.kernels.ops import gossip_axpy_rolled
            return tuple(gossip_axpy_rolled(x, roll_shifts, weights,
                                            interpret=interpret)
                         for x in leaves)
        return tuple(combine([permute_term(x, t) for t in topo.terms],
                             weights)
                     for x in leaves)

    def combine_wire(pays, ws):
        # decode folded into the combine: payloads widen to f32 exactly
        # once, already weighted/dequantized (DESIGN §9).
        if use_fused_kernel:
            from repro.kernels.ops import gossip_axpy_wire
            return gossip_axpy_wire(pays, ws, fmt=wire.fmt,
                                    block_rows=wire.block_rows,
                                    interpret=interpret)
        acc = None
        for w, p in zip(ws, pays):
            term = w * wire.decode(p)
            acc = term if acc is None else acc + term
        return acc

    def body_wire(*leaves):
        payload = wire.payload_from_leaves(leaves)
        if masked and B > 1:
            # blocked masked fallback: gather needs per-agent indexing, so
            # decode shard-locally first (that hop ships f32; §6 matrix).
            return (masked_gather_mix(wire.decode(payload)),)
        if masked:
            i = _flat_device_index(names, sizes)
            wcols = jnp.asarray(wcols_np)
            ws = [wcols[k, i] for k in range(len(topo.terms))]
        else:
            ws = weights
        pays = [wire.map_payload(lambda l: permute_term(l, t), payload)
                for t in topo.terms]
        return (combine_wire(pays, ws),)

    spec = P(axis_flat) if shard_axes is None else P(axis_flat, shard_axes)
    if wire is not None:
        specs = tuple(spec for _ in tree)
        (out,) = jax.shard_map(body_wire, mesh=mesh, in_specs=specs,
                               out_specs=(spec,), check_vma=False)(*tree)
        return out

    flat, treedef = jax.tree_util.tree_flatten(tree)
    specs = tuple(spec for _ in flat)
    out = jax.shard_map(body, mesh=mesh, in_specs=specs, out_specs=specs,
                        check_vma=False)(*flat)
    return jax.tree_util.tree_unflatten(treedef, list(out))


def mix_dense_sharded(topo: Topology, mesh, agent_axes, shard_axes,
                      tree: Any) -> Any:
    """Shard-resident dense oracle (DESIGN §7): ``W x`` under the same
    ``P(agent_axes, shard_axes)`` layout the sharded ppermute engine uses.

    Each shard all-gathers its OWN row block along the agent axis only
    (never the shard axis), applies the dense W to the gathered
    ``(A, rows/S, ...)`` stack, and keeps its own agent's result — so the
    oracle stays row-sharded end to end and the sharded equivalence test
    ``mix_ppermute == mix_dense_sharded == mix_dense`` runs under a real
    pods × shards host mesh without materializing a replica.
    """
    from jax.sharding import PartitionSpec as P

    names, _, _, B = _agent_axis_info(topo, mesh, agent_axes)
    assert B == 1, "shard-resident dense oracle needs one agent per slice"
    axis_flat = names if len(names) > 1 else names[0]
    A = topo.n_agents
    W = jnp.asarray(topo.dense_matrix(), dtype=jnp.float32)

    def body(x):
        # x: (1, rows/S, ...) — this agent's row block on this shard
        gathered = jax.lax.all_gather(x[0], axis_flat)   # (A, rows/S, ...)
        flat = gathered.reshape(A, -1).astype(jnp.float32)
        mixed = (W @ flat).reshape(gathered.shape).astype(x.dtype)
        idx = jax.lax.axis_index(axis_flat)
        return jax.lax.dynamic_slice_in_dim(mixed, idx, 1, axis=0)

    spec = P(axis_flat, shard_axes)
    flat, treedef = jax.tree_util.tree_flatten(tree)
    out = [jax.shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec,
                         check_vma=False)(l) for l in flat]
    return jax.tree_util.tree_unflatten(treedef, out)


def make_mixer(topo: Topology, engine: str = "shifts", mesh=None,
               agent_axes=None, use_fused_kernel: bool = False,
               transport: str = "auto", shard_axes: str | None = None,
               wire=None):
    """Return ``mix(tree) -> tree``.  engine ∈ {"dense", "shifts", "ppermute"}.

    ``mesh``/``agent_axes`` are required for (and only used by) the ppermute
    engine; ``use_fused_kernel`` routes its combine through the fused Pallas
    ``gossip_axpy`` kernel, ``transport`` selects its wire mechanism and
    ``shard_axes`` enables shard-resident gossip over FSDP row shards
    (see :func:`mix_ppermute`).

    With ``wire`` (a :class:`repro.core.wire.WireCodec`) the mixer takes the
    codec's *encoded* payload and returns the decoded f32 mix.  Only the
    ppermute engine actually ships wire bytes; dense/shifts decode first and
    mix in f32 — the single-device reference of the identical semantics
    (the engines still agree exactly, payload-in, f32-out).
    """
    if wire is not None and wire.fmt == "f32":
        wire = None
    if engine == "dense":
        base = functools.partial(mix_dense, topo)
        if wire is None:
            return base
        return lambda payload: base(wire.decode(payload))
    if engine == "shifts":
        base = functools.partial(mix_shifts, topo)
        if wire is None:
            return base
        return lambda payload: base(wire.decode(payload))
    if engine == "ppermute":
        assert mesh is not None and agent_axes is not None, \
            "ppermute engine needs mesh= and agent_axes="
        return functools.partial(mix_ppermute, topo, mesh, agent_axes,
                                 use_fused_kernel=use_fused_kernel,
                                 transport=transport, shard_axes=shard_axes,
                                 wire=wire)
    raise ValueError(f"unknown mixing engine: {engine}")


def make_schedule_mixer(sched, engine: str = "shifts", mesh=None,
                        agent_axes=None, use_fused_kernel: bool = False,
                        shard_axes: str | None = None, wire=None):
    """Step-indexed mixer over a :class:`~repro.core.schedule.GossipSchedule`:
    returns ``mix(tree, step=0) -> tree`` applying the schedule's round
    ``step % period`` through the chosen engine.

    Every round gets its own engine closure (its own permute plan / kernel
    arity); a concrete ``step`` dispatches in Python, a traced one through
    ``jax.lax.switch`` — the round index is replicated (it derives from the
    global step), so the branch collectives stay SPMD-consistent.  Period-1
    schedules skip the switch entirely and are bit-identical to the static
    ``make_mixer`` path.

    The step→round map is the schedule's ``round_index`` — plain schedules
    fold the step mod the period; an
    :class:`~repro.core.elastic.ElasticSchedule` additionally selects the
    liveness epoch, so churn rides through here with no engine changes.
    """
    mixers = [make_mixer(r, engine, mesh=mesh, agent_axes=agent_axes,
                         use_fused_kernel=use_fused_kernel,
                         shard_axes=shard_axes, wire=wire)
              for r in sched.rounds]
    if len(mixers) == 1:
        return lambda tree, step=0: mixers[0](tree)

    def mix(tree, step=0):
        r = sched.round_index(step)
        if isinstance(r, (int, np.integer)):
            return mixers[int(r)](tree)
        return jax.lax.switch(r, mixers, tree)

    return mix


def make_overlap_mixer(sched, engine: str = "ppermute", mesh=None,
                       agent_axes=None, use_fused_kernel: bool = False,
                       interpret: bool | None = None,
                       shard_axes: str | None = None, wire=None):
    """Phase-split schedule mixer for the overlapped gossip pipeline
    (DESIGN §6): returns ``(issue, complete)`` such that
    ``complete(issue(x, step), step)`` equals the synchronous
    ``make_schedule_mixer(...)(x, step)`` for a single-array payload ``x``
    (the packed bus).

    ``issue`` runs ONLY the round's collective permutes — no arithmetic —
    and returns a ``(K, A, ...)`` stack of per-term payloads, where
    ``K = max arity over rounds``; shorter rounds pad the stack with the
    unpermuted payload under weight 0, so every round shares one stack
    shape (a traced-step ``lax.switch`` needs that) and one combine kernel
    arity.  ``complete`` runs only the weighted n-ary combine (the fused
    ``gossip_axpy`` when requested).  Everything the caller places between
    the two calls — the backward pass, in the trainer — is
    data-independent of the in-flight permutes, which is exactly the
    window XLA's latency-hiding scheduler uses to take the wire off the
    critical path.

    For the ``dense``/``shifts`` engines there is no separable wire phase:
    ``issue`` is the identity and ``complete`` the full mix, so the delayed
    pipeline's *algorithmic* semantics (gradients at the pre-mix iterate)
    are engine-independent and single-device-testable even though only the
    ppermute engine gains overlap.

    Straggler degradation (DESIGN §8): ``complete(payloads, step, late=)``
    takes an optional ``(K,)`` bool mask of LATE payload slots
    (:meth:`repro.core.elastic.StragglerPlan.late_at`).  A late slot's
    payload is replaced by the round's SELF payload under the slot's
    original weight *before* the combine — the self-weight absorption
    ``W_eff = Σ_{k∉late} w_k P_k + (Σ_{k∈late} w_k) I``, which keeps W_eff
    doubly stochastic with positive diagonal and never reads the late
    (possibly garbage) buffer, so a straggler degrades mixing instead of
    blocking or NaNing the step.  Rounds without an explicit self term use
    a weight-0 pad slot, which always holds the unpermuted (self) payload.
    The dense engine supports ``late`` through an explicit per-term W_eff
    oracle (the straggler tests' reference); shifts has no payload stack
    and rejects it.  ``complete.n_terms`` exposes the stack arity K for
    :class:`~repro.core.elastic.StragglerPlan` validation.

    With ``wire`` (a :class:`repro.core.wire.WireCodec`, DESIGN §9) the
    pipeline composes with the compressed wire: ``issue`` takes the codec's
    *encoded* payload (quantized at issue time, behind the backward pass —
    the residual was split off by the EF encode before the call) and stacks
    each payload component per term; ``complete`` folds the decode into the
    combine and returns the f32 mixed bus.  Late-slot substitution operates
    on the encoded stacks component-wise, so a straggler degrades onto its
    own *quantized* self payload — exactly what it put on the wire.
    """
    if wire is not None and wire.fmt == "f32":
        wire = None
    R = len(sched.rounds)
    K = max(len(r.terms) for r in sched.rounds)

    def self_index(topo):
        si = next((k for k, t in enumerate(topo.terms) if t.shift == 0),
                  len(topo.terms))
        assert si < K, \
            f"{topo.name}: no self term and no pad slot to degrade onto"
        return si

    if engine != "ppermute":
        mix = make_schedule_mixer(sched, engine, mesh=mesh,
                                  agent_axes=agent_axes,
                                  use_fused_kernel=use_fused_kernel,
                                  shard_axes=shard_axes, wire=wire)
        if engine == "dense":
            # per-term dense stacks: Wk = diag(wcol_k) P_k, Ik = diag(wcol_k)
            n = sched.n_agents
            Wk_np = np.zeros((R, K, n, n), np.float32)
            Ik_np = np.zeros((R, K, n, n), np.float32)
            idx = np.arange(n)
            for r, topo in enumerate(sched.rounds):
                for k, t in enumerate(topo.terms):
                    wcol = (topo.term_weights(t) if _is_masked(topo)
                            else np.full(n, t.weight))
                    Wk_np[r, k, idx, topo.term_sources(t)] = wcol
                    Ik_np[r, k, idx, idx] = wcol
            Wk_t, Ik_t = jnp.asarray(Wk_np), jnp.asarray(Ik_np)

        def complete(x, step=0, late=None):
            if late is None:
                return mix(x, step)
            assert engine == "dense", \
                "straggler degradation needs the ppermute or dense engine"
            if wire is not None:
                x = wire.decode(x)
            r = sched.round_index(step)
            lateb = jnp.asarray(late).reshape(K, 1, 1)
            W_eff = jnp.sum(jnp.where(lateb, Ik_t[r], Wk_t[r]), axis=0)
            return accumulate_f32(functools.partial(
                jax.tree.map, functools.partial(_mix_leaf_dense, W_eff)))(x)

        complete.n_terms = K
        return (lambda x, step=0: x), complete

    from jax.sharding import PartitionSpec as P

    assert mesh is not None and agent_axes is not None, \
        "overlap mixer needs mesh= and agent_axes= for the ppermute engine"
    A = sched.n_agents
    any_masked = any(_is_masked(r) for r in sched.rounds)

    names0, _, _, B0 = _agent_axis_info(sched.rounds[0], mesh, agent_axes)
    axis0 = names0 if len(names0) > 1 else names0[0]
    if any_masked:
        assert B0 == 1, \
            "masked overlap gossip needs one agent per mesh slice (B = 1)"

    # weight table: (R, K) replicated normally; per-agent (R, K, A) columns
    # sharded over the agent axis when any round is liveness-masked.
    if any_masked:
        w_np = np.zeros((R, K, A), np.float32)
        for r, topo in enumerate(sched.rounds):
            for k, t in enumerate(topo.terms):
                w_np[r, k] = (topo.term_weights(t) if _is_masked(topo)
                              else t.weight)
        w_spec = P(None, axis0)
    else:
        w_np = np.zeros((R, K), np.float32)
        for r, topo in enumerate(sched.rounds):
            w_np[r, :len(topo.terms)] = [t.weight for t in topo.terms]
        w_spec = P()
    w_table = jnp.asarray(w_np)
    self_np = np.asarray([self_index(r) for r in sched.rounds], np.int32)
    self_t = jnp.asarray(self_np)

    def make_issue(topo):
        names, sizes, split, B = _agent_axis_info(topo, mesh, agent_axes)
        axis_flat = names if len(names) > 1 else names[0]
        if shard_axes is not None:
            assert B == 1, \
                "shard-resident gossip needs one agent per mesh slice"
        permute_term = _make_permute_term(topo, names, sizes, split, B)

        def stack_terms(x):
            pays = [permute_term(x, t) for t in topo.terms]
            pays += [x] * (K - len(pays))   # weight-0 pad to the max arity
            return jnp.stack(pays)

        in_spec = (P(axis_flat) if shard_axes is None
                   else P(axis_flat, shard_axes))
        out_spec = (P(None, axis_flat) if shard_axes is None
                    else P(None, axis_flat, shard_axes))
        if wire is None:
            return jax.shard_map(stack_terms, mesh=mesh, in_specs=(in_spec,),
                                 out_specs=out_spec, check_vma=False)

        # wire-coded issue: stack every payload component per term — the
        # permutes run on the wire dtype, scales ride with their blocks.
        def body_wire(*leaves):
            return tuple(stack_terms(l) for l in leaves)

        nl = 2 if wire.fmt == "int8" else 1
        sm = jax.shard_map(body_wire, mesh=mesh, in_specs=(in_spec,) * nl,
                           out_specs=(out_spec,) * nl, check_vma=False)
        return lambda payload: wire.payload_from_leaves(
            sm(*wire.payload_leaves(payload)))

    issues = [make_issue(r) for r in sched.rounds]

    def issue(x, step=0):
        if R == 1:
            return issues[0](x)
        r = sched.round_index(step)
        if isinstance(r, (int, np.integer)):
            return issues[int(r)](x)
        return jax.lax.switch(r, issues, x)

    def combine_body(w, p):
        # p: (K, B_shard, ...) payload stack for this shard's agent block;
        # w: (K,) replicated round weights, or this agent's (K, 1) column
        # when the schedule carries masked rounds.
        ops = [p[k] for k in range(K)]
        ws = [w[k] if w.ndim == 1 else w[k, 0] for k in range(K)]
        if use_fused_kernel:
            from repro.kernels.ops import gossip_axpy
            return gossip_axpy(ops, ws, interpret=interpret)
        acc = ws[0] * ops[0]
        for k in range(1, K):
            acc = acc + ws[k] * ops[k]
        return acc

    def combine_body_wire(w, *pleaves):
        # pleaves: per-component (K, B_shard, ...) stacks; regroup per term
        # and fold the decode into the weighted combine (DESIGN §9).
        ws = [w[k] if w.ndim == 1 else w[k, 0] for k in range(K)]
        ops = [wire.payload_from_leaves([leaf[k] for leaf in pleaves])
               for k in range(K)]
        if use_fused_kernel:
            from repro.kernels.ops import gossip_axpy_wire
            return gossip_axpy_wire(ops, ws, fmt=wire.fmt,
                                    block_rows=wire.block_rows,
                                    interpret=interpret)
        acc = None
        for wk, op in zip(ws, ops):
            term = wk * wire.decode(op)
            acc = term if acc is None else acc + term
        return acc

    pay_spec = (P(None, axis0) if shard_axes is None
                else P(None, axis0, shard_axes))
    out0 = P(axis0) if shard_axes is None else P(axis0, shard_axes)
    if wire is None:
        combine = jax.shard_map(combine_body, mesh=mesh,
                                in_specs=(w_spec, pay_spec), out_specs=out0,
                                check_vma=False)
    else:
        nl = 2 if wire.fmt == "int8" else 1
        combine_sm = jax.shard_map(combine_body_wire, mesh=mesh,
                                   in_specs=(w_spec,) + (pay_spec,) * nl,
                                   out_specs=out0, check_vma=False)

        def combine(w, payloads):
            return combine_sm(w, *wire.payload_leaves(payloads))

    def complete(payloads, step=0, late=None):
        r = sched.round_index(step)
        if late is not None:
            # substitute late slots with the round's self payload BEFORE
            # the combine — original weights then realize the self-weight
            # absorption W_eff without ever reading the late buffer.  With
            # a wire codec this runs component-wise on the encoded stacks.
            def sub(pay):
                if isinstance(r, (int, np.integer)):
                    selfpay = pay[int(self_np[r])]
                else:
                    selfpay = jnp.take(pay, self_t[r], axis=0)
                lateb = jnp.asarray(late).reshape(
                    (K,) + (1,) * (pay.ndim - 1))
                return jnp.where(lateb, selfpay[None], pay)

            payloads = jax.tree.map(sub, payloads)
        return combine(w_table[r], payloads)

    complete.n_terms = K
    return issue, complete


# ---------------------------------------------------------------------------
# unified mixer factory + policy-group mixer (DESIGN §12)
# ---------------------------------------------------------------------------

def build_mixer(sched, *, mode: str = "schedule", engine: str = "shifts",
                mesh=None, agent_axes=None, use_fused_kernel: bool = False,
                interpret: bool | None = None, transport: str = "auto",
                shard_axes: str | None = None, wire=None):
    """Single mixer entry point over the three construction modes.

    ``mode="static"`` takes one :class:`~repro.core.topology.Topology` (or
    a period-1 schedule) and returns ``mix(tree) -> tree``
    (:func:`make_mixer`); ``mode="schedule"`` takes a
    :class:`~repro.core.schedule.GossipSchedule` (a bare topology is
    wrapped static) and returns ``mix(tree, step=0)``
    (:func:`make_schedule_mixer`); ``mode="overlap"`` returns the
    ``(issue, complete)`` phase-split pair (:func:`make_overlap_mixer`).
    The legacy ``make_*`` names stay as thin aliases of this factory's
    modes — new call sites should come through here.
    """
    rounds = getattr(sched, "rounds", None)
    if mode == "static":
        topo = sched
        if rounds is not None:
            assert len(rounds) == 1, \
                f"mode='static' needs a topology or a period-1 schedule, " \
                f"got period {len(rounds)}"
            topo = rounds[0]
        return make_mixer(topo, engine, mesh=mesh, agent_axes=agent_axes,
                          use_fused_kernel=use_fused_kernel,
                          transport=transport, shard_axes=shard_axes,
                          wire=wire)
    if rounds is None:
        from .schedule import StaticSchedule
        sched = StaticSchedule(sched)
    if mode == "schedule":
        return make_schedule_mixer(sched, engine, mesh=mesh,
                                   agent_axes=agent_axes,
                                   use_fused_kernel=use_fused_kernel,
                                   shard_axes=shard_axes, wire=wire)
    if mode == "overlap":
        return make_overlap_mixer(sched, engine, mesh=mesh,
                                  agent_axes=agent_axes,
                                  use_fused_kernel=use_fused_kernel,
                                  interpret=interpret,
                                  shard_axes=shard_axes, wire=wire)
    raise ValueError(f"unknown mixer mode: {mode!r} "
                     "(expected 'static', 'schedule' or 'overlap')")


@dataclasses.dataclass(frozen=True)
class GroupPlan:
    """One policy group's resolved mixing plan: the layout's
    :class:`~repro.core.bus.BusGroup` (row range + cadence), the group's
    own :class:`~repro.core.schedule.GossipSchedule` (``None`` for a full
    opt-out) and an optional per-group wire codec (stateless
    quantize-on-the-wire; the error-feedback wire stays run-level)."""

    group: Any
    sched: Any = None
    wire: Any = None


def make_group_mixer(plans, *, engine: str = "ppermute", mesh=None,
                     agent_axes=None, use_fused_kernel: bool = False,
                     shard_axes: str | None = None):
    """Group-aware bus mixer (DESIGN §12): ``mix(bus, step=0) -> bus``.

    ``plans`` must cover the full ``(A, rows, 128)`` bus with contiguous
    row ranges.  Each step issues one permute plan per *active* group:

    * ``gossip_every == 0`` (opt-out) groups are pure slices — no mixer is
      ever built for their rows, so they contribute ZERO collectives to
      the lowered HLO (pinned by test);
    * ``gossip_every == k > 1`` groups mix only on steps with
      ``step % k == k-1``, on their own round clock ``step // k`` so the
      skip cadence cannot gcd-alias schedule rounds away; off-steps lower
      through ``lax.cond`` (or a Python branch for concrete steps) and
      ship nothing;
    * every-step groups apply their schedule round at ``step`` directly.

    Each group's sub-mixer sees the group's row slice as a one-leaf tree,
    so it reuses the unmodified engines — per-group schedules, wire
    codecs and masked rounds all compose exactly as on the whole-bus
    path.  The mixed slices are reassembled by row-order concatenation.
    """
    plans = sorted(plans, key=lambda p: p.group.row)
    segments = []  # (row, rows, apply(bus_seg, step) -> seg)
    cursor = 0
    for plan in plans:
        g = plan.group
        assert g.row == cursor, \
            f"group {g.name!r} rows not contiguous: starts at {g.row}, " \
            f"expected {cursor}"
        cursor = g.row + g.rows
        if g.rows == 0:
            continue
        if g.gossip_every == 0 or plan.sched is None:
            segments.append((g.row, g.rows, None))
            continue
        inner = make_schedule_mixer(plan.sched, engine, mesh=mesh,
                                    agent_axes=agent_axes,
                                    use_fused_kernel=use_fused_kernel,
                                    shard_axes=shard_axes, wire=plan.wire)
        k = g.gossip_every
        if k == 1:
            segments.append((g.row, g.rows, inner))
            continue

        def gated(seg, step, inner=inner, k=k):
            gstep = step // k
            if isinstance(step, (int, np.integer)):
                return inner(seg, gstep) if step % k == k - 1 else seg
            return jax.lax.cond(step % k == k - 1,
                                lambda s: inner(s, gstep),
                                lambda s: s, seg)

        segments.append((g.row, g.rows, gated))

    def mix(bus, step=0):
        assert bus.ndim == 3, bus.shape
        assert cursor == bus.shape[1], (cursor, bus.shape)
        out = []
        for row, rows, apply in segments:
            seg = jax.lax.slice_in_dim(bus, row, row + rows, axis=1)
            out.append(seg if apply is None else apply(seg, step))
        return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)

    return mix
