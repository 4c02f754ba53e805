"""ParamBus: packed flat-buffer layout for the per-agent parameter set.

The EDM hot loop (DESIGN §5) is launch- and memory-bound when run *per
leaf*: a ~100-leaf transformer pays ~100 Pallas launches per fused update,
~100 `ppermute`s per gossip term, and per-leaf pad-to-tile waste.  The bus
packs the full per-agent pytree — params, grads, m, ψ — into ONE
``(A, rows, 128)`` superbuffer under a **static layout**, so the whole EDM
step runs bus-resident:

* one ``edm_update`` pallas_call over the entire bus (one grid);
* one ``ppermute`` per gossip term and one n-ary ``gossip_axpy`` combine
  per step (the mixing engines already operate leaf-wise over pytrees with
  a leading agent axis — a bus is simply a one-leaf tree);
* ``m``/``ψ`` stay in bus layout across steps (pack once at ``init_state``,
  unpack only for loss/grad and checkpointing).

Layout contract (DESIGN §5):

* lane width is fixed at 128 (:data:`~repro.kernels.edm_update.LANE`);
  every leaf's flattened elements start at an 8-row (8×128-element)
  boundary, so each leaf slot is independently VPU-tile-aligned;
* the buffer's total row count is rounded up to a multiple of
  ``block_rows · shards`` (default: the REPRO_BLOCK_ROWS-tunable kernel
  tile × the FSDP shard count, DESIGN §7) — the single tail pad region;
  all pad elements are zero and stay zero under the EDM update and any
  doubly-stochastic mix (both map 0 → 0), so the pad never contaminates
  logical values;
* shard-resident mode (``shards=S > 1``, DESIGN §7): the row axis is
  meant to be sharded S ways over the pod-internal mesh axis.  The
  rounding above guarantees ``rows % S == 0`` **and** that each shard's
  ``rows/S`` block is itself a whole number of kernel grid tiles, so
  every shard can run the fused kernels and the gossip permutes on its
  own row block without ever gathering;
* dtype policy: the bus carries one storage dtype (default f32); leaves
  are cast on pack and restored to their recorded dtype on unpack.  Any
  sub-f32 leaf (bf16/f16) round-trips losslessly through an f32 bus; a
  bf16 bus is the lossy wire-compression configuration and is only exact
  for bf16 leaves.

Policy groups (DESIGN §12): the bus is no longer one monolithic policy —
it is a small number of named **groups**, each owning a contiguous,
block-aligned row range plus its own gossip policy (schedule name,
``gossip_every`` cadence — 0 opts the group out of gossip entirely — and
wire format).  Leaves are assigned to groups by substring predicates over
their ``|``-joined pytree path (``blocks|0|moe|w_gate``); unmatched leaves
fall into a trailing ``"dense"`` group.  The default (no specs) is a
single ``"dense"`` group spanning the whole buffer whose layout is
bit-identical to the ungrouped layout — pinned by test.

Layouts are static Python objects (hashable, cached) — ``pack_tree`` /
``unpack_tree`` are pure jnp reshuffles, safe to trace under jit, and a
jitted step that closes over a layout never retraces on weight values.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp

__all__ = ["LANE", "BusLayout", "LeafSlot", "GroupSpec", "BusGroup",
           "make_layout", "layout_of", "group_specs_from_json", "leaf_paths",
           "pack_tree", "unpack_tree", "leaf_views", "padded_rows",
           "make_pipeline", "pipeline_payload", "pipeline_advance"]

LANE = 128  # must match repro.kernels.edm_update.LANE
_SUBLANE = 8  # 8×128 VPU tile: every leaf slot starts on an 8-row boundary


def padded_rows(n_elems: int, align: int = _SUBLANE) -> int:
    """Rows of 128 lanes holding ``n_elems``, rounded up to ``align`` rows."""
    rows = -(-n_elems // LANE)
    return -(-rows // align) * align


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Static placement of one pytree leaf inside the bus.

    ``shape``/``dtype`` are the *per-agent* logical leaf (agent axis
    stripped); the leaf occupies rows ``[row, row + rows)`` of the bus,
    elements ``[row·128, row·128 + size)`` of the flattened view.
    """

    row: int
    rows: int
    shape: Tuple[int, ...]
    dtype: Any
    size: int


@dataclasses.dataclass(frozen=True)
class GroupSpec:
    """Declarative gossip policy for one set of leaves (DESIGN §12).

    ``match`` is a tuple of substring patterns tested against each leaf's
    ``|``-joined pytree path (e.g. ``("moe|w_gate",)`` matches every
    expert gate across all blocks); an empty tuple is a catch-all.  A
    callable ``path -> bool`` is also accepted (tests / exotic policies).

    ``gossip_every``: 1 = every gossip round, k > 1 = slow-cycle (the
    group mixes on steps where ``step % k == k-1``, with its own round
    clock ``step // k`` so no schedule round is gcd-aliased away),
    0 = full opt-out (local-only leaves — ships zero wire bytes, pinned
    in HLO).  ``wire``: per-group payload format ("f32"/"bf16"/"int8",
    stateless quantization — the error-feedback wire stays a run-level,
    single-group feature).  ``schedule``: gossip-schedule name override
    ("" inherits the run's schedule).
    """

    name: str
    match: Union[Tuple[str, ...], Callable[[str], bool]] = ()
    gossip_every: int = 1
    wire: str = "f32"
    schedule: str = ""

    def __post_init__(self):
        assert self.gossip_every >= 0, self.gossip_every
        assert self.wire in ("f32", "bf16", "int8"), self.wire
        if not callable(self.match):
            object.__setattr__(self, "match", tuple(self.match))

    def matches(self, path: str) -> bool:
        if callable(self.match):
            return bool(self.match(path))
        return any(p in path for p in self.match) if self.match else True


@dataclasses.dataclass(frozen=True)
class BusGroup:
    """Resolved policy group inside a layout: rows ``[row, row + rows)``
    of the bus, holding the slots indexed by ``slots`` (indices into
    ``layout.slots``), under one gossip policy.  ``rows`` is a whole
    multiple of ``block_rows · shards`` (or 0 if the group matched no
    leaves), so every group is independently griddable and shardable."""

    name: str
    row: int
    rows: int
    slots: Tuple[int, ...]
    gossip_every: int = 1
    wire: str = "f32"
    schedule: str = ""

    @property
    def elems(self) -> int:
        """Padded elements this group ships per agent per permute."""
        return self.rows * LANE


def group_specs_from_json(obj: Any) -> Tuple[GroupSpec, ...]:
    """Build group specs from a parsed ``--gossip-groups`` JSON list:
    ``[{"name": ..., "match": [...], "gossip_every": ..., "wire": ...,
    "schedule": ...}, ...]``.  ``match`` may be one pattern or a list."""
    assert isinstance(obj, (list, tuple)), obj
    specs = []
    for d in obj:
        assert isinstance(d, dict) and "name" in d, d
        match = d.get("match", ())
        if isinstance(match, str):
            match = (match,)
        specs.append(GroupSpec(
            name=str(d["name"]), match=tuple(match),
            gossip_every=int(d.get("gossip_every", 1)),
            wire=str(d.get("wire", "f32")),
            schedule=str(d.get("schedule", ""))))
    return tuple(specs)


@dataclasses.dataclass(frozen=True)
class BusLayout:
    """Static bus layout: where every leaf of the packed tree lives.

    Built from an example tree whose leaves carry a leading agent axis
    ``(A, *shape)``; the layout itself is agent-count-agnostic (``A`` is
    whatever ``pack_tree`` receives), which is why one cached layout backs
    init, the train step and checkpoint restore alike.
    """

    treedef: Any
    slots: Tuple[LeafSlot, ...]
    rows: int                  # total rows incl. tail pad; % (block_rows·shards) == 0
    block_rows: int
    dtype: Any                 # bus storage dtype (f32 default)
    shards: int = 1            # FSDP row-shard count (DESIGN §7)
    groups: Tuple[BusGroup, ...] = ()  # policy groups, contiguous by row

    @property
    def is_grouped(self) -> bool:
        """True when the layout carries a non-trivial policy — more than
        one populated group, or a single group with a non-default policy.
        Ungrouped and trivially-grouped layouts take the legacy (single
        permute plan) mixing path and are bit-identical to it."""
        live = [g for g in self.groups if g.rows]
        if len(live) > 1:
            return True
        return any(g.gossip_every != 1 or g.wire != "f32" or g.schedule
                   for g in live)

    @property
    def shard_rows(self) -> int:
        """Rows each FSDP shard owns (``rows / shards``) — a whole number
        of ``block_rows`` grid tiles by layout construction."""
        assert self.rows % self.shards == 0, (self.rows, self.shards)
        return self.rows // self.shards

    @property
    def logical_elems(self) -> int:
        """Elements that carry data (excludes alignment + tail pad)."""
        return sum(s.size for s in self.slots)

    @property
    def padded_elems(self) -> int:
        """Total bus elements per agent (rows × 128) — what one permute of
        the bus actually ships, and what one kernel pass streams."""
        return self.rows * LANE

    @property
    def pad_waste(self) -> float:
        """Fraction of the bus that is alignment/tail padding."""
        return 1.0 - self.logical_elems / max(self.padded_elems, 1)


def _leaf_signature(tree: Any) -> tuple:
    # per-agent signature: the leading agent axis is stripped, so trees
    # differing only in A hit the same cached layout
    flat, treedef = jax.tree_util.tree_flatten(tree)
    return (treedef, tuple((tuple(l.shape[1:]), jnp.dtype(l.dtype).name)
                           for l in flat))


def _path_str(path) -> str:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        elif hasattr(k, "name"):
            out.append(str(k.name))
        else:
            out.append(str(k))
    return "|".join(out)


def leaf_paths(tree: Any) -> List[str]:
    """``|``-joined pytree path of every leaf, in flatten order — the
    strings :class:`GroupSpec` predicates match against (same separator as
    the checkpoint key flattening)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [_path_str(p) for p, _ in flat]


_LAYOUT_CACHE: dict = {}


def make_layout(tree: Any, *, block_rows: int | None = None,
                dtype: Any = jnp.float32, shards: int = 1,
                groups: Optional[Tuple[GroupSpec, ...]] = None) -> BusLayout:
    """Build (or fetch from cache) the bus layout for ``tree``.

    ``tree`` leaves must be floating arrays (or ShapeDtypeStructs) of shape
    ``(A, *leaf_shape)`` — the leading agent axis is stripped; two trees
    differing only in ``A`` share one layout.  ``block_rows`` defaults to
    the kernel's :data:`~repro.kernels.edm_update.BLOCK_ROWS` so the packed
    buffer is directly griddable by ``edm_update_flat``.  ``shards`` rounds
    each group's rows up to ``block_rows · shards`` so the row axis splits
    evenly into per-shard blocks that are themselves griddable
    (shard-resident gossip, DESIGN §7).

    ``groups`` assigns leaves to policy groups (DESIGN §12): each leaf
    joins the first spec whose predicate matches its path; unmatched
    leaves fall into a trailing default ``"dense"`` group.  Groups occupy
    contiguous row ranges in spec order, each independently rounded to
    the ``block_rows · shards`` quantum.  ``groups=None`` (or a single
    catch-all spec) yields a layout bit-identical to the ungrouped bus.
    """
    from repro.kernels.edm_update import BLOCK_ROWS, LANE as _KERNEL_LANE
    assert _KERNEL_LANE == LANE, (
        "bus layout lane width drifted from the kernel grid", LANE,
        _KERNEL_LANE)
    if block_rows is None:
        block_rows = BLOCK_ROWS
    assert block_rows > 0 and block_rows % _SUBLANE == 0, block_rows
    assert shards >= 1, shards
    flat, treedef = jax.tree_util.tree_flatten(tree)
    assert flat, "cannot build a bus layout for an empty tree"
    specs = tuple(groups) if groups else (GroupSpec("dense"),)
    if not any((not callable(s.match)) and not s.match for s in specs):
        # no catch-all: unmatched leaves gossip normally in "dense"
        specs = specs + (GroupSpec("dense"),)
    names = [s.name for s in specs]
    assert len(set(names)) == len(names), f"duplicate group names: {names}"
    key = (_leaf_signature(tree), block_rows, jnp.dtype(dtype).name, shards,
           specs)
    hit = _LAYOUT_CACHE.get(key)
    if hit is not None:
        return hit
    paths = leaf_paths(tree)
    members: List[List[int]] = [[] for _ in specs]
    for i, path in enumerate(paths):
        for gi, spec in enumerate(specs):
            if spec.matches(path):
                members[gi].append(i)
                break
    quantum = block_rows * shards
    slot_at: List[Optional[LeafSlot]] = [None] * len(flat)
    resolved: List[BusGroup] = []
    base = 0
    for spec, idxs in zip(specs, members):
        row = base
        for i in idxs:
            leaf = flat[i]
            assert leaf.ndim >= 1, "bus leaves need a leading agent axis"
            assert jnp.issubdtype(leaf.dtype, jnp.floating), \
                f"bus packs floating leaves only, got {leaf.dtype}"
            shape = tuple(leaf.shape[1:])
            size = 1
            for s in shape:
                size *= s
            rows = padded_rows(size)
            slot_at[i] = LeafSlot(row, rows, shape, jnp.dtype(leaf.dtype),
                                  size)
            row += rows
        used = row - base
        grows = -(-used // quantum) * quantum if used else 0
        resolved.append(BusGroup(spec.name, base, grows, tuple(idxs),
                                 spec.gossip_every, spec.wire, spec.schedule))
        base += grows
    total = base if base else quantum
    assert all(s is not None for s in slot_at)
    layout = BusLayout(treedef, tuple(slot_at), total, block_rows,
                       jnp.dtype(dtype), shards, tuple(resolved))
    _LAYOUT_CACHE[key] = layout
    return layout


def layout_of(model, n_agents: int, *, block_rows: int | None = None,
              dtype: Any = jnp.float32, shards: int = 1,
              groups: Optional[Tuple[GroupSpec, ...]] = None) -> BusLayout:
    """Layout for a :class:`~repro.models.api.Model`'s parameter tree with
    a leading agent axis — shape-only (``jax.eval_shape``), no allocation."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    lifted = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_agents,) + tuple(s.shape), s.dtype),
        shapes)
    return make_layout(lifted, block_rows=block_rows, dtype=dtype,
                       shards=shards, groups=groups)


def pack_tree(layout: BusLayout, tree: Any) -> jax.Array:
    """Pack ``tree`` (leaves ``(A, *shape)``) into one ``(A, rows, 128)``
    buffer in bus dtype.  Pure jnp; pad elements are zero.  Segments are
    emitted in physical row order (slot rows are not monotone in flatten
    order once the layout is grouped), with zero-fill for every group's
    tail pad.  Each segment is shaped ``(A, slot_rows, 128)`` before the
    row-axis concatenate, so the bus is assembled in its own tiled layout
    — a flat ``(A, rows·128)`` concatenate would need a full-bus relayout
    on TPU before a Pallas kernel could read it."""
    flat = layout.treedef.flatten_up_to(tree)
    assert len(flat) == len(layout.slots)
    A = flat[0].shape[0]
    parts = []
    cursor = 0  # in bus rows
    order = sorted(range(len(flat)), key=lambda i: layout.slots[i].row)
    for i in order:
        leaf, slot = flat[i], layout.slots[i]
        assert leaf.shape == (A,) + slot.shape, (leaf.shape, A, slot.shape)
        gap = slot.row - cursor
        assert gap >= 0, (slot.row, cursor)
        if gap:
            parts.append(jnp.zeros((A, gap, LANE), layout.dtype))
        seg = leaf.reshape(A, slot.size).astype(layout.dtype)
        pad = slot.rows * LANE - slot.size
        if pad:
            seg = jnp.pad(seg, ((0, 0), (0, pad)))
        parts.append(seg.reshape(A, slot.rows, LANE))
        cursor = slot.row + slot.rows
    tail = layout.rows - cursor
    if tail:
        parts.append(jnp.zeros((A, tail, LANE), layout.dtype))
    return jnp.concatenate(parts, axis=1)


def _slot_views(layout: BusLayout, bus: jax.Array):
    """Flat per-slot ``(A, *leaf_shape)`` views of the bus (bus dtype) —
    the single slicing loop behind :func:`unpack_tree` and
    :func:`leaf_views`.  Each slot's rows are sliced from the tiled bus
    first (whole 8-row tiles), so no full-bus relayout is needed.  Plain
    indexing, so a host (numpy) bus unpacks on the host."""
    A, rows, lane = bus.shape
    assert rows == layout.rows and lane == LANE, (bus.shape, layout.rows)
    out = []
    for slot in layout.slots:
        seg = bus[:, slot.row:slot.row + slot.rows].reshape(
            A, slot.rows * LANE)
        out.append(seg[:, :slot.size].reshape((A,) + slot.shape))
    return out


def unpack_tree(layout: BusLayout, bus: jax.Array) -> Any:
    """Inverse of :func:`pack_tree`: restore the logical pytree (per-leaf
    shapes and dtypes) from an ``(A, rows, 128)`` bus buffer."""
    leaves = [v.astype(slot.dtype)
              for v, slot in zip(_slot_views(layout, bus), layout.slots)]
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


# ---------------------------------------------------------------------------
# double-buffered pipeline slots (DESIGN §6)
# ---------------------------------------------------------------------------
#
# The overlapped gossip pipeline carries its in-flight payload in the train
# state: ``slot`` is a (2, A, rows, 128) stack of two bus buffers and
# ``parity`` a replicated int32 bit selecting the LIVE one.  Step t reads
# slot[parity] (its permutes are issued before the backward pass), writes the
# freshly produced payload φ' into slot[1−parity], and flips the bit — so the
# buffer a collective is still reading is never the one the EDM update
# writes, and a donated step aliases both slots in place with no
# write-after-read hazard between the wire and the update.

def make_pipeline(bus: jax.Array) -> dict:
    """Initial pipeline state: ``bus`` (= φ(0) = x(0)) in the live slot,
    zeros in the spare, parity 0."""
    assert bus.ndim == 3 and bus.shape[-1] == LANE, bus.shape
    return {"slot": jnp.stack([bus, jnp.zeros_like(bus)]),
            "parity": jnp.zeros((), jnp.int32)}


def pipeline_payload(pipe: dict) -> jax.Array:
    """The live in-flight payload ``slot[parity]`` — what this step's gossip
    permutes ship (parity is replicated, so the dynamic index is
    SPMD-consistent)."""
    return jax.lax.dynamic_index_in_dim(pipe["slot"], pipe["parity"], axis=0,
                                        keepdims=False)


def pipeline_advance(pipe: dict, phi_new: jax.Array) -> dict:
    """Write the next payload into the spare slot and flip the parity bit.
    The old live slot's contents become dead but stay allocated — that's the
    double buffer."""
    slot = jax.lax.dynamic_update_index_in_dim(pipe["slot"], phi_new,
                                               1 - pipe["parity"], axis=0)
    return {"slot": slot, "parity": 1 - pipe["parity"]}


def leaf_views(layout: BusLayout, bus: jax.Array) -> Any:
    """Per-leaf *bus-dtype* views of the packed buffer, as a pytree matching
    the layout's structure: each view is ``(A, *leaf_shape)`` in the bus
    storage dtype (no cast back — useful for in-layout diagnostics like
    per-leaf norms without a full unpack)."""
    return jax.tree_util.tree_unflatten(layout.treedef,
                                        _slot_views(layout, bus))
