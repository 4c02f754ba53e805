"""Minimal dependency-free checkpointing: pytree ↔ .npz with path keys.

Checkpoint format note (DESIGN §5): checkpoints always store the **logical**
parameter tree — per-leaf arrays under path keys — never the packed bus
buffer.  A bus-resident train state (``RunConfig.packed_bus``) is unpacked
on save and re-packed on load via the ``layout=`` argument, so checkpoints
are interchangeable between bus and tree-resident runs and survive layout
changes (block-row retuning, dtype-policy changes) across restarts.

The overlapped pipeline's state (DESIGN §6) follows the same rule:
:func:`save_state` normalizes the double-buffered ``pipeline`` to its LIVE
payload (``slot[parity]``, stored as a logical tree) plus the parity bit —
the dead slot is never serialized, and :func:`load_state` re-materializes a
``slot[2]`` whose live slot holds φ(t), so a resumed run reproduces the
pipeline trajectory exactly.

Policy groups (DESIGN §12) ride the same contract for free: a grouped
:class:`~repro.core.bus.BusLayout` permutes leaf *rows* inside the bus,
but the save path unpacks to the logical tree before anything touches
disk — so checkpoints written under one group spec load under any other
(1-group → 2-group, regrouped, or back to tree-resident), because
``layout=`` on each side is only that side's row map.  ``_is_bus`` keys
on the layout's total ``rows``, which includes every group's tail pad.
"""
from __future__ import annotations

import json
import os
from typing import Any, Optional, Sequence

import jax
import numpy as np

__all__ = ["save", "load", "save_state", "load_state", "resize_state",
           "load_state_resized", "export_consensus", "load_consensus"]

_SEP = "|"
# .npz stores ml_dtypes leaves (bfloat16, float8, ...) as raw void bytes,
# which load back as dtype ``|V2``; they are written as same-width unsigned
# ints and this entry records the dtype to view them back as.
_DTYPES = "__dtypes__"


def _savez(path: str, arrays: dict) -> None:
    exotic = {k: str(a.dtype) for k, a in arrays.items()
              if a.dtype.kind == "V"}
    stored = {k: a.view(f"u{a.dtype.itemsize}") if k in exotic else a
              for k, a in arrays.items()}
    if exotic:
        stored[_DTYPES] = np.array(json.dumps(exotic))
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    np.savez(path, **stored)


class _Npz:
    """Read side of :func:`_savez`: ``files`` and ``[key]`` like
    ``np.load``, with ml_dtypes leaves viewed back to their dtype."""

    def __init__(self, path: str):
        self._data = np.load(path)
        self.files = [k for k in self._data.files if k != _DTYPES]
        # names resolve once ml_dtypes is imported, which jax does
        self._dtypes = ({k: np.dtype(v) for k, v in
                         json.loads(str(self._data[_DTYPES])).items()}
                        if _DTYPES in self._data.files else {})

    def __getitem__(self, key: str) -> np.ndarray:
        arr = self._data[key]
        dt = self._dtypes.get(key)
        return arr if dt is None else arr.view(dt)


def _flatten(tree: Any):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = _SEP.join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out[key] = np.asarray(leaf)
    return out, treedef


def _flatten_keys(tree: Any):
    """Path keys + leaves without materializing arrays (works on
    ShapeDtypeStructs — ``load`` only needs shapes, not values)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    keys = [_SEP.join(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path) for path, _ in flat]
    return keys, [leaf for _, leaf in flat]


def _is_bus(leaf: Any, layout) -> bool:
    """A leaf is a packed-bus buffer iff it is ``(..A.., rows, 128)``-shaped
    for this layout — anything else (step counters, parity bits) passes
    through the bus translation untouched."""
    from repro.core.bus import LANE
    shape = tuple(getattr(leaf, "shape", ()))
    return len(shape) == 3 and shape[-2:] == (layout.rows, LANE)


def _unbus(tree: Any, layout) -> Any:
    """Expand every (A, rows, 128) bus leaf of ``tree`` into its logical
    subtree (tree may be one bus buffer, or e.g. a {"m","psi"} dict of
    them); non-bus leaves (scalars like ``step``) pass through."""
    from repro.core.bus import unpack_tree
    return jax.tree.map(
        lambda b: unpack_tree(layout, b) if _is_bus(b, layout) else b, tree)


def save(path: str, tree: Any, layout: Optional[Any] = None) -> None:
    """Save ``tree`` as .npz.  ``layout`` marks ``tree``'s bus-shaped array
    leaves as packed-bus buffers (:class:`~repro.core.bus.BusLayout`): they
    are unpacked to the logical tree first, keeping the on-disk format
    layout-independent.

    FSDP-sharded buses (DESIGN §7) serialize like any other state: each
    bus is gathered to host once and unpacked there — the on-disk format
    carries no trace of the
    run's sharding or shard-padded layout, so a checkpoint saved sharded
    loads into a gathered run (or a different shard count) and vice
    versa.

    The tree is pulled to host before the bus translation: unpacking on
    the device would hold a second copy of every bus next to the state,
    which a full-width run on one chip does not have room for."""
    tree = jax.device_get(tree)
    if layout is not None:
        tree = _unbus(tree, layout)
    arrays, _ = _flatten(tree)
    _savez(path, arrays)


def load(path: str, like: Any, layout: Optional[Any] = None) -> Any:
    """Restore into the structure of ``like`` (dtypes/shapes validated).

    With ``layout=``, ``like``'s bus-shaped leaves are packed-bus buffers:
    the checkpoint (stored logical, see :func:`save`) is loaded against the
    unpacked structure and re-packed into bus layout on the way out;
    non-bus leaves load as-is.
    """
    if layout is not None:
        from repro.core.bus import pack_tree
        # structural template only — eval_shape, so no unpack is computed
        template = jax.eval_shape(lambda t: _unbus(t, layout), like)
        logical = load(path, template)
        return jax.tree.map(
            lambda b, sub: pack_tree(layout, sub) if _is_bus(b, layout)
            else sub,
            like, logical,
            is_leaf=lambda x: _is_bus(x, layout))
    data = _Npz(path)
    keys, refs = _flatten_keys(like)
    leaves = []
    for key, ref in zip(keys, refs):
        got = data[key]
        assert got.shape == tuple(ref.shape), (key, got.shape, ref.shape)
        leaves.append(got)
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(like), leaves)


# ---------------------------------------------------------------------------
# full TrainState checkpoints (params + opt + step [+ overlap pipeline])
# ---------------------------------------------------------------------------

def save_state(path: str, state: Any, layout: Optional[Any] = None) -> None:
    """Checkpoint a full trainer ``state`` dict.  Bus-resident slots unpack
    to logical trees per the format note; the overlap ``pipeline`` is
    normalized to ``{"phi": live payload, "parity": bit}`` — the spare slot
    is dead by construction and never hits disk."""
    tree = dict(state)
    pipe = tree.pop("pipeline", None)
    if pipe is not None:
        parity = np.asarray(jax.device_get(pipe["parity"]))
        live = np.asarray(jax.device_get(pipe["slot"]))[int(parity)]
        tree["pipeline"] = {"phi": live, "parity": parity}
    save(path, tree, layout=layout)


def load_state(path: str, like: Any, layout: Optional[Any] = None) -> Any:
    """Restore a full trainer state into the structure of ``like`` (the
    freshly built state of the resuming run).  Pipeline checkpoints carry
    only the live payload: the restored ``slot[2]`` holds φ(t) in BOTH
    slots, so ``slot[parity]`` is correct for any stored parity and the
    first resumed step overwrites the spare exactly as the uninterrupted
    run would.

    Wire-format changes across a restart (DESIGN §9): a checkpoint saved
    by an f32-wire run carries no ``opt["e"]`` residual — resuming it
    under ``wire ∈ {bf16, int8}`` zero-fills the residual, which is the
    EF-correct cold start (e(0) = 0).  The reverse direction (compressed
    → f32) needs nothing: :func:`load` reads only the keys the new state
    asks for, so a stale residual in the file is simply ignored."""
    import jax.numpy as jnp

    like2 = dict(like)
    e_like = None
    opt_like = like2.get("opt")
    if isinstance(opt_like, dict) and "e" in opt_like:
        have = set(_Npz(path).files)
        if not any(k.split(_SEP)[:2] == ["opt", "e"] for k in have):
            opt_like = dict(opt_like)
            e_like = opt_like.pop("e")
            like2["opt"] = opt_like
    pipe_like = like2.pop("pipeline", None)
    if pipe_like is not None:
        slot = pipe_like["slot"]
        like2["pipeline"] = {
            "phi": jax.ShapeDtypeStruct(tuple(slot.shape[1:]), slot.dtype),
            "parity": jax.ShapeDtypeStruct((), jnp.int32),
        }
    tree = load(path, like2, layout=layout)
    if pipe_like is not None:
        pp = tree.pop("pipeline")
        phi = jnp.asarray(pp["phi"])
        tree["pipeline"] = {"slot": jnp.stack([phi, phi]),
                            "parity": jnp.asarray(pp["parity"], jnp.int32)}
    if e_like is not None:
        tree["opt"] = dict(tree["opt"])
        tree["opt"]["e"] = jax.tree.map(
            lambda l: jnp.zeros(tuple(l.shape), l.dtype), e_like)
    return tree


# ---------------------------------------------------------------------------
# train → serve handoff: consensus export (DESIGN §10)
# ---------------------------------------------------------------------------

def export_consensus(src_path: str, dst_path: str) -> None:
    """Export the EDM consensus iterate from a training checkpoint: the
    per-leaf mean over the leading agent axis of every ``params`` leaf,
    written as a single-replica params tree (no agent axis, no opt state).

    Why the mean: the gossip matrix W is doubly stochastic, so the agent
    mean is invariant under mixing and is exactly the consensus target the
    bias-corrected update drives every agent toward (PAPER.md; Momentum
    Tracking, arXiv 2209.15505) — x̄ is *the* trained artifact serving
    should load.

    Why this is sharding-independent: :func:`save` always materializes the
    logical gathered tree — bus-resident, FSDP-sharded (``agents="pod"``)
    and tree-resident runs write byte-identical params leaves — so a
    consensus export from a pod run equals the export from the gathered
    run, and the serving side re-lays it out under whatever
    ``serve_param_specs`` mesh it runs on.

    The reduction runs in float64 and rounds once to the stored dtype, so
    the export is independent of the agent count's summation order."""
    data = _Npz(src_path)
    prefix = "params" + _SEP
    out = {}
    for k in data.files:
        if not k.startswith(prefix):
            continue
        leaf = data[k]
        out[k[len(prefix):]] = (
            leaf.mean(axis=0, dtype=np.float64).astype(leaf.dtype))
    assert out, f"{src_path}: no params leaves to export"
    _savez(dst_path, out)


def load_consensus(path: str, like_params: Any) -> Any:
    """Load a consensus export into the structure of ``like_params`` (a
    single-replica params tree / eval_shape thereof)."""
    return load(path, like_params)


# ---------------------------------------------------------------------------
# elastic join/leave: cross-size state resize (DESIGN §8)
# ---------------------------------------------------------------------------

def resize_state(state: Any, survivors: Sequence[int],
                 n_agents: int) -> Any:
    """Re-shape a trainer state from its saved agent set onto ``n_agents``.

    ``survivors`` selects (in order) which saved agents carry over; their
    rows are taken verbatim, so a shrink — and the A→A identity resize —
    is bit-exact.  When ``n_agents > len(survivors)``, re-admitted agents
    are appended with the join policy that keeps the first resumed step
    exactly the synchronous one for them:

    * ``params`` (x):  the consensus mean over the surviving agents
      (bus zero-pads stay zero under the mean, so the packed layout
      contract is preserved);
    * ``opt["psi"]``:  the new agent's own x row — ψ := x makes the
      bias-corrected payload φ = ψ₂ + x − ψ collapse to ψ₂ at the next
      step, i.e. a joining agent re-enters as if at step 0;
    * every other opt slot (m, trackers, error feedback):  zeros;
    * the overlap ``pipeline`` slots:  the new x row in both buffers
      (φ(0) = x(0), the same seeding :func:`~repro.train.trainer.
      init_state` uses).

    Operates directly on whatever layout the state is in — packed
    ``(A, rows, 128)`` buses and logical per-leaf trees resize the same
    way, along axis 0 (axis 1 for the pipeline's ``slot``).
    """
    import jax.numpy as jnp

    surv = np.asarray(list(survivors), dtype=np.int64)
    m = len(surv)
    assert m <= n_agents, (m, n_agents)
    pad = n_agents - m

    def keep(l, axis=0):
        return jnp.take(jnp.asarray(l), jnp.asarray(surv), axis=axis)

    def grow(kept, fill, axis=0):
        if pad == 0:
            return kept
        reps = [1] * kept.ndim
        reps[axis] = pad
        return jnp.concatenate([kept, jnp.tile(fill, reps)], axis=axis)

    new_params = jax.tree.map(
        lambda l: grow(keep(l), keep(l).mean(axis=0, keepdims=True)),
        state["params"])
    new_opt = {}
    for slot, sub in state.get("opt", {}).items():
        if slot == "psi":
            new_opt[slot] = jax.tree.map(
                lambda l, x: jnp.concatenate([keep(l), x[m:]], axis=0)
                if pad else keep(l), sub, new_params)
        else:
            new_opt[slot] = jax.tree.map(
                lambda l: grow(keep(l),
                               jnp.zeros_like(keep(l)[:1])), sub)
    out = dict(state)
    out["params"] = new_params
    out["opt"] = new_opt
    pipe = state.get("pipeline")
    if pipe is not None:
        slot = jax.tree.map(
            lambda l, x: jnp.concatenate(
                [keep(l, axis=1),
                 jnp.broadcast_to(x[None, m:],
                                  (l.shape[0], pad) + x.shape[1:])],
                axis=1) if pad else keep(l, axis=1),
            pipe["slot"], new_params)
        out["pipeline"] = {"slot": slot, "parity": pipe["parity"]}
    return out


def load_state_resized(path: str, like: Any, layout: Optional[Any] = None,
                       survivors: Optional[Sequence[int]] = None) -> Any:
    """Restore a checkpoint saved at A agents into a run built at A′.

    The saved agent count is read off the checkpoint itself; the state is
    loaded against an A-shaped template (the :class:`~repro.core.bus.
    BusLayout` is agent-count-agnostic, so the SAME ``layout`` serves both
    sizes) and then re-shaped by :func:`resize_state`.  ``survivors``
    defaults to the first ``min(A, A′)`` agents; A′ == A with default
    survivors round-trips bit-identically through :func:`load_state`.
    """
    data = _Npz(path)
    pkeys = [k for k in data.files if k.split(_SEP)[0] == "params"]
    assert pkeys, f"{path}: no params leaves in checkpoint"
    a_old = int(data[pkeys[0]].shape[0])

    def agent_leaves(sub, a):
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((a,) + tuple(l.shape[1:]),
                                           l.dtype), sub)

    a_new = jax.tree.leaves(like["params"])[0].shape[0]
    if a_old == a_new and survivors is None:
        return load_state(path, like, layout=layout)

    like_old = {}
    for k, v in like.items():
        if k == "pipeline":
            slot = v["slot"]
            like_old[k] = {
                "slot": jax.ShapeDtypeStruct(
                    (slot.shape[0], a_old) + tuple(slot.shape[2:]),
                    slot.dtype),
                "parity": v["parity"]}
        elif k in ("params", "opt"):
            like_old[k] = agent_leaves(v, a_old)
        else:
            like_old[k] = v
    state_old = load_state(path, like_old, layout=layout)
    surv = (list(survivors) if survivors is not None
            else list(range(min(a_old, a_new))))
    return resize_state(state_old, surv, a_new)
