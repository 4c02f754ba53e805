"""Mixture-of-Experts FFN: top-k router, capacity-based sort dispatch,
optional shared experts (DeepSeekMoE-style fine-grained configuration),
and a dropless expert-share layer that computes only the experts it holds
with a grouped matmul (:func:`apply_moe_dropless`, DESIGN §14).

Dispatch is gather/scatter-based (static shapes, no (T, E, C) one-hot tensor)
so that compiled FLOPs ≈ active FLOPs — this is what makes the MoE rooflines
honest.  Experts are sharded over the 'model' mesh axis (expert parallelism);
GSPMD inserts the dispatch all-to-alls.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from repro.kernels import ops as kops
from .layers import dense_init, rms_norm, swiglu

__all__ = ["init_moe", "apply_moe", "apply_moe_dropless", "set_moe_mesh",
           "EXPERT_LEAF_PATTERNS", "expert_group_spec"]

# pytree-path patterns of the per-expert weights (leading expert dim E,
# sharded over the expert-parallel axis).  The router, the MoE layernorm
# and the shared experts are replicated and gossip with the dense group —
# "moe|w_gate" does NOT match "moe|shared|w_gate".
EXPERT_LEAF_PATTERNS = ("moe|w_gate", "moe|w_up", "moe|w_down")


def expert_group_spec(gossip_every: int = 0, wire: str = "f32",
                      schedule: str = ""):
    """Policy-group spec for the expert weights (DESIGN §12).

    Expert-parallel fleets keep expert shards resident per pod — the
    default ``gossip_every=0`` opts them out of gossip entirely (each
    pod's experts specialize on its data); ``gossip_every=k`` slow-cycles
    them instead, optionally at a cheaper ``wire`` format or on their own
    ``schedule``.  Pass through ``RunConfig.gossip_groups="moe[:k]"``.
    """
    from repro.core.bus import GroupSpec
    return GroupSpec("experts", EXPERT_LEAF_PATTERNS,
                     gossip_every=gossip_every, wire=wire, schedule=schedule)

# §Perf lever: when a mesh is registered, the dispatch/combine buffers get
# explicit sharding constraints; with impl="shard_map" the whole MoE FFN runs
# as a manually-sharded layer (expert-local dispatch + one activation psum —
# see apply_moe_shard_map).  Enabled by the dry-run / launcher via
# ``set_moe_mesh(mesh, impl=...)``; None = let GSPMD decide.
_MESH = {"mesh": None, "impl": "gspmd"}


def set_moe_mesh(mesh, impl: str = "gspmd") -> None:
    _MESH["mesh"] = mesh
    _MESH["impl"] = impl


def _constrain(x, *spec):
    mesh = _MESH["mesh"]
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*spec)))


def init_moe(key, cfg) -> Dict:
    """The router over all ``n_experts``; the weights of the held ones."""
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.d_ff
    E_held = cfg.experts_held
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "ln": jnp.zeros((d,), dt),
        "router": dense_init(ks[0], (d, E), 0, jnp.float32),
        "w_gate": dense_init(ks[1], (E_held, d, ff), 1, dt),
        "w_up": dense_init(ks[2], (E_held, d, ff), 1, dt),
        "w_down": dense_init(ks[3], (E_held, ff, d), 1, dt),
    }
    if cfg.n_shared_experts:
        sff = cfg.n_shared_experts * ff
        p["shared"] = {
            "w_gate": dense_init(ks[4], (d, sff), 0, dt),
            "w_up": dense_init(ks[5], (d, sff), 0, dt),
            "w_down": dense_init(jax.random.fold_in(key, 7), (sff, d), 0, dt),
        }
    return p


def _route(logits: jax.Array, k: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing with softmax-renormalized weights.

    Returns (weights (T,k) f32, expert_idx (T,k) i32, aux_loss scalar)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)   # (T, E)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.clip(jnp.sum(w, -1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss
    E = logits.shape[-1]
    density = jnp.mean(jax.nn.one_hot(idx[:, 0], E), axis=0)
    prob_density = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(density * prob_density)
    return w, idx, aux


def _dispatch_compute_combine(flat, w, idx, keep_extra, wg, wu, wd, C):
    """Sort-based capacity dispatch + expert FFN + weighted combine.

    flat: (T, d); w/idx: (T, k) routing; keep_extra: (T*k,) ownership mask
    (True = this shard serves the assignment); experts wg/wu/wd: (E_l, d, f).
    Returns (T, d) combined output (zeros at unserved assignments)."""
    T, d = flat.shape
    E_l = wg.shape[0]
    k = idx.shape[1]
    e_flat = idx.reshape(-1)
    w_flat = w.reshape(-1)
    order = jnp.argsort(e_flat)
    e_sorted = e_flat[order]
    tok_sorted = order // k
    own_sorted = keep_extra[order]
    counts = jnp.bincount(jnp.where(keep_extra, e_flat, E_l), length=E_l + 1)
    starts = jnp.cumsum(counts) - counts
    # rank within owned assignments of each expert
    owned_before = jnp.cumsum(own_sorted.astype(jnp.int32)) - own_sorted
    rank = owned_before - starts[jnp.clip(e_sorted, 0, E_l)]
    keep = own_sorted & (rank < C) & (e_sorted < E_l)
    slot = jnp.clip(e_sorted, 0, E_l - 1) * C + jnp.clip(rank, 0, C - 1)

    buf = jnp.zeros((E_l * C, d), flat.dtype)
    buf = buf.at[slot].add(jnp.where(keep[:, None], flat[tok_sorted], 0))
    buf = buf.reshape(E_l, C, d)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    out_buf = jnp.einsum("ecf,efd->ecd", g * u, wd).reshape(E_l * C, d)
    gathered = out_buf[slot] * (w_flat[order] * keep)[:, None].astype(flat.dtype)
    return jnp.zeros((T, d), flat.dtype).at[tok_sorted].add(gathered)


def apply_moe_shard_map(p: Dict, cfg, x: jax.Array, eps: float, mesh):
    """Manually-sharded MoE FFN (§Perf, serving path).

    Insight: in our TP scheme the FFN input is replicated across the 'model'
    axis, so each model shard already holds every token of its data shard —
    dispatch to the shard's *own* E/16 experts is purely local, and the
    combine is ONE activation-sized psum over 'model' (identical cost to a
    dense row-parallel FFN).  No dispatch all-reduce, no all-to-all.
    """
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    T = B * S
    h = rms_norm(x, p["ln"], eps)
    flat = h.reshape(T, d)
    dp = 1
    if "data" in mesh.axis_names:
        dp = mesh.devices.shape[mesh.axis_names.index("data")]
    use_dp = dp > 1 and T % dp == 0
    T_l = T // dp if use_dp else T
    C = max(8, int(cfg.capacity_factor * T_l * k / E))

    def body(flat_l, router, wg, wu, wd):
        E_l = wg.shape[0]
        shard = jax.lax.axis_index("model")
        w, idx, aux = _route(flat_l @ router.astype(flat_l.dtype), k)
        # ownership: assignment handled here iff its expert lives on this shard
        e_flat = idx.reshape(-1)
        local = (e_flat >= shard * E_l) & (e_flat < (shard + 1) * E_l)
        idx_local = jnp.where(local.reshape(idx.shape), idx - shard * E_l, E_l)
        out = _dispatch_compute_combine(flat_l, w, idx_local, local, wg, wu,
                                        wd, C)
        out = jax.lax.psum(out, "model")
        if use_dp:
            aux = jax.lax.pmean(aux, "data")
        return out, aux

    specs_w = P("model", None, None)
    d_ax = "data" if use_dp else None
    out_flat, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(d_ax, None), P(None, None), specs_w, specs_w, specs_w),
        out_specs=(P(d_ax, None), P()), check_vma=False,
    )(flat, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    y = out_flat.reshape(B, S, d)
    if "shared" in p:
        sp = p["shared"]
        y = y + swiglu(h, sp["w_gate"], sp["w_up"], sp["w_down"])
    return x + y, cfg.router_aux_coef * aux


def apply_moe(p: Dict, cfg, x: jax.Array, eps: float) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) → (B, S, d), aux_loss.

    Capacity-based dispatch:  T*k assignments are sorted by expert id,
    ranked within each expert, and tokens beyond capacity C are dropped
    (their combine weight is zeroed) — Switch/GShard semantics.  A
    ``moe_dropless`` configuration takes :func:`apply_moe_dropless`.
    """
    if cfg.moe_dropless:
        y, aux, _ = apply_moe_dropless(p, cfg, x, eps)
        return y, aux
    if _MESH["impl"] == "shard_map" and _MESH["mesh"] is not None:
        return apply_moe_shard_map(p, cfg, x, eps, _MESH["mesh"])
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    T = B * S
    C = max(8, int(cfg.capacity_factor * T * k / E))
    h = rms_norm(x, p["ln"], eps)
    flat = h.reshape(T, d)

    w, idx, aux = _route(flat @ p["router"].astype(flat.dtype), k)

    # ---- dispatch --------------------------------------------------------
    e_flat = idx.reshape(-1)                       # (T*k,) expert ids
    w_flat = w.reshape(-1)
    order = jnp.argsort(e_flat)                    # stable ascending experts
    e_sorted = e_flat[order]
    tok_sorted = order // k                        # source token of each slot
    # rank within expert: position among same-expert entries
    counts = jnp.bincount(e_flat, length=E)       # tokens per expert
    starts = jnp.cumsum(counts) - counts           # offset of each expert group
    rank = jnp.arange(T * k) - starts[e_sorted]
    keep = rank < C
    slot = e_sorted * C + jnp.clip(rank, 0, C - 1)  # (T*k,) buffer slot

    buf = jnp.zeros((E * C, d), flat.dtype)
    buf = buf.at[slot].add(jnp.where(keep[:, None], flat[tok_sorted], 0))
    buf = _constrain(buf.reshape(E, C, d), "model", None, None)

    # ---- expert compute (batched over E; sharded over 'model') -----------
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"])
    out_buf = _constrain(out_buf, "model", None, None).reshape(E * C, d)

    # ---- combine ---------------------------------------------------------
    gathered = out_buf[slot] * (w_flat[order] * keep)[:, None].astype(flat.dtype)
    combined = jnp.zeros((T, d), flat.dtype).at[tok_sorted].add(gathered)
    combined = _constrain(combined, "data", None)

    y = combined.reshape(B, S, d)
    if "shared" in p:
        sp = p["shared"]
        y = y + swiglu(h, sp["w_gate"], sp["w_up"], sp["w_down"])
    return x + y, cfg.router_aux_coef * aux


# ---------------------------------------------------------------------------
# dropless expert share (DESIGN §14)
# ---------------------------------------------------------------------------

GMM_ROWS = 128       # m tile of the grouped matmul: a group's last tile is
                     # computed whole, so small tiles waste fewest rows


def _gmm_tiling(m: int, k: int, n: int):
    """(m, k, n) tiles of a grouped matmul: the whole contraction up to
    2048 wide, so consecutive row tiles of one group reuse the expert's
    weight tile without reading it again."""
    return (GMM_ROWS, min(k, 2048), min(n, 512))


def _gmm(lhs, rhs, group_sizes):
    """Rows of group g of ``lhs`` times ``rhs[g]`` for the groups ``rhs``
    holds (the first ``rhs.shape[0]`` of ``group_sizes``); rows of the
    other groups are zero and cost nothing.  A Pallas kernel (megablox),
    interpreted off the TPU."""
    return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, _gmm_tiling, None,
                        None, False, not kops._on_tpu())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, src, inv, k):
    """Rows ``x[src]`` (M, d), where ``src = order // k`` lists each
    assignment's token in sorted order.  The backward gathers too: token t's
    cotangent sums the rows of its k assignments, found through ``inv``
    (sorted position of assignment t·k + j), instead of a scatter-add."""
    return x[src]


def _dispatch_fwd(x, src, inv, k):
    return x[src], (inv, x.shape[0])


def _dispatch_bwd(k, res, g):
    inv, T = res
    return (g[inv[:T * k]].reshape(T, k, -1).sum(1), None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _permute_rows(y, perm, inv):
    """``y[perm]`` for a permutation ``perm`` whose inverse is ``inv``: the
    backward is the gather ``g[inv]``."""
    return y[perm]


def _permute_rows_fwd(y, perm, inv):
    return y[perm], inv


def _permute_rows_bwd(inv, g):
    return g[inv], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def apply_moe_dropless(p: Dict, cfg, x: jax.Array,
                       eps: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, d) → (x + y, aux_loss, rows held).

    The router scores every token over all ``n_experts`` (softmax of an f32
    product) and keeps its top ``experts_per_token``; the gate weights are
    those scores, renormalised only where ``norm_topk_prob``, times
    ``routed_scaling_factor``.  This layer holds experts [expert_first,
    expert_first + experts_held): every assignment to one of them is
    computed, none dropped, and the rest are left to the chips that hold
    those experts (on one chip, to no one).  The assignments are sorted so
    that the held experts' come first, grouped by expert; three grouped
    matmuls compute only those rows; the gate-weighted rows are gathered
    back to their tokens.  Shared experts see every token.

    Scopes: ``moe_route`` (router, top-k, auxiliary loss), ``moe_dispatch``
    (sort, gather into groups, weighted gather back), ``moe_experts`` (the
    grouped matmuls), ``moe_shared``.
    """
    B, S, d = x.shape
    k, E_held = cfg.experts_per_token, cfg.experts_held
    T = B * S
    h = rms_norm(x, p["ln"], eps)
    flat = h.reshape(T, d)

    with jax.named_scope("moe_route"):
        scores = jax.nn.softmax(jnp.matmul(
            flat.astype(jnp.float32), p["router"],
            precision=jax.lax.Precision.HIGHEST), axis=-1)      # (T, E)
        w, idx = jax.lax.top_k(scores, k)
        if cfg.norm_topk_prob:
            w = w / jnp.clip(jnp.sum(w, -1, keepdims=True), 1e-9)
        w = w * cfg.routed_scaling_factor
        aux = _aux_loss(cfg, scores, idx, B, S)

    with jax.named_scope("moe_dispatch"):
        M = -(-T * k // GMM_ROWS) * GMM_ROWS         # assignments, padded
        local = idx.reshape(-1) - cfg.expert_first
        held = (local >= 0) & (local < E_held)
        # group key: the held expert's local id, E_held for the rest (and
        # for the padding rows, which carry token 0 at weight 0)
        key = jnp.full((M,), E_held, jnp.int32).at[:T * k].set(
            jnp.where(held, local, E_held))
        order = jnp.argsort(key, stable=True)
        inv = jnp.zeros((M,), jnp.int32).at[order].set(
            jnp.arange(M, dtype=jnp.int32))
        group_sizes = jnp.bincount(key, length=E_held + 1).astype(jnp.int32)
        rows = jnp.sum(group_sizes[:E_held])
        src = jnp.where(order < T * k, order // k, 0)
        xs = _dispatch(flat, src, inv, k)

    with jax.named_scope("moe_experts"):
        g = jax.nn.silu(_gmm(xs, p["w_gate"], group_sizes))
        u = _gmm(xs, p["w_up"], group_sizes)
        ys = _gmm(g * u, p["w_down"], group_sizes)

    with jax.named_scope("moe_dispatch"):
        gate = jnp.where(held, w.reshape(-1), 0.0).reshape(T, k, 1)
        y = jnp.sum(_permute_rows(ys, inv, order)[:T * k].reshape(T, k, d)
                    .astype(jnp.float32) * gate, axis=1).astype(x.dtype)

    y = y.reshape(B, S, d)
    if "shared" in p:
        with jax.named_scope("moe_shared"):
            sp = p["shared"]
            y = y + swiglu(h, sp["w_gate"], sp["w_up"], sp["w_down"])
    return x + y, cfg.router_aux_coef * aux, rows


def _aux_loss(cfg, scores, idx, B: int, S: int) -> jax.Array:
    """The load-balance loss over all experts, before its coefficient.
    ``seq`` (DeepSeek-V2 §2.2.3): per sequence, Σ_i f_i·P_i with f_i =
    E/(k·S) × the assignments to expert i and P_i the mean score of
    expert i, averaged over the batch; ``switch``: E × Σ_i (share of tokens
    whose first choice is i) × (mean score of i) over the batch."""
    E, k = cfg.n_experts, cfg.experts_per_token
    if cfg.router_aux == "seq":
        counts = jax.nn.one_hot(idx.reshape(B, S * k), E,
                                dtype=jnp.float32).sum(1)        # (B, E)
        f = counts * (E / (k * S))
        P = scores.reshape(B, S, E).mean(1)
        return jnp.mean(jnp.sum(f * P, -1))
    density = jnp.mean(jax.nn.one_hot(idx[:, 0], E), axis=0)
    return E * jnp.sum(density * jnp.mean(scores, axis=0))
