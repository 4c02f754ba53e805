"""Mixture-of-Experts FFN: top-k router, capacity-based sort dispatch,
optional shared experts (DeepSeekMoE-style fine-grained configuration).

Dispatch is gather/scatter-based (static shapes, no (T, E, C) one-hot tensor)
so that compiled FLOPs ≈ active FLOPs — this is what makes the MoE rooflines
honest.  Experts are sharded over the 'model' mesh axis (expert parallelism);
GSPMD inserts the dispatch all-to-alls.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .layers import dense_init, rms_norm, swiglu

__all__ = ["init_moe", "apply_moe", "set_moe_mesh", "EXPERT_LEAF_PATTERNS",
           "expert_group_spec"]

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
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.d_ff
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "ln": jnp.zeros((d,), dt),
        "router": dense_init(ks[0], (d, E), 0, jnp.float32),
        "w_gate": dense_init(ks[1], (E, d, ff), 1, dt),
        "w_up": dense_init(ks[2], (E, d, ff), 1, dt),
        "w_down": dense_init(ks[3], (E, ff, d), 1, dt),
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
    (their combine weight is zeroed) — Switch/GShard semantics.
    """
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
