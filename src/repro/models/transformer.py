"""Unified decoder LM covering dense / MoE / SSM / hybrid / VLM families.

Layers are grouped into **period blocks** (configs.base.block_period): all
layers at the same position-within-period share a stacked parameter tree of
leading dim ``n_blocks`` and the stack is driven by ``jax.lax.scan`` — this
keeps the HLO size O(period) instead of O(n_layers), which is what makes the
94-layer MoE dry-run compile in seconds.  A model's ``first_k_dense``
leading layers (``params["lead"]``, one tree each) run before the scan.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import (ModelConfig, layer_kinds, n_blocks,
                                period_kinds)
from .attention import (apply_attn, apply_attn_paged,
                        apply_attn_paged_prefill, init_attn, init_kv_cache)
from .layers import apply_dense_ffn, dense_init, init_dense_ffn, rms_norm
from .mamba import apply_mamba, init_mamba, init_ssm_cache
from .moe import apply_moe, apply_moe_dropless, init_moe

__all__ = [
    "init_lm", "lm_loss", "lm_prefill", "lm_decode_step",
    "lm_decode_step_paged", "lm_prefill_chunk_paged", "lm_serve_step_mixed",
    "init_lm_cache", "lm_param_specs", "lm_cache_specs",
    "set_seq_parallel_mesh",
]

# §Perf lever (Megatron-style sequence parallelism): constrain the residual
# stream between layers to be sequence-sharded over 'model', turning each TP
# all-reduce (2× payload) into reduce-scatter + all-gather (1× payload).
_SEQ_PAR = {"mesh": None}


def set_seq_parallel_mesh(mesh) -> None:
    _SEQ_PAR["mesh"] = mesh


# §Perf lever (ZeRO-3 / agents="pod" mode): re-constrain each layer's weight
# slice to its FSDP sharding INSIDE the scan body, so XLA all-gathers one
# layer at a time instead of materializing the whole unsharded stack.
_FSDP = {"mesh": None, "specs": None}


def set_fsdp_constraint(mesh, specs) -> None:
    _FSDP["mesh"] = mesh
    _FSDP["specs"] = specs


def _fsdp_constrain(tree, pi):
    mesh = _FSDP["mesh"]
    if mesh is None:
        return tree
    from jax.sharding import NamedSharding
    return jax.tree.map(
        lambda x, sp: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, sp)),
        tree, _FSDP["specs"][pi], is_leaf=lambda v: isinstance(v, P))


def _seq_constrain(x):
    mesh = _SEQ_PAR["mesh"]
    if mesh is None:
        return x
    from jax.sharding import NamedSharding
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(None, "model", None)))


# ---------------------------------------------------------------------------
# per-layer init / apply / spec
# ---------------------------------------------------------------------------

def _init_layer(cfg: ModelConfig, kind, key) -> Dict:
    mixer, ffn = kind
    k1, k2 = jax.random.split(key)
    p = {}
    if mixer == "attn":
        p["attn"] = init_attn(k1, cfg)
    else:
        p["ssm"] = init_mamba(k1, cfg)
    if ffn == "dense":
        ff = cfg.dense_d_ff or cfg.d_ff
        p["ffn"] = init_dense_ffn(k2, cfg.d_model, ff, cfg.mlp_gated,
                                  jnp.dtype(cfg.dtype))
    elif ffn == "moe":
        p["moe"] = init_moe(k2, cfg)
    return p


def _zero_stats(cfg) -> Dict:
    """The stack's running sums: the auxiliary loss, and for a dropless
    expert share the assignments its held experts computed."""
    stats = {"aux": jnp.zeros((), jnp.float32)}
    if cfg.moe_dropless and any(f == "moe" for _, f in layer_kinds(cfg)):
        stats["moe_rows_held"] = jnp.zeros((), jnp.int32)
    return stats


def _apply_layer(cfg, kind, p, x, positions, *, mode, cache, window, stats):
    mixer, ffn = kind
    new_cache = None
    if mixer == "attn":
        x, new_cache = apply_attn(p["attn"], cfg, x, positions, mode=mode,
                                  cache=cache, window=window)
    else:
        m_mode = "decode" if mode == "decode" else "train"
        x, new_cache = apply_mamba(p["ssm"], cfg, x, mode=m_mode, cache=cache)
    if ffn == "dense":
        x = apply_dense_ffn(p["ffn"], x, cfg.norm_eps)
    elif ffn == "moe":
        if cfg.moe_dropless:
            x, a, rows = apply_moe_dropless(p["moe"], cfg, x, cfg.norm_eps)
            stats = dict(stats, moe_rows_held=stats["moe_rows_held"] + rows)
        else:
            x, a = apply_moe(p["moe"], cfg, x, cfg.norm_eps)
        stats = dict(stats, aux=stats["aux"] + a)
    return x, new_cache, stats


def _attn_specs(cfg) -> Dict:
    if cfg.attn_kind == "mla":
        return {"ln": P(None), "wq": P(None, "model"),
                "wkv_a": P(None, None), "latent": {"ln": P(None)},
                "wkv_b": P(None, "model"), "wo": P("model", None)}
    sp = {
        "ln": P(None),
        "wq": P(None, "model"), "wk": P(None, "model"), "wv": P(None, "model"),
        "wo": P("model", None),
    }
    if cfg.qkv_bias:
        sp.update({"bq": P("model"), "bk": P("model"), "bv": P("model")})
    if cfg.qk_norm:
        sp.update({"q_norm": P(None), "k_norm": P(None)})
    return sp


def _ssm_specs(cfg) -> Dict:
    return {
        "ln": P(None),
        "in_proj": P(None, "model"),
        "conv_w": P(None, "model"), "conv_b": P("model"),
        "x_proj": P("model", None),
        "dt_proj": P(None, "model"), "dt_bias": P("model"),
        "A_log": P("model", None), "D": P("model"),
        "out_proj": P("model", None),
    }


def _ffn_specs(cfg, gated) -> Dict:
    sp = {"ln": P(None), "w_up": P(None, "model"), "w_down": P("model", None)}
    if gated:
        sp["w_gate"] = P(None, "model")
    return sp


def _moe_specs(cfg) -> Dict:
    sp = {
        "ln": P(None),
        "router": P(None, None),
        # expert parallelism: experts sharded over the 'model' axis
        "w_gate": P("model", None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }
    if cfg.n_shared_experts:
        sp["shared"] = _ffn_specs(cfg, True)
        del sp["shared"]["ln"]
    return sp


def _layer_specs(cfg, kind) -> Dict:
    mixer, ffn = kind
    sp = {}
    if mixer == "attn":
        sp["attn"] = _attn_specs(cfg)
    else:
        sp["ssm"] = _ssm_specs(cfg)
    if ffn == "dense":
        sp["ffn"] = _ffn_specs(cfg, cfg.mlp_gated)
    elif ffn == "moe":
        sp["moe"] = _moe_specs(cfg)
    return sp


def _prepend(spec: P, axis) -> P:
    return P(axis, *tuple(spec))


# ---------------------------------------------------------------------------
# model init / specs
# ---------------------------------------------------------------------------

def _lead_kinds(cfg: ModelConfig):
    return layer_kinds(cfg)[:cfg.first_k_dense]


def init_lm(cfg: ModelConfig, key) -> Dict:
    kinds = period_kinds(cfg)
    period, nb = len(kinds), n_blocks(cfg)
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, period + 3)
    blocks = []
    for pi, kind in enumerate(kinds):
        bkeys = jax.random.split(keys[pi], nb)
        blocks.append(jax.vmap(functools.partial(_init_layer, cfg, kind))(bkeys))
    params = {
        "embed": dense_init(keys[-3], (cfg.vocab_size, cfg.d_model), 1, dt),
        "blocks": tuple(blocks),
        "final_ln": jnp.zeros((cfg.d_model,), dt),
        "lm_head": dense_init(keys[-2], (cfg.d_model, cfg.vocab_size), 0, dt),
    }
    if cfg.first_k_dense:
        lkeys = jax.random.split(keys[-1], cfg.first_k_dense)
        params["lead"] = tuple(_init_layer(cfg, kind, k)
                               for kind, k in zip(_lead_kinds(cfg), lkeys))
    return params


def lm_param_specs(cfg: ModelConfig) -> Dict:
    blocks = []
    for kind in period_kinds(cfg):
        sp = _layer_specs(cfg, kind)
        blocks.append(jax.tree.map(
            lambda s: _prepend(s, None), sp,
            is_leaf=lambda s: isinstance(s, P)))
    specs = {
        "embed": P("model", None),
        "blocks": tuple(blocks),
        "final_ln": P(None),
        "lm_head": P(None, "model"),
    }
    if cfg.first_k_dense:
        specs["lead"] = tuple(_layer_specs(cfg, kind)
                              for kind in _lead_kinds(cfg))
    return specs


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _embed_inputs(cfg, params, tokens, frontend_embeds):
    x = jnp.take(params["embed"], tokens, axis=0)
    if frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    return x


def _stack_scan(cfg, params, x, positions, *, mode, caches=None, window=0,
                remat=True, unroll=False, remat_policy="full"):
    """The leading dense layers, then the period-block scan.  ``caches`` is
    a tuple: one cache per leading layer, then one stacked cache per period
    position.  Returns (x, new_caches, stats) (:func:`_zero_stats`)."""
    kinds = period_kinds(cfg)
    k = cfg.first_k_dense

    def remat_fn(fn):
        if not (remat and mode == "train"):
            return fn
        if remat_policy == "dots":
            # save matmul (incl. TP-all-reduced) outputs; recompute only
            # elementwise ops — no collective recompute in backward (§Perf)
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.dots_saveable)
        return jax.checkpoint(fn)

    stats = _zero_stats(cfg)
    lead_caches = []
    for i, kind in enumerate(_lead_kinds(cfg)):
        def lead(x, stats, lp, c, kind=kind):
            x, nc, stats = _apply_layer(cfg, kind, lp, _seq_constrain(x),
                                        positions, mode=mode, cache=c,
                                        window=window, stats=stats)
            return x, nc if nc is not None else 0.0, stats
        x, nc, stats = remat_fn(lead)(
            x, stats, params["lead"][i], None if caches is None else caches[i])
        lead_caches.append(nc)

    def body(carry, xs):
        x, stats = carry
        block_params, block_caches = xs
        new_caches = []
        for pi, kind in enumerate(kinds):
            c = None if block_caches is None else block_caches[pi]
            x = _seq_constrain(x)
            bp = _fsdp_constrain(block_params[pi], pi)
            x, nc, stats = _apply_layer(cfg, kind, bp, x, positions,
                                        mode=mode, cache=c, window=window,
                                        stats=stats)
            new_caches.append(nc if nc is not None else 0.0)
        return (x, stats), tuple(new_caches)

    xs = (params["blocks"], None if caches is None else tuple(caches[k:]))
    (x, stats), new_caches = jax.lax.scan(
        remat_fn(body), (x, stats), xs, unroll=unroll)
    return x, tuple(lead_caches) + tuple(new_caches), stats


def _logits(cfg, params, x):
    h = rms_norm(x, params["final_ln"], cfg.norm_eps)
    return h @ params["lm_head"]


def lm_loss(cfg: ModelConfig, params, batch, *, remat=True,
            unroll=False, remat_policy="full", with_stats=False):
    """Next-token cross entropy plus the auxiliary loss.  batch: {tokens
    (B,S) int32, [frontend (B,P,d)]}; loss predicts tokens[1:] from prefix.
    ``with_stats`` returns (loss, counters): ``moe_rows_held`` for a
    dropless expert share, else nothing."""
    tokens = batch["tokens"]
    fe = batch.get("frontend")
    x = _embed_inputs(cfg, params, tokens, fe)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x, _, stats = _stack_scan(cfg, params, x, positions, mode="train",
                              remat=remat, unroll=unroll,
                              remat_policy=remat_policy)
    logits = _logits(cfg, params, x).astype(jnp.float32)
    # predict token t+1 at position t; frontend positions predict nothing.
    n_front = 0 if fe is None else fe.shape[1]
    pred = logits[:, n_front:-1]                       # (B, St-1, V)
    tgt = tokens[:, 1:]
    logz = jax.nn.logsumexp(pred, axis=-1)
    gold = jnp.take_along_axis(pred, tgt[..., None], axis=-1)[..., 0]
    nll = jnp.mean(logz - gold)
    loss = nll + stats.pop("aux")
    return (loss, stats) if with_stats else loss


def init_lm_cache(cfg: ModelConfig, batch: int, length: int):
    """Cache pytree matching the stack: one cache per leading dense layer,
    then a tuple over period positions of stacked (n_blocks, ...) leaves."""
    nb = n_blocks(cfg)
    caches = [init_kv_cache(cfg, batch, length) for _ in _lead_kinds(cfg)]
    for mixer, _ in period_kinds(cfg):
        if mixer == "attn":
            one = init_kv_cache(cfg, batch, length)
        else:
            one = init_ssm_cache(cfg, batch)
        caches.append(jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (nb,) + l.shape), one))
    return tuple(caches)


def lm_cache_specs(cfg: ModelConfig) -> Tuple:
    # (B, S, K, hd): batch over 'data', kv heads over 'model'
    specs = [{"k": P("data", None, "model", None),
              "v": P("data", None, "model", None)}
             for _ in _lead_kinds(cfg)]
    for mixer, _ in period_kinds(cfg):
        if mixer == "attn":
            # (n_blocks, B, S, K, hd): batch over 'data', kv heads over 'model'
            one = {"k": P(None, "data", None, "model", None),
                   "v": P(None, "data", None, "model", None)}
        else:
            one = {"h": P(None, "data", "model", None),
                   "conv": P(None, "data", None, "model")}
        specs.append(one)
    return tuple(specs)


def lm_prefill(cfg: ModelConfig, params, tokens, frontend_embeds=None,
               window: int = 0, unroll=False):
    """Full-sequence forward returning (last-token logits, kv caches)."""
    x = _embed_inputs(cfg, params, tokens, frontend_embeds)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    # prefill needs per-layer caches as scan *outputs*; mamba still needs a
    # zero initial state, so pass explicit empty caches where required.
    caches = init_lm_cache(cfg, B, S if not window else window)
    x, new_caches, _ = _stack_scan(cfg, params, x, positions, mode="prefill",
                                   caches=caches, window=window, remat=False,
                                   unroll=unroll)
    logits = _logits(cfg, params, x[:, -1:])
    return logits, new_caches


def lm_decode_step(cfg: ModelConfig, params, caches, token, pos, *,
                   window: int = 0, unroll=False):
    """One decode step.  token: (B, 1) int32; pos: scalar int32 (uniform
    across the batch).  Returns (logits (B,1,V), new caches)."""
    x = jnp.take(params["embed"], token, axis=0)
    B = token.shape[0]
    positions = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)
    x, new_caches, _ = _stack_scan(cfg, params, x, positions, mode="decode",
                                   caches=caches, window=window, remat=False,
                                   unroll=unroll)
    return _logits(cfg, params, x), new_caches


def lm_decode_step_paged(cfg: ModelConfig, params, pools, token, positions,
                         page_table, kv_len, *, window: int = 0,
                         unroll=False, attn_fn=None):
    """One continuous-batching decode step over the whole slot batch
    (DESIGN §10).  Unlike :func:`lm_decode_step`, positions are **ragged**:
    ``positions`` is (B,) int32 — each slot's absolute token position —
    and ``kv_len`` is (B,) valid KV rows (0 for idle slots).  ``pools`` is
    the paged-cache tree (tuple over period positions of {"k","v"} pools
    with leading ``n_blocks``), scanned exactly like dense caches.
    ``attn_fn`` threads the attention backend down to
    :func:`~repro.models.attention.apply_attn_paged`.
    Returns (logits (B, 1, V), new pools)."""
    x = jnp.take(params["embed"], token, axis=0)
    B = token.shape[0]
    pos2 = positions.reshape(B, 1).astype(jnp.int32)
    kinds = _check_attn_only(cfg)

    def body(carry, xs):
        x, aux = carry
        block_params, block_pools = xs
        new_pools = []
        for pi, (mixer, ffn) in enumerate(kinds):
            bp = _fsdp_constrain(block_params[pi], pi)
            x, npools = apply_attn_paged(
                bp["attn"], cfg, x, pos2, pools=block_pools[pi],
                page_table=page_table, kv_len=kv_len, window=window,
                attn_fn=attn_fn)
            if ffn == "dense":
                x = apply_dense_ffn(bp["ffn"], x, cfg.norm_eps)
            elif ffn == "moe":
                x, a = apply_moe(bp["moe"], cfg, x, cfg.norm_eps)
                aux = aux + a
            new_pools.append(npools)
        return (x, aux), tuple(new_pools)

    (x, _), new_pools = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (params["blocks"], pools),
        unroll=unroll)
    return _logits(cfg, params, x), new_pools


def _check_attn_only(cfg):
    kinds = period_kinds(cfg)
    assert all(mixer == "attn" for mixer, _ in kinds), \
        "paged serving covers attention mixers only (DESIGN §10 scope note)"
    assert cfg.attn_kind == "gqa" and not cfg.first_k_dense, \
        "paged serving covers GQA stacks without leading dense layers"
    return kinds


def lm_prefill_chunk_paged(cfg: ModelConfig, params, pools, tokens, pt_row,
                           chunk_start, chunk_len, *, window: int = 0,
                           unroll=False, attn_fn=None):
    """One chunked-prefill step for ONE slot (DESIGN §11): run a fixed-size
    chunk of the slot's prompt through the stack, attending to the slot's
    previously-filled pages, and scatter the chunk's K/V into its pages.

    tokens: (1, C) int32 — the chunk, padded to the static width C;
    pt_row: (n_pages,) the slot's page-table row; chunk_start / chunk_len:
    traced int32 scalars (cursor and valid-token count).  ``attn_fn``
    selects the Pallas paged-prefill kernel (see
    :func:`~repro.models.attention.apply_attn_paged_prefill`).
    Returns (logits (1, C, V), new pools) — logits rows ≥ chunk_len are
    padding garbage the caller must ignore."""
    kinds = _check_attn_only(cfg)
    x = jnp.take(params["embed"], tokens, axis=0)

    def body(carry, xs):
        x, aux = carry
        block_params, block_pools = xs
        new_pools = []
        for pi, (mixer, ffn) in enumerate(kinds):
            bp = _fsdp_constrain(block_params[pi], pi)
            x, npools = apply_attn_paged_prefill(
                bp["attn"], cfg, x, pools=block_pools[pi], pt_row=pt_row,
                chunk_start=chunk_start, chunk_len=chunk_len, window=window,
                attn_fn=attn_fn)
            if ffn == "dense":
                x = apply_dense_ffn(bp["ffn"], x, cfg.norm_eps)
            elif ffn == "moe":
                x, a = apply_moe(bp["moe"], cfg, x, cfg.norm_eps)
                aux = aux + a
            new_pools.append(npools)
        return (x, aux), tuple(new_pools)

    (x, _), new_pools = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (params["blocks"], pools),
        unroll=unroll)
    return _logits(cfg, params, x), new_pools


def lm_serve_step_mixed(cfg: ModelConfig, params, pools, token, positions,
                        page_table, kv_len, chunk_tokens, pt_row,
                        chunk_start, chunk_len, *, window: int = 0,
                        unroll=False, attn_fn=None, prefill_attn_fn=None):
    """The fused mixed-work serving step (DESIGN §11): every live decode
    slot advances one token AND one prefill chunk of one mid-prefill slot
    runs, inside a SINGLE weight scan — the chunk piggybacks on the
    weights the decode batch already pulled through VMEM, which is the
    whole point of chunked prefill (no separate prompt pass, no
    head-of-line blocking).

    Decode inputs are exactly :func:`lm_decode_step_paged`'s (the engine
    masks mid-prefill slots out of ``page_table``/``kv_len`` — their ring
    rows are live); chunk inputs are exactly
    :func:`lm_prefill_chunk_paged`'s.  Within a layer the decode batch
    runs first, then the chunk — their page writes are disjoint (the
    chunk's slot is masked out of the decode dispatch, so its decode-side
    write sinks to the null page).

    Returns (decode logits (B, 1, V), chunk logits (1, C, V), new pools).
    """
    kinds = _check_attn_only(cfg)
    xd = jnp.take(params["embed"], token, axis=0)
    xc = jnp.take(params["embed"], chunk_tokens, axis=0)
    B = token.shape[0]
    pos2 = positions.reshape(B, 1).astype(jnp.int32)

    def body(carry, xs):
        xd, xc, aux = carry
        block_params, block_pools = xs
        new_pools = []
        for pi, (mixer, ffn) in enumerate(kinds):
            bp = _fsdp_constrain(block_params[pi], pi)
            xd, npools = apply_attn_paged(
                bp["attn"], cfg, xd, pos2, pools=block_pools[pi],
                page_table=page_table, kv_len=kv_len, window=window,
                attn_fn=attn_fn)
            xc, npools = apply_attn_paged_prefill(
                bp["attn"], cfg, xc, pools=npools, pt_row=pt_row,
                chunk_start=chunk_start, chunk_len=chunk_len, window=window,
                attn_fn=prefill_attn_fn)
            if ffn == "dense":
                xd = apply_dense_ffn(bp["ffn"], xd, cfg.norm_eps)
                xc = apply_dense_ffn(bp["ffn"], xc, cfg.norm_eps)
            elif ffn == "moe":
                xd, ad = apply_moe(bp["moe"], cfg, xd, cfg.norm_eps)
                xc, ac = apply_moe(bp["moe"], cfg, xc, cfg.norm_eps)
                aux = aux + ad + ac
            new_pools.append(npools)
        return (xd, xc, aux), tuple(new_pools)

    (xd, xc, _), new_pools = jax.lax.scan(
        body, (xd, xc, jnp.zeros((), jnp.float32)),
        (params["blocks"], pools), unroll=unroll)
    return _logits(cfg, params, xd), _logits(cfg, params, xc), new_pools
