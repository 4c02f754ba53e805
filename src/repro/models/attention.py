"""GQA attention: full-causal, sliding-window, cross, and cached decode;
multi-head latent attention (MLA, DeepSeek-V2) with YaRN rope on the
training path and through a dense cache.

The jnp path here is the reference used for training/dry-run lowering; the
Pallas flash kernel (repro.kernels.flash_attention) is the TPU hot path and is
validated against :func:`sdpa_ref` in tests.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import math

import jax
import jax.numpy as jnp
import numpy as np

from .layers import dense_init, rms_norm, rope

__all__ = ["init_attn", "apply_attn", "apply_attn_paged", "apply_mla",
           "init_mla", "mla_softmax_scale", "yarn_inv_freq",
           "apply_attn_paged_prefill", "init_kv_cache", "sdpa_ref",
           "sdpa_pos_ref", "prev_page_positions", "paged_prefill_sdpa"]

NEG_INF = -1e30

# §Perf lever: keep the attention *data path* (logits → probs → out) in bf16
# — mirrors the Pallas flash kernel, whose f32 accumulators live in VMEM while
# HBM-crossing tensors stay bf16.  Halves the activation-cotangent collective
# payloads that otherwise ride the f32 jnp reference path.
_BF16_PATH = {"on": False}


def set_bf16_path(flag: bool) -> None:
    _BF16_PATH["on"] = bool(flag)


def init_attn(key, cfg, cross: bool = False) -> Dict:
    if cfg.attn_kind == "mla":
        return init_mla(key, cfg)
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "ln": jnp.zeros((d,), dt),
        "wq": dense_init(ks[0], (d, H * hd), 0, dt),
        "wk": dense_init(ks[1], (d, K * hd), 0, dt),
        "wv": dense_init(ks[2], (d, K * hd), 0, dt),
        "wo": dense_init(ks[3], (H * hd, d), 0, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((K * hd,), dt)
        p["bv"] = jnp.zeros((K * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dt)
        p["k_norm"] = jnp.zeros((hd,), dt)
    return p


def sdpa_ref(q, k, v, *, causal: bool, window: int = 0,
             q_offset: int = 0, kv_len: Optional[jax.Array] = None,
             scale: Optional[float] = None):
    """Scaled dot-product attention with GQA head sharing.

    q, k: (B, Sq|Sk, H|K, hd); v: (B, Sk, K, hd_v).  H % K == 0.
    ``scale`` defaults to hd^-1/2.
    ``q_offset``: absolute position of q[0] (for cached decode).
    ``kv_len``:   optional dynamic number of valid kv entries (decode);
                  a scalar, or a ``(B,)`` array for ragged slot batches
                  (the continuous-batching engine, DESIGN §10).
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    scale = hd ** -0.5 if scale is None else scale
    bf16_path = _BF16_PATH["on"] and q.dtype == jnp.bfloat16
    acc_dt = q.dtype if bf16_path else jnp.float32
    qf = q.astype(acc_dt).reshape(B, Sq, K, G, hd)
    kf = k.astype(acc_dt)
    vf = v.astype(acc_dt)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qf * scale, kf,
                        preferred_element_type=jnp.float32)  # (B,K,G,Sq,Sk)
    Sk = k.shape[1]
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), dtype=bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    lens = None if kv_len is None else jnp.asarray(kv_len)
    if lens is not None and lens.ndim == 1:
        # ragged slot batch: per-slot valid-kv mask (decode-only shapes,
        # Sq = 1 — the (B, Sq, Sk) mask never rides the training path)
        bmask = mask[None] & (k_pos[None, None, :] < lens[:, None, None])
        logits = jnp.where(bmask[:, None, None], logits, NEG_INF)
    else:
        if lens is not None:
            mask &= k_pos[None, :] < lens
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(acc_dt)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, vf)
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def sdpa_pos_ref(q, k, v, *, q_pos, k_pos, k_valid, window: int = 0):
    """GQA SDPA with EXPLICIT per-row key positions and validity — the
    chunked-prefill reference (DESIGN §11), where the key rows are a mix
    of ring/linear page rows and the in-flight chunk so neither positions
    nor validity are derivable from row indices.

    q: (B, Sq, H, hd); k, v: (B, Sk, K, hd); q_pos: (Sq,) absolute query
    positions; k_pos: (Sk,) absolute key positions; k_valid: (Sk,) bool.
    Masking: valid ∧ causal (k_pos ≤ q_pos) ∧ window (k_pos > q_pos − w).
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    scale = hd ** -0.5
    qf = q.astype(jnp.float32).reshape(B, Sq, K, G, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qf * scale,
                        k.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    mask = k_valid[None, :] & (k_pos[None, :] <= q_pos[:, None])
    if window:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def prev_page_positions(n_rows, chunk_start, window: int = 0):
    """(positions, valid) of the previously-filled page rows a prefill
    chunk starting at absolute position ``chunk_start`` attends to.

    Linear (``window == 0``): row r holds position r, valid iff
    r < chunk_start.  Ring: row r holds the LATEST position p < chunk_start
    with p ≡ r (mod window) — ``(chunk_start−1) − ((chunk_start−1−r) mod
    window)`` — valid iff that position exists (p ≥ 0); the occupied rows
    are exactly the prefix [0, min(chunk_start, window))."""
    r = jnp.arange(n_rows, dtype=jnp.int32)
    start = jnp.asarray(chunk_start, jnp.int32)
    if window:
        pos = (start - 1) - jnp.mod(start - 1 - r, window)
        # rows past the ring (NULL page-table entries) alias in-window
        # positions through the mod — only the ring's own rows are real
        valid = (pos >= 0) & (pos < start) & (r < window)
    else:
        pos = r
        valid = (pos >= 0) & (pos < start)
    return pos, valid


def paged_prefill_sdpa(q, k_chunk, v_chunk, k_pool, v_pool, pt_row,
                       chunk_start, chunk_len, *, window: int = 0):
    """Pure-jnp chunked-prefill attention (DESIGN §11): chunk queries
    attend causally to every previously-filled page row of ONE slot
    (gathered through its page-table row) plus the in-flight chunk's own
    keys — the chunk K/V ride alongside rather than through the pool, so
    ring rows the chunk is about to overwrite are still read at their
    pre-chunk values.

    q: (1, C, H, hd); k_chunk, v_chunk: (1, C, K, hd); k_pool, v_pool:
    (K, num_pages, page_size, hd); pt_row: (n_pages,) physical page ids;
    chunk_start: absolute position of q[0]; chunk_len: valid chunk rows
    (the last chunk is padded — rows ≥ chunk_len are masked everywhere).

    This is both the ``attn_impl="ref"`` op sequence and (via
    :func:`repro.kernels.ref.paged_prefill_attention_ref`) the oracle the
    Pallas paged-prefill kernel is tested against."""
    C = q.shape[1]
    k_prev = _gather_pages(k_pool, pt_row[None])      # (1, R, K, hd)
    v_prev = _gather_pages(v_pool, pt_row[None])
    kpos_prev, valid_prev = prev_page_positions(k_prev.shape[1],
                                                chunk_start, window)
    # sanitize never-written rows: masked logits already exclude them, but
    # 0·NaN = NaN in the value matmul would leak pool poison (DESIGN §10)
    dead = ~valid_prev[None, :, None, None]
    k_prev = jnp.where(dead, 0.0, k_prev).astype(k_prev.dtype)
    v_prev = jnp.where(dead, 0.0, v_prev).astype(v_prev.dtype)
    qpos = (jnp.asarray(chunk_start, jnp.int32)
            + jnp.arange(C, dtype=jnp.int32))
    k_all = jnp.concatenate([k_prev, k_chunk], axis=1)
    v_all = jnp.concatenate([v_prev, v_chunk], axis=1)
    k_pos = jnp.concatenate([kpos_prev, qpos])
    k_valid = jnp.concatenate(
        [valid_prev, jnp.arange(C) < jnp.asarray(chunk_len, jnp.int32)])
    return sdpa_pos_ref(q, k_all, v_all, q_pos=qpos, k_pos=k_pos,
                        k_valid=k_valid, window=window)


def _qkv(p, cfg, x, positions):
    use_rope = getattr(cfg, "pos_emb", "rope") == "rope"
    B, S, d = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, K, hd)
    v = v.reshape(B, S, K, hd)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def init_kv_cache(cfg, batch: int, length: int, dtype=None):
    dt = dtype or jnp.dtype(cfg.dtype)
    if cfg.attn_kind == "mla":
        # the expanded per-head keys and values, not the latent
        H = cfg.n_heads
        return {"k": jnp.zeros((batch, length, H, cfg.qk_head_dim), dt),
                "v": jnp.zeros((batch, length, H, cfg.v_head_dim), dt)}
    K, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((batch, length, K, hd), dt),
        "v": jnp.zeros((batch, length, K, hd), dt),
    }


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1)
# ---------------------------------------------------------------------------

def yarn_inv_freq(cfg) -> np.ndarray:
    """(qk_rope_head_dim / 2,) rope frequencies.  With ``rope_factor`` > 1,
    YaRN: dimension i keeps θ^(-2i/d) below the beta_fast correction
    dimension, takes θ^(-2i/d)/factor above the beta_slow one, and a linear
    ramp between them."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    freq = base ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1:
        return freq.astype(np.float32)

    def corr_dim(n_rot):
        return (dim * math.log(cfg.rope_original_max_pos
                               / (n_rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (freq * (1 - ramp) + freq / cfg.rope_factor * ramp).astype(
        np.float32)


def mla_softmax_scale(cfg) -> float:
    """qk_head_dim^-1/2, times YaRN's mscale² where rope is scaled."""
    m = (0.1 * cfg.yarn_mscale_all_dim * math.log(cfg.rope_factor) + 1.0
         if cfg.rope_factor > 1 else 1.0)
    return cfg.qk_head_dim ** -0.5 * m * m


def init_mla(key, cfg) -> Dict:
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    return {
        "ln": jnp.zeros((d,), dt),
        "wq": dense_init(ks[0], (d, H * cfg.qk_head_dim), 0, dt),
        "wkv_a": dense_init(ks[1], (d, r + cfg.qk_rope_head_dim), 0, dt),
        "latent": {"ln": jnp.zeros((r,), dt)},
        "wkv_b": dense_init(ks[2], (r, H * (cfg.qk_nope_head_dim
                                            + cfg.v_head_dim)), 0, dt),
        "wo": dense_init(ks[3], (H * cfg.v_head_dim, d), 0, dt),
    }


def _mla_qkv(p, cfg, h, positions):
    """q, k (B, S, H, nope + rope) and v (B, S, H, v_head_dim) of normed
    hidden states h: keys and values from the normed latent, one rope key
    shared by every head."""
    B, S, _ = h.shape
    H, dn, dr, r = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                    cfg.kv_lora_rank)
    inv_freq = jnp.asarray(yarn_inv_freq(cfg))
    q = (h @ p["wq"]).reshape(B, S, H, dn + dr)
    kv_a = h @ p["wkv_a"]
    latent = rms_norm(kv_a[..., :r], p["latent"]["ln"], cfg.norm_eps)
    kv = (latent @ p["wkv_b"]).reshape(B, S, H, dn + cfg.v_head_dim)
    q_pe = rope(q[..., dn:], positions, cfg.rope_theta, inv_freq)
    k_pe = rope(kv_a[..., None, r:], positions, cfg.rope_theta, inv_freq)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (B, S, H, dr))], axis=-1)
    return q, k, kv[..., dn:]


def apply_mla(p, cfg, x, positions, *, mode: str = "train",
              cache: Optional[Dict] = None) -> Tuple:
    """MLA sub-block with pre-norm + residual, in the ``jax.named_scope``
    ``mla``.  ``train``/``prefill`` attend causally over the sequence
    (prefill also returns the per-head keys and values as the cache);
    ``decode`` writes one token into that dense cache and attends over it.
    Returns (y, new_cache)."""
    with jax.named_scope("mla"):
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        B, S = h.shape[:2]
        scale = mla_softmax_scale(cfg)
        q, k, v = _mla_qkv(p, cfg, h, positions)
        new_cache = None
        if mode in ("train", "prefill"):
            out = sdpa_ref(q, k, v, causal=True, scale=scale)
            if mode == "prefill":
                new_cache = {"k": k, "v": v}
        else:
            assert mode == "decode" and cache is not None, mode
            pos = positions[0, 0]
            k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, pos, 1)
            v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, pos, 1)
            out = sdpa_ref(q, k, v, causal=False, kv_len=pos + 1,
                           scale=scale)
            new_cache = {"k": k, "v": v}
        y = out.reshape(B, S, cfg.n_heads * cfg.v_head_dim) @ p["wo"]
        return x + y, new_cache


def apply_attn(p, cfg, x, positions, *, mode: str = "train",
               cache: Optional[Dict] = None, window: int = 0,
               cur_len: Optional[jax.Array] = None,
               xattn_kv: Optional[Tuple] = None) -> Tuple:
    """Attention sub-block with pre-norm + residual.

    mode:
      "train"   — full (or sliding-window) causal self-attention.
      "prefill" — as train, but also fills and returns the cache.
      "decode"  — single-step (Sq=1) with ring-buffer/linear cache update.
      "cross"   — encoder-decoder cross attention (xattn_kv = (k, v)).
    Returns (y, new_cache).
    """
    if cfg.attn_kind == "mla":
        assert not (window or cfg.sliding_window) and mode != "cross", \
            "MLA runs full causal self-attention only"
        return apply_mla(p, cfg, x, positions, mode=mode, cache=cache)
    resid = x
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    win = window or cfg.sliding_window

    if mode == "cross":
        B, S, d = h.shape
        H, hd = cfg.n_heads, cfg.hd
        q = (h @ p["wq"]).reshape(B, S, H, hd)
        k, v = xattn_kv
        out = sdpa_ref(q, k, v, causal=False)
        y = out.reshape(B, S, H * hd) @ p["wo"]
        return resid + y, cache

    if mode in ("train", "prefill"):
        q, k, v = _qkv(p, cfg, h, positions)
        out = sdpa_ref(q, k, v, causal=True, window=win)
        new_cache = None
        if mode == "prefill":
            if win and k.shape[1] > win:
                # keep the last `win` entries, rolled so that ring-buffer slot
                # of position p is p % win (decode-compatible layout).
                S = k.shape[1]
                k_w, v_w = k[:, -win:], v[:, -win:]
                shift = (S - win) % win
                new_cache = {"k": jnp.roll(k_w, shift, axis=1),
                             "v": jnp.roll(v_w, shift, axis=1)}
            else:
                new_cache = {"k": k, "v": v}
        B, S = h.shape[:2]
        y = out.reshape(B, S, cfg.n_heads * cfg.hd) @ p["wo"]
        return resid + y, new_cache

    assert mode == "decode" and cache is not None
    # one new token; positions: (B, 1) absolute position of the new token
    q, k_new, v_new = _qkv(p, cfg, h, positions)
    L = cache["k"].shape[1]
    if win and L == win:
        # ring buffer: slot = pos mod window
        slot = positions[0, 0] % win
    else:
        slot = cur_len if cur_len is not None else positions[0, 0]
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=1)
    if win and L == win:
        # every occupied slot is within the window → plain full attention over
        # the ring buffer (positions beyond cur fill are zero-keyed but masked
        # by kv_len when the buffer is not yet full).
        n_valid = jnp.minimum(positions[0, 0] + 1, win)
        out = sdpa_ref(q, k, v, causal=False, kv_len=n_valid)
    else:
        out = sdpa_ref(q, k, v, causal=False, kv_len=positions[0, 0] + 1)
    B = h.shape[0]
    y = out.reshape(B, 1, cfg.n_heads * cfg.hd) @ p["wo"]
    return resid + y, {"k": k, "v": v}


def _gather_pages(pool, page_table):
    """(K, num_pages, page_size, hd) × (B, n_pages) → dense
    (B, n_pages·page_size, K, hd) view — the same op sequence as
    :func:`repro.kernels.ref.gather_pages` (kept local: kernels imports
    this module for ``sdpa_ref``)."""
    B, n_pages = page_table.shape
    K, _, page_size, hd = pool.shape
    dense = jnp.take(pool, page_table.reshape(-1), axis=1)
    return dense.reshape(K, B, n_pages * page_size, hd).transpose(1, 2, 0, 3)


def apply_attn_paged(p, cfg, x, positions, *, pools, page_table, kv_len,
                     window: int = 0, attn_fn=None) -> Tuple:
    """Paged decode attention sub-block (DESIGN §10): one token per slot,
    KV read/written through a page table instead of a contiguous cache.

    x: (B, 1, d) slot-batched new-token activations; positions: (B, 1)
    per-slot absolute position of the new token (ragged — unlike
    :func:`apply_attn`'s uniform decode ``pos``); pools: {"k","v"} page
    pools ``(K, num_pages, page_size, hd)``; page_table: (B, n_pages)
    physical-page ids; kv_len: (B,) valid KV rows to attend over
    *including* the row written here — the scheduler passes 0 for idle
    slots, whose writes sink to the null page and whose output is junk
    that the active mask discards.

    ``attn_fn(q, k_pool, v_pool, page_table, kv_len) -> (B, K, G, hd)``
    selects the attention backend (the Pallas paged kernel); ``None``
    runs the pure-jnp gather + ``sdpa_ref`` reference — the exact op
    sequence of :func:`repro.kernels.ref.paged_attention_ref`, which the
    bit-exact engine-vs-dense gate relies on.

    Returns (y, new_pools).
    """
    resid = x
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k_new, v_new = _qkv(p, cfg, h, positions)

    B = x.shape[0]
    page_size = pools["k"].shape[2]
    # logical write row: absolute position, folded onto the ring in
    # window mode (same layout as the dense ring cache: row = pos % win)
    row = positions[:, 0] % window if window else positions[:, 0]
    phys = page_table[jnp.arange(B), row // page_size]        # (B,)
    rin = row % page_size
    # idle slots (page-table row all NULL) scatter into the null page —
    # duplicate (0, 0) targets collide only with each other, never with a
    # live slot's pages (allocator invariant).
    k_pool = pools["k"].at[:, phys, rin].set(k_new[:, 0].transpose(1, 0, 2))
    v_pool = pools["v"].at[:, phys, rin].set(v_new[:, 0].transpose(1, 0, 2))

    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    qg = q.reshape(B, K, H // K, hd)
    if attn_fn is None:
        k = _gather_pages(k_pool, page_table)
        v = _gather_pages(v_pool, page_table)
        out = sdpa_ref(q, k, v, causal=False, kv_len=kv_len)
    else:
        out = attn_fn(qg, k_pool, v_pool, page_table, kv_len)
        out = out.reshape(B, 1, H, hd)
    y = out.reshape(B, 1, H * hd) @ p["wo"]
    return resid + y, {"k": k_pool, "v": v_pool}


def apply_attn_paged_prefill(p, cfg, x, *, pools, pt_row, chunk_start,
                             chunk_len, window: int = 0,
                             attn_fn=None) -> Tuple:
    """Chunked-prefill attention sub-block (DESIGN §11): one fixed-size
    chunk of ONE slot's prompt, attending over that slot's
    previously-filled pages plus itself, then scattered into the pages.

    x: (1, C, d) chunk activations (C is the STATIC chunk width — the
    whole serving trace compiles this shape once); pt_row: (n_pages,)
    the slot's page-table row; chunk_start: absolute position of x[:, 0]
    (the slot's prefill cursor); chunk_len: traced valid-token count —
    the last chunk of a prompt is padded, and padded rows are masked out
    of the attention AND their page writes sink to the null page.

    Attention runs BEFORE the write: in ring mode a chunk's rows alias
    ring rows that still hold live pre-chunk keys (position p − window is
    in-window for early chunk queries), so write-then-attend would read
    the overwritten values.  ``attn_fn(q, k_chunk, v_chunk, k_pool,
    v_pool, pt_row, chunk_start, chunk_len) -> (1, C, H, hd)`` selects
    the Pallas paged-prefill kernel; ``None`` runs
    :func:`paged_prefill_sdpa` — the oracle's exact op sequence.

    Returns (y (1, C, d), new_pools).
    """
    resid = x
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    C = x.shape[1]
    qpos = (jnp.asarray(chunk_start, jnp.int32)
            + jnp.arange(C, dtype=jnp.int32))
    q, k_new, v_new = _qkv(p, cfg, h, qpos[None])
    if attn_fn is None:
        out = paged_prefill_sdpa(q, k_new, v_new, pools["k"], pools["v"],
                                 pt_row, chunk_start, chunk_len,
                                 window=window)
    else:
        out = attn_fn(q, k_new, v_new, pools["k"], pools["v"], pt_row,
                      chunk_start, chunk_len)
    # scatter the chunk's VALID rows into the slot's pages; padded rows
    # redirect to physical page 0 (the null write sink — same idiom as
    # idle decode slots, see apply_attn_paged).  Ring rows are distinct
    # within one chunk because the engine enforces C <= window.
    page_size = pools["k"].shape[2]
    row = jnp.mod(qpos, window) if window else qpos
    live = jnp.arange(C) < jnp.asarray(chunk_len, jnp.int32)
    phys = jnp.where(live, pt_row[row // page_size], 0)
    rin = row % page_size
    k_pool = pools["k"].at[:, phys, rin].set(k_new[0].transpose(1, 0, 2))
    v_pool = pools["v"].at[:, phys, rin].set(v_new[0].transpose(1, 0, 2))
    y = out.reshape(1, C, cfg.n_heads * cfg.hd) @ p["wo"]
    return resid + y, {"k": k_pool, "v": v_pool}
