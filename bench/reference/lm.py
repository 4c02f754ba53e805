"""Plain float32 reference of the decoder LM the program trains and serves.

Written from the published block, in straightforward ``jax.numpy``: it
imports nothing of the program.  It reads the parameter tree by its key
names (``embed``, ``blocks``, ``final_ln``, ``lm_head``; per layer
``attn`` with ``ln``/``wq``/``wk``/``wv``/``wo`` and optional
``bq``/``bk``/``bv``, ``ffn`` with ``ln``/``w_up``/``w_down`` and an
optional ``w_gate``), layer leaves stacked on a leading layer axis.

- RMSNorm with a ``(1 + w)`` scale: ``x · rsqrt(mean(x²) + eps) · (1 + w)``;
- grouped-query causal attention with rotary embeddings that rotate the two
  halves of each head (``theta`` from the configuration);
- a SwiGLU MLP, or a tanh-GELU MLP where ``mlp_gated`` is false;
- next-token cross entropy, the mean over every predicted position.

Every matmul runs at ``precision=HIGHEST``.  ``matmul_dtype`` rounds both
inputs of every matmul to a lower type first: the control that computes
the same reference in a precision below the configuration's.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _scaled(x, dtype):
    """``x`` rounded to ``dtype`` under one power-of-two scale per tensor
    that puts its largest magnitude near the type's largest value, as
    low-precision training scales its tensors."""
    amax = jnp.max(jnp.abs(x))
    top = float(jnp.finfo(dtype).max)
    scale = jnp.where(amax > 0, 2.0 ** jnp.floor(jnp.log2(top / amax)), 1.0)
    scale = jax.lax.stop_gradient(scale)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _lower(x, dtype):
    return _scaled(x, dtype)


def _lower_fwd(x, dtype):
    return _lower(x, dtype), None


def _lower_bwd(dtype, _, g):
    return (_scaled(g, dtype),)


_lower.defvjp(_lower_fwd, _lower_bwd)


def _round(x, dtype):
    """``x`` rounded to ``dtype``, its cotangent too, so the backward
    pass's matmuls run in that type as well."""
    return x if dtype is None else _lower(x, dtype)


def mm(a, b, dtype=None):
    return jnp.matmul(_round(a, dtype), _round(b, dtype), precision=HIGHEST)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def rope(x, positions, theta):
    """x: (B, S, H, hd); positions: (S,)."""
    half = x.shape[-1] // 2
    inv = theta ** -(jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, qpos, kpos, dtype):
    """Causal softmax attention of query rows at ``qpos`` over all keys.
    q: (B, Sq, K, G, hd) already scaled; k, v: (B, Sk, K, hd)."""
    s = jnp.einsum("bqkgh,bskh->bkgqs", _round(q, dtype), _round(k, dtype),
                   precision=HIGHEST)
    s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgqs,bskh->bqkgh", _round(w, dtype), _round(v, dtype),
                      precision=HIGHEST)


def attention(p, m: Dict, h, positions, dtype=None, q_block: int = 0):
    """Grouped-query causal attention; ``q_block`` splits the queries into
    blocks of that many rows, so long sequences fit."""
    B, S, _ = h.shape
    H, K = m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or m["d_model"] // H
    q, k, v = mm(h, p["wq"], dtype), mm(h, p["wk"], dtype), mm(h, p["wv"], dtype)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q.reshape(B, S, H, hd), positions, m["rope_theta"])
    k = rope(k.reshape(B, S, K, hd), positions, m["rope_theta"])
    v = v.reshape(B, S, K, hd)
    q = q.reshape(B, S, K, H // K, hd) * hd ** -0.5
    if q_block and S > q_block:
        nb = S // q_block
        qb = jnp.moveaxis(q.reshape(B, nb, q_block, K, H // K, hd), 1, 0)
        pb = positions.reshape(nb, q_block)
        o = jax.lax.map(lambda a: _attend(a[0], k, v, a[1], positions, dtype),
                        (qb, pb))
        o = jnp.moveaxis(o, 0, 1)
    else:
        o = _attend(q, k, v, positions, positions, dtype)
    return mm(o.reshape(B, S, H * hd), p["wo"], dtype)


def layer(lp, m: Dict, x, positions, dtype=None, q_block: int = 0):
    """One pre-norm block: attention then MLP, each with its residual."""
    eps = m.get("norm_eps", 1e-6)
    x = x + attention(lp["attn"], m, rms_norm(x, lp["attn"]["ln"], eps),
                      positions, dtype, q_block)
    return x + mlp(lp["ffn"], rms_norm(x, lp["ffn"]["ln"], eps),
                   m.get("mlp_gated", True), dtype)


def mlp(p, h, gated: bool, dtype=None):
    up = mm(h, p["w_up"], dtype)
    if gated:
        act = jax.nn.silu(mm(h, p["w_gate"], dtype)) * up
    else:
        act = jax.nn.gelu(up, approximate=True)
    return mm(act, p["w_down"], dtype)


def hidden(params, m: Dict, tokens, dtype=None, remat: bool = True):
    """Final-norm hidden states (B, S, d) of token ids (B, S)."""
    positions = jnp.arange(tokens.shape[1])
    x = jnp.take(params["embed"], tokens, axis=0)

    def body(x, lp):
        return layer(lp, m, x, positions, dtype), None

    body = jax.checkpoint(body) if remat else body
    (blocks,) = params["blocks"]
    x, _ = jax.lax.scan(body, x, blocks)
    return rms_norm(x, params["final_ln"], m.get("norm_eps", 1e-6))


def loss(params, m: Dict, tokens, dtype=None, positions_used: Optional[int] = None):
    """Mean next-token cross entropy of tokens (B, S).  ``positions_used``
    keeps only the first that many predicted positions in the mean (the
    half-batch fault)."""
    h = hidden(params, m, tokens, dtype)
    logits = mm(h[:, :-1], params["lm_head"], dtype)
    tgt = tokens[:, 1:]
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0])
    if positions_used is not None:
        nll = nll[:, :positions_used]
    return jnp.mean(nll)
