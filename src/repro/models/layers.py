"""Shared neural building blocks (pure-JAX, pytree params)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "rms_norm", "rope", "dense_init", "swiglu", "gelu_mlp",
    "init_dense_ffn", "apply_dense_ffn",
]


def dense_init(key, shape, in_axis=0, dtype=jnp.float32, scale=1.0):
    """Truncated-normal fan-in init."""
    fan_in = shape[in_axis] if isinstance(in_axis, int) else int(
        np.prod([shape[a] for a in in_axis]))
    std = scale / np.sqrt(fan_in)
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         inv_freq=None) -> jax.Array:
    """Rotary embedding over the two halves of each head.  x: (..., S, H,
    hd); positions: (..., S); ``inv_freq`` (hd/2,) replaces θ's own
    frequencies (YaRN)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = (1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
             if inv_freq is None else inv_freq)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]   # (..., S, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# FFN (dense)
# --------------------------------------------------------------------------

def init_dense_ffn(key, d_model: int, d_ff: int, gated: bool, dtype):
    ks = jax.random.split(key, 3)
    p = {
        "ln": jnp.zeros((d_model,), dtype),
        "w_up": dense_init(ks[0], (d_model, d_ff), 0, dtype),
        "w_down": dense_init(ks[1], (d_ff, d_model), 0, dtype),
    }
    if gated:
        p["w_gate"] = dense_init(ks[2], (d_model, d_ff), 0, dtype)
    return p


def swiglu(x, w_gate, w_up, w_down):
    g = jax.nn.silu(x @ w_gate)
    return (g * (x @ w_up)) @ w_down


def gelu_mlp(x, w_up, w_down):
    return jax.nn.gelu(x @ w_up) @ w_down


def apply_dense_ffn(p, x, eps: float):
    h = rms_norm(x, p["ln"], eps)
    if "w_gate" in p:
        return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    return x + gelu_mlp(h, p["w_up"], p["w_down"])
