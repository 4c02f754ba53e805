"""Plain float32 reference of a DeepSeek-V2 decoder at one chip's expert
share, and of decentralized EDM training over it.

Written from the published block (arXiv:2405.04434 §2) in straightforward
``jax.numpy``; it imports nothing of the program.  It reads the parameter
tree by its key names: ``embed``, ``lead`` (one tree per leading dense
layer), ``blocks`` (layer leaves stacked on a leading axis), ``final_ln``,
``lm_head``; per layer ``attn`` with ``ln``/``wq``/``wkv_a``/
``latent.ln``/``wkv_b``/``wo``, and ``ffn`` (``ln``/``w_gate``/``w_up``/
``w_down``) or ``moe`` (``ln``, ``router``, the held experts'
``w_gate``/``w_up``/``w_down`` and ``shared``).

- MLA, h of (S, d): q = h·W_q split per head into a no-rope and a rope
  part; [c, k_pe] = h·W_kva, c = RMSNorm(c); [k_nope, v] = c·W_kvb per
  head; rope on q_pe per head and on k_pe once, shared by every head;
  causal softmax(q·kᵀ·s)·v, then ·W_o.  s = qk_head_dim^-1/2 · m², m =
  0.1 · mscale_all_dim · ln(factor) + 1.
- YaRN frequencies on the rope dimensions, i < d_r/2: f_i = θ^(-2i/d_r);
  low/high = the floor/ceil correction dimensions of β_fast/β_slow
  rotations over the original context; r_i = clip((i − low)/(high − low),
  0, 1); inv_freq_i = f_i (1 − r_i) + f_i/factor · r_i.  Rope rotates the
  two halves of the rope part.
- MoE: s = softmax(h·W_r) over all experts (f32); the top k by s; gate
  weights the top-k s (renormalised only where ``norm_topk_prob``) times
  ``routed_scaling_factor``; y = Σ over the top k held here of gate ·
  SwiGLU_e(h) + SwiGLU_shared(h).  Every held expert is computed densely
  on every token and weighted by its gate (zero where not chosen): no
  assignment is dropped.  Sequence-wise auxiliary loss over all experts:
  per sequence Σ_i f_i P_i, f_i = E/(k S) × assignments to i, P_i the mean
  score of i; its mean over the batch times ``router_aux_coef``, summed
  over layers, is added to the loss.
- RMSNorm with a ``(1 + w)`` scale, pre-norm blocks, untied head, mean
  next-token cross entropy.

Every matmul runs at ``precision=HIGHEST`` (under
``jax.default_matmul_precision("highest")`` besides); ``matmul_dtype``
rounds both inputs of every matmul, score and softmax weight to a lower
type, as ``lm.py``'s control does.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import edm
from .lm import HIGHEST, _round, mm, rms_norm

Q_BLOCK = 512


def yarn_inv_freq(m: Dict) -> jax.Array:
    dim, base = m["qk_rope_head_dim"], m["rope_theta"]
    f = base ** -(np.arange(dim // 2, dtype=np.float64) * 2 / dim)
    factor = m.get("rope_factor", 1.0)
    if factor <= 1:
        return jnp.asarray(f, jnp.float32)
    orig = m["rope_original_max_pos"]

    def corr(n_rot):
        return dim * math.log(orig / (n_rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(m["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr(m["yarn_beta_slow"])), dim - 1)
    r = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return jnp.asarray(f * (1 - r) + f / factor * r, jnp.float32)


def softmax_scale(m: Dict) -> float:
    dq = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    factor = m.get("rope_factor", 1.0)
    ms = (0.1 * m["yarn_mscale_all_dim"] * math.log(factor) + 1.0
          if factor > 1 else 1.0)
    return dq ** -0.5 * ms * ms


def rope(x, positions, inv_freq):
    """x: (B, S, H, d_r); rotate its two halves."""
    half = x.shape[-1] // 2
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.checkpoint, static_argnums=(5, 6))
def _attend(q, k, v, qpos, kpos, scale, dtype):
    """Causal softmax attention of query rows at ``qpos`` over all keys.
    q: (B, Sq, H, dq); k: (B, Sk, H, dq); v: (B, Sk, H, dv)."""
    s = jnp.einsum("bqhd,bshd->bhqs", _round(q, dtype), _round(k, dtype),
                   precision=HIGHEST) * scale
    s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqs,bshd->bqhd", _round(w, dtype), _round(v, dtype),
                      precision=HIGHEST)


def mla(p, m: Dict, h, positions, dtype=None, q_block: int = Q_BLOCK):
    B, S, _ = h.shape
    H, dn, dr = m["n_heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    r, dv = m["kv_lora_rank"], m["v_head_dim"]
    inv = yarn_inv_freq(m)
    q = mm(h, p["wq"], dtype).reshape(B, S, H, dn + dr)
    kv_a = mm(h, p["wkv_a"], dtype)
    c = rms_norm(kv_a[..., :r], p["latent"]["ln"], m["norm_eps"])
    kv = mm(c, p["wkv_b"], dtype).reshape(B, S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], positions, inv)], -1)
    k_pe = rope(kv_a[:, :, None, r:], positions, inv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (B, S, H, dr))],
                        -1)
    v = kv[..., dn:]
    scale = softmax_scale(m)
    if q_block and S > q_block:
        nb = S // q_block
        qb = jnp.moveaxis(q.reshape(B, nb, q_block, H, dn + dr), 1, 0)
        pb = positions.reshape(nb, q_block)
        o = jax.lax.map(lambda a: _attend(a[0], k, v, a[1], positions, scale,
                                          dtype), (qb, pb))
        o = jnp.moveaxis(o, 0, 1).reshape(B, S, H, dv)
    else:
        o = _attend(q, k, v, positions, positions, scale, dtype)
    return mm(o.reshape(B, S, H * dv), p["wo"], dtype)


def swiglu(p, h, dtype=None):
    return mm(jax.nn.silu(mm(h, p["w_gate"], dtype)) * mm(h, p["w_up"], dtype),
              p["w_down"], dtype)


def moe(p, m: Dict, h, dtype=None):
    """(y, auxiliary loss before its coefficient) of the held experts and
    the shared ones, for normed hidden states h (B, S, d)."""
    B, S, d = h.shape
    E, k = m["n_experts"], m["experts_per_token"]
    assert m["router_aux"] == "seq", m["router_aux"]
    held = m.get("n_experts_held") or E
    first = m.get("expert_first", 0)
    flat = h.reshape(B * S, d)
    scores = jax.nn.softmax(jnp.matmul(flat, p["router"], precision=HIGHEST),
                            axis=-1)
    top, idx = jax.lax.top_k(scores, k)
    if m.get("norm_topk_prob", True):
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * m.get("routed_scaling_factor", 1.0)
    gate = jnp.zeros_like(scores).at[jnp.arange(B * S)[:, None], idx].set(top)
    y = jnp.zeros_like(flat)
    for e in range(held):
        we = {n: p[n][e] for n in ("w_gate", "w_up", "w_down")}
        y = y + gate[:, first + e, None] * swiglu(we, flat, dtype)
    y = y.reshape(B, S, d) + swiglu(p["shared"], h, dtype)
    counts = jax.nn.one_hot(idx.reshape(B, S * k), E).sum(1)
    aux = jnp.mean(jnp.sum(counts * (E / (k * S))
                           * scores.reshape(B, S, E).mean(1), -1))
    return y, aux


def layer(lp, m: Dict, x, positions, dtype=None):
    """One pre-norm block: MLA then a dense MLP or the MoE layer, each with
    its residual; returns (x, auxiliary loss)."""
    eps = m["norm_eps"]
    x = x + mla(lp["attn"], m, rms_norm(x, lp["attn"]["ln"], eps), positions,
                dtype)
    if "ffn" in lp:
        return x + swiglu(lp["ffn"], rms_norm(x, lp["ffn"]["ln"], eps),
                          dtype), jnp.zeros((), jnp.float32)
    y, aux = moe(lp["moe"], m, rms_norm(x, lp["moe"]["ln"], eps), dtype)
    return x + y, aux


def loss(params, m: Dict, tokens, dtype=None,
         positions_used: Optional[int] = None):
    """Mean next-token cross entropy of tokens (B, S) plus the auxiliary
    loss; ``positions_used`` keeps the first that many predicted positions
    in the mean (the half-batch fault)."""
    positions = jnp.arange(tokens.shape[1])
    x = jnp.take(params["embed"], tokens, axis=0)
    aux = jnp.zeros((), jnp.float32)
    for lp in params.get("lead", ()):
        x, a = jax.checkpoint(
            lambda x, lp: layer(lp, m, x, positions, dtype))(x, lp)
        aux = aux + a

    def body(carry, lp):
        x, aux = carry
        x, a = layer(lp, m, x, positions, dtype)
        return (x, aux + a), None

    (blocks,) = params["blocks"]
    (x, aux), _ = jax.lax.scan(jax.checkpoint(body), (x, aux), blocks)
    h = rms_norm(x, params["final_ln"], m["norm_eps"])
    logits = mm(h[:, :-1], params["lm_head"], dtype)
    tgt = tokens[:, 1:]
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0])
    if positions_used is not None:
        nll = nll[:, :positions_used]
    return jnp.mean(nll) + m["router_aux_coef"] * aux


@functools.lru_cache(maxsize=None)
def _value_and_grad(m_json: str, dt, used):
    m = json.loads(m_json)

    @jax.jit
    def value_and_grad(x, tokens):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(lambda p: loss(p, m, tokens, dt,
                                                     used))(x)

    return value_and_grad


def run(weights, m: Dict, batches: Sequence[jax.Array], *, alpha: float,
        beta: float, topology: str = "ring", steps: int = 3,
        matmul_dtype=None, half_batch: bool = False, gossip: bool = True,
        devices: Optional[List] = None) -> Dict[str, np.ndarray]:
    """``edm.run``'s readings, over this model: the mean loss of each of
    ``steps`` EDM steps from ``weights``, the per-(agent, leaf) norms of
    the first gradient and of x(steps) − x(0).  The variants are
    ``edm.run``'s (a lower matmul type, half of each row's positions, no
    exchange)."""
    if topology != "ring":
        raise ValueError(f"the reference knows the ring only, not {topology}")
    A = batches[0].shape[0]
    W = edm.ring_weights(A) if gossip else np.eye(A)
    devs = devices or [None]
    put = lambda t, a: (t if devs[a % len(devs)] is None
                        else jax.device_put(t, devs[a % len(devs)]))
    S = batches[0].shape[-1]
    used = (S - 1) // 2 if half_batch else None
    dt = None if matmul_dtype is None else jnp.dtype(matmul_dtype)
    value_and_grad = _value_and_grad(json.dumps(m, sort_keys=True), dt, used)
    # a copy of every leaf, f32 ones (the router) too: x, ψ and m are
    # donated leaf by leaf
    f32 = lambda t: jax.tree.map(
        lambda l: jnp.array(l, jnp.float32, copy=True), t)
    x = [put(f32(weights), a) for a in range(A)]
    psi = [put(f32(weights), a) for a in range(A)]
    mom = [put(jax.tree.map(jnp.zeros_like, f32(weights)), a)
           for a in range(A)]
    losses, grad_norms = [], None
    for t in range(steps):
        step_losses, norms = [], []
        for a in range(A):
            val, g = value_and_grad(x[a], put(batches[t][a], a))
            step_losses.append(float(val))
            if t == 0:
                norms.append(np.asarray(edm.leaf_norms(g)))
            x[a], mom[a], psi[a] = edm._edm_local(x[a], mom[a], psi[a], g,
                                                  alpha=alpha, beta=beta)
            del g
        losses.append(float(np.mean(step_losses)))
        if t == 0:
            grad_norms = np.stack(norms)
        if len(devs) == 1:
            x = edm._mix_all(x, W=tuple(tuple(float(w) for w in r)
                                        for r in W))
            continue
        phi, x = x, []
        for a in range(A):
            nb = [j for j in range(A) if W[a, j] != 0.0]
            x.append(edm._combine([put(phi[j], a) for j in nb],
                                  [float(W[a, j]) for j in nb]))
        del phi
    change = np.stack([np.asarray(edm._diff_norms(x[a], put(weights, a)))
                       for a in range(A)])
    return {"losses": np.asarray(losses), "grad_norms": grad_norms,
            "change_norms": change}
