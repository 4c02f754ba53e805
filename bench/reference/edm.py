"""Plain float32 reference of decentralized EDM training (the paper's
Algorithm 1) over a gossip ring, leaf by leaf, one agent at a time.

Per agent i and step t, with ψ(0) = x(0) and m(0) = 0::

    m  ← β m + (1 − β) g(x_i)
    ψ' ← x_i − α m
    φ  ← ψ' + x_i − ψ
    x_i ← Σ_j w_ij φ_j          (ring: w_ii = 1/2, w_i,i±1 = 1/4; two
                                agents: 1/2 each)

It imports nothing of the program.  Agents are held one tree each and
updated in place (donated), so three copies per agent and one gradient
are the most it holds: a whole-width model fits one chip beside nothing
else.  ``devices`` places agent i's trees on ``devices[i % len]``.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import lm


def ring_weights(n: int) -> np.ndarray:
    if n == 1:
        return np.ones((1, 1))
    if n == 2:
        return np.full((2, 2), 0.5)
    w = 0.5 * np.eye(n)
    for i in range(n):
        w[i, (i + 1) % n] += 0.25
        w[i, (i - 1) % n] += 0.25
    return w


@jax.jit
def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in jax.tree.leaves(tree)])


@jax.jit
def _diff_norms(a, b):
    return leaf_norms(jax.tree.map(lambda x, y: x - y.astype(jnp.float32),
                                   a, b))


@functools.partial(jax.jit, static_argnames=("alpha", "beta"),
                   donate_argnums=(0, 1, 2))
def _edm_local(x, m, psi, g, *, alpha, beta):
    """(x, m, ψ, g) → (φ, m', ψ'), x/m/ψ donated."""
    m2 = jax.tree.map(lambda a, b: beta * a + (1.0 - beta) * b, m, g)
    psi2 = jax.tree.map(lambda a, b: a - alpha * b, x, m2)
    phi = jax.tree.map(lambda a, b, c: a + b - c, psi2, x, psi)
    return phi, m2, psi2


@jax.jit
def _combine(trees, weights):
    return jax.tree.map(lambda *ls: sum(w * l for w, l in zip(weights, ls)),
                        *trees)


@functools.partial(jax.jit, static_argnames=("W",), donate_argnums=(0,))
def _mix_all(phis, *, W):
    """Every agent's x = Σ_j w_ij φ_j at once, the φ trees donated: the
    combine of agents that share one device."""
    return [jax.tree.map(lambda *ls: sum(w * l for w, l in zip(row, ls)
                                         if w), *phis) for row in W]


@functools.lru_cache(maxsize=None)
def _value_and_grad(m_json: str, dt, used):
    m = json.loads(m_json)

    @jax.jit
    def value_and_grad(x, tokens):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda p: lm.loss(p, m, tokens, dt, used))(x)

    return value_and_grad


def run(weights, m: Dict, batches: Sequence[jax.Array], *, alpha: float,
        beta: float, topology: str = "ring", steps: int = 3,
        matmul_dtype=None, half_batch: bool = False, gossip: bool = True,
        devices: Optional[List] = None) -> Dict[str, np.ndarray]:
    """Readings of ``steps`` EDM steps from ``weights`` (one agent's tree,
    any float type; every agent starts there).  ``batches[t]`` is step t's
    (A, b, S) token batch.  Returns the mean loss of each step, the
    per-(agent, leaf) norms of the first gradient, and of x(steps) − x(0).

    ``matmul_dtype``, ``half_batch`` and ``gossip=False`` give the control
    and the faults: matmuls in a lower type, the loss over half of each
    row's positions, and no exchange between agents."""
    if topology != "ring":
        raise ValueError(f"the reference knows the ring only, not {topology}")
    A = batches[0].shape[0]
    W = ring_weights(A) if gossip else np.eye(A)
    devs = devices or [None]
    put = lambda t, a: (t if devs[a % len(devs)] is None
                        else jax.device_put(t, devs[a % len(devs)]))
    S = batches[0].shape[-1]
    used = (S - 1) // 2 if half_batch else None
    dt = None if matmul_dtype is None else jnp.dtype(matmul_dtype)

    value_and_grad = _value_and_grad(json.dumps(m, sort_keys=True), dt,
                                     used)
    f32 = lambda t: jax.tree.map(lambda l: l.astype(jnp.float32), t)
    x = [put(f32(weights), a) for a in range(A)]
    psi = [put(f32(weights), a) for a in range(A)]
    mom = [put(jax.tree.map(jnp.zeros_like, f32(weights)), a)
           for a in range(A)]
    losses, grad_norms = [], None
    for t in range(steps):
        step_losses, norms = [], []
        for a in range(A):
            tok = put(batches[t][a], a)
            val, g = value_and_grad(x[a], tok)
            step_losses.append(float(val))
            if t == 0:
                norms.append(np.asarray(leaf_norms(g)))
            x[a], mom[a], psi[a] = _edm_local(x[a], mom[a], psi[a], g,
                                              alpha=alpha, beta=beta)
            del g
        losses.append(float(np.mean(step_losses)))
        if t == 0:
            grad_norms = np.stack(norms)
        if len(devs) == 1:
            x = _mix_all(x, W=tuple(tuple(float(w) for w in r) for r in W))
            continue
        phi = x
        x = []
        for a in range(A):
            nb = [j for j in range(A) if W[a, j] != 0.0]
            x.append(_combine([put(phi[j], a) for j in nb],
                              [float(W[a, j]) for j in nb]))
        del phi
    change = np.stack([np.asarray(_diff_norms(x[a], put(weights, a)))
                       for a in range(A)])
    return {"losses": np.asarray(losses), "grad_norms": grad_norms,
            "change_norms": change}
