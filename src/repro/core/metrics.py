"""Diagnostics used throughout the paper's analysis and our experiments."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

__all__ = [
    "agent_mean",
    "bus_consensus",
    "bus_grad_norm",
    "consensus_distance",
    "grad_norm_at_mean",
    "heterogeneity_zeta2",
    "tree_sqnorm",
]


def tree_sqnorm(tree: Any) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)


def agent_mean(tree: Any) -> Any:
    """x̄ = (1/n) Σ_i x_i  over the leading agent axis."""
    return jax.tree.map(lambda l: jnp.mean(l, axis=0, keepdims=True), tree)


def consensus_distance(tree: Any) -> jax.Array:
    """‖X − X̄‖²_F — the paper's deviation term E‖P_I X‖²."""
    mean = agent_mean(tree)
    return tree_sqnorm(jax.tree.map(lambda x, m: x - m, tree, mean))


# ---------------------------------------------------------------------------
# packed-bus diagnostics (DESIGN §5/§13): the bus's pad elements are zero by
# layout contract, so a reduction over the (A, rows, 128) superbuffer equals
# the per-leaf reduction over the logical tree — no unpack, no per-leaf
# reduction kernels on the metrics path.
# ---------------------------------------------------------------------------

def bus_consensus(bus: jax.Array) -> jax.Array:
    """‖X − X̄‖²_F over a packed ``(A, rows, 128)`` bus (pad rows deviate by
    0, so this equals the logical-tree consensus).

    The XLA expression, for any placement of the agents.  XLA cannot fuse
    the mean over the agent axis into the reduction that consumes it: on a
    TPU it makes the mean, broadcasts it back to the bus's shape and reads
    the bus again, several bus-sized passes in all.  Where one device holds
    every agent's copy and the fused kernels are on, the train step takes
    the one-pass Pallas kernel ``repro.kernels.ops.bus_consensus`` instead
    (``repro.train.trainer.step_consensus``)."""
    dev = bus - jnp.mean(bus, axis=0, keepdims=True)
    return jnp.sum(jnp.square(dev.astype(jnp.float32)))


def bus_grad_norm(g_bus: jax.Array) -> jax.Array:
    """Global gradient norm over a packed gradient bus in one reduction, a
    single read of the bus (equals the per-leaf sqrt-of-sum over the
    unpacked grads: the bus is f32 and its pads are zero)."""
    return jnp.sqrt(jnp.sum(jnp.square(g_bus.astype(jnp.float32))))


def grad_norm_at_mean(grad_fn, params: Any) -> jax.Array:
    """‖∇f(x̄)‖² where grad_fn maps a single-agent pytree to its gradient."""
    mean = jax.tree.map(lambda l: jnp.mean(l, axis=0), params)
    return tree_sqnorm(grad_fn(mean))


def heterogeneity_zeta2(per_agent_grads: Any) -> jax.Array:
    """ζ² = (1/n) Σ_i ‖∇f_i − ∇f‖²  evaluated at a common point
    (per_agent_grads leaves: (A, ...))."""
    mean = agent_mean(per_agent_grads)
    dev = jax.tree.map(lambda g, m: g - m, per_agent_grads, mean)
    n = jax.tree.leaves(per_agent_grads)[0].shape[0]
    return consensus_distance_from_dev(dev) / n


def consensus_distance_from_dev(dev: Any) -> jax.Array:
    return tree_sqnorm(dev)
