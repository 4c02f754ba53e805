"""Seeded inputs: keys from any whole-number seed, and weights made on the
device in one jitted call for the shapes of the program's parameter
tree.

Each weight is a whole number in [-128, 128) times a power of two: exact
in bfloat16 and in float32, so every program that makes them, however XLA
fuses it, makes the same bits."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NORM_KEYS = ("ln", "final_ln", "q_norm", "k_norm")
BIAS_KEYS = ("bq", "bk", "bv")


def seed_key(seed: int, stream: int):
    """Key of one stream (weights, data, ...) of a run's seed.  Seeds wider
    than 32 bits keep their high bits (``PRNGKey`` alone drops them)."""
    seed = int(seed)
    base = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    base = jax.random.fold_in(base, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(base, stream)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def std_of(name: str, shape) -> float:
    """Norm offsets and biases small, matrices at 1/√fan-in (the embedding
    over its width)."""
    if name in NORM_KEYS:
        return 0.1
    if name in BIAS_KEYS:
        return 0.02
    fan_in = shape[-1] if name == "embed" else shape[-2]
    return float(1.0 / np.sqrt(fan_in))


def make_weights(shapes, key):
    """A tree like ``shapes`` (``jax.ShapeDtypeStruct`` leaves), each leaf
    uniform with about :func:`std_of`'s deviation (the step a power of
    two), in the leaf's own type."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, s) in enumerate(flat):
        std = std_of(_leaf_name(path), s.shape)
        step = float(2.0 ** np.round(np.log2(std * np.sqrt(3.0) / 128.0)))
        k = jax.random.fold_in(key, i)
        ints = jax.random.randint(k, s.shape, -128, 128, jnp.int32)
        out.append((ints.astype(jnp.float32) * step).astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)
