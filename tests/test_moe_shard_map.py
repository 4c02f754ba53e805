"""shard_map expert-local MoE (§Perf P1 winner) vs plain-path oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.launch.mesh import make_mesh
from repro.models.moe import apply_moe, apply_moe_shard_map, init_moe


def _cfg(E, k, shared, cf=8.0):
    return ModelConfig(name="m", family="moe", n_layers=1, d_model=32,
                       n_heads=2, n_kv_heads=2, d_ff=48, vocab_size=64,
                       n_experts=E, experts_per_token=k,
                       n_shared_experts=shared, capacity_factor=cf,
                       dtype="float32")


@pytest.mark.parametrize("E,k,shared", [(4, 1, 0), (8, 2, 1), (16, 4, 2)])
def test_shard_map_moe_matches_plain(E, k, shared):
    cfg = _cfg(E, k, shared)
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    ref, aux_ref = apply_moe(p, cfg, x, 1e-6)
    mesh = make_mesh((1, 1), ("data", "model"))
    got, aux = jax.jit(
        lambda p, x: apply_moe_shard_map(p, cfg, x, 1e-6, mesh))(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=3e-4, atol=3e-5)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-4)


def test_shard_map_moe_grad_finite():
    """The shard_map path must be differentiable (training usability)."""
    cfg = _cfg(4, 2, 1)
    p = init_moe(jax.random.PRNGKey(2), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 32))
    mesh = make_mesh((1, 1), ("data", "model"))

    def loss(p):
        y, aux = apply_moe_shard_map(p, cfg, x, 1e-6, mesh)
        return jnp.sum(y ** 2) + aux

    g = jax.grad(loss)(p)
    assert all(bool(jnp.all(jnp.isfinite(l))) for l in jax.tree.leaves(g))
    assert float(jnp.max(jnp.abs(g["w_gate"]))) > 0
