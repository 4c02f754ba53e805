"""The consensus kernel (``kernels/edm_update.py`` ``bus_consensus_flat``,
``kernels/ops.py`` ``bus_consensus``) and where the train step uses it
(``train/trainer.py`` ``step_consensus``), in interpret mode on the CPU:

* the kernel equals ``core.metrics.bus_consensus`` and the per-leaf
  ``consensus_distance`` of a packed tree, for 1, 2 and 4 agents and two
  tile heights; identical copies read exactly 0, and zero pad rows add 0;
* both step bodies, with no mesh and on a one-device mesh, run the kernel
  and give the consensus of the XLA expression, with loss, grad norm and
  state unchanged;
* four agents on four devices (agents split) keep the XLA expression.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, RunConfig
from repro.core import bus, metrics
from repro.data import SyntheticLM
from repro.kernels import ops
from repro.launch.mesh import gossip_agent_axes, make_gossip_mesh
from repro.models import build_model
from repro.train import (build_train_step, init_state, make_gossip_schedule,
                         step_consensus, trainer)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ,
       "PYTHONPATH": os.path.join(REPO, "src")
       + (os.pathsep + os.environ["PYTHONPATH"]
          if os.environ.get("PYTHONPATH") else "")}


def _tree(A, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return {"a": jax.random.normal(ks[0], (A, 17, 9)),
            "b": jax.random.normal(ks[1], (A, 131)),
            "c": 1.0 + 0.01 * jax.random.normal(ks[2], (A, 40, 128))}


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block_rows", [8, 512])
@pytest.mark.parametrize("A", [1, 2, 4])
def test_kernel_matches_the_xla_expression(A, block_rows):
    tree = _tree(A)
    layout = bus.make_layout(tree, block_rows=block_rows)
    packed = bus.pack_tree(layout, tree)
    got = float(ops.bus_consensus(packed, block_rows=block_rows))
    if A == 1:
        assert got == 0.0
    else:
        np.testing.assert_allclose(got, float(metrics.bus_consensus(packed)),
                                   rtol=1e-5)
        np.testing.assert_allclose(got,
                                   float(metrics.consensus_distance(tree)),
                                   rtol=1e-5)
    # every agent holding the same copy reads exactly 0
    same = jnp.broadcast_to(packed[:1], packed.shape)
    assert float(ops.bus_consensus(same, block_rows=block_rows)) == 0.0
    # zero pad rows (a whole tile more) deviate by 0
    padded = jnp.concatenate(
        [packed, jnp.zeros((A, block_rows, 128), packed.dtype)], axis=1)
    assert float(ops.bus_consensus(padded, block_rows=block_rows)) == got


def test_kernel_returns_one_lane_partial_per_tile():
    from repro.kernels.edm_update import bus_consensus_flat
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 128))
    parts = bus_consensus_flat(x, block_rows=16, interpret=True)
    assert parts.shape == (4, 1, 128) and parts.dtype == jnp.float32
    # tile i's lane j: Σ_a Σ_rows of the tile (x_a − x̄)² in that lane
    dev = np.asarray(x) - np.asarray(x).mean(0, keepdims=True)
    want = (dev ** 2).sum(0).reshape(4, 16, 128).sum(1)
    np.testing.assert_allclose(np.asarray(parts[:, 0]), want, rtol=1e-5)


# ---------------------------------------------------------------------------
# the choice of path
# ---------------------------------------------------------------------------

def test_step_consensus_chooses_by_where_the_agents_live():
    kw = dict(block_rows=8)
    # unfused, or rows sharded over a mesh axis: the XLA expression
    assert step_consensus(None, None, None, use_fused_kernel=False,
                          **kw) is metrics.bus_consensus
    assert step_consensus(None, "pod", "data", use_fused_kernel=True,
                          **kw) is metrics.bus_consensus
    # no mesh: the bare kernel
    assert step_consensus(None, None, None, use_fused_kernel=True,
                          **kw).func is ops.bus_consensus
    # a one-device mesh holding both agents: the kernel under shard_map
    mesh = make_gossip_mesh(2, agents_per_device=2)
    fn = step_consensus(mesh, gossip_agent_axes(mesh), None,
                        use_fused_kernel=True, **kw)
    assert fn is not metrics.bus_consensus
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 128))
    np.testing.assert_allclose(float(jax.jit(fn)(x)),
                               float(metrics.bus_consensus(x)), rtol=1e-5)


# ---------------------------------------------------------------------------
# the train step
# ---------------------------------------------------------------------------

A = 4


def _model():
    cfg = ModelConfig(name="cons-tiny", family="dense", n_layers=1,
                      d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                      vocab_size=64, dtype="float32")
    return build_model(cfg)


def _run(overlap, engine):
    return RunConfig(global_batch=A, seq_len=8, algorithm="edm", alpha=0.1,
                     topology="ring", gossip_engine=engine, packed_bus=True,
                     agents_per_device=A, overlap=overlap, remat=False)


def _trajectory(model, run, mesh, n=3):
    sched = make_gossip_schedule(run, A)
    axes = gossip_agent_axes(mesh) if mesh is not None else None
    step = jax.jit(build_train_step(model, run, sched, use_fused_kernel=True,
                                    mesh=mesh, agent_axes=axes))
    batch = SyntheticLM(vocab_size=64, seq_len=8, n_agents=A).sample(
        jax.random.PRNGKey(1), 1)
    state = init_state(model, run, A, jax.random.PRNGKey(0))
    jaxpr = str(jax.make_jaxpr(step)(state, batch))
    traj = []
    for _ in range(n):
        state, m = step(state, batch)
        traj.append({k: float(v) for k, v in m.items()})
    return jaxpr, traj, state


@pytest.mark.parametrize("overlap", ["off", "delayed"])
@pytest.mark.parametrize("on_mesh", [False, True], ids=["no-mesh", "mesh1"])
def test_step_consensus_matches_the_xla_expression(overlap, on_mesh,
                                                   monkeypatch):
    model = _model()
    mesh = make_gossip_mesh(A, agents_per_device=A) if on_mesh else None
    run = _run(overlap, "ppermute" if on_mesh else "dense")
    jaxpr, traj, state = _trajectory(model, run, mesh)
    assert "name=bus_consensus" in jaxpr
    with monkeypatch.context() as mp:
        mp.setattr(trainer, "step_consensus",
                   lambda *a, **k: metrics.bus_consensus)
        jaxpr0, traj0, state0 = _trajectory(model, run, mesh)
    assert "name=bus_consensus" not in jaxpr0
    for m, m0 in zip(traj, traj0):
        assert m["loss"] == m0["loss"]
        assert m["grad_norm"] == m0["grad_norm"]
        np.testing.assert_allclose(m["consensus"], m0["consensus"],
                                   rtol=1e-5)
    assert traj[-1]["consensus"] > 0
    for k in ("params", "opt"):
        for a, b in zip(jax.tree.leaves(state[k]), jax.tree.leaves(state0[k])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


_SPLIT_CODE = r"""
import jax, numpy as np
from repro.configs.base import ModelConfig, RunConfig
from repro.core import metrics
from repro.data import SyntheticLM
from repro.launch.mesh import gossip_agent_axes, make_gossip_mesh
from repro.models import build_model
from repro.train import (build_train_step, init_state, make_gossip_schedule,
                         step_consensus)

A = 4
mesh = make_gossip_mesh(A)
assert mesh.devices.size == 4, mesh
axes = gossip_agent_axes(mesh)
assert step_consensus(mesh, axes, None, use_fused_kernel=True,
                      block_rows=8) is metrics.bus_consensus
model = build_model(ModelConfig(name="cons-split", family="dense",
                                n_layers=1, d_model=32, n_heads=2,
                                n_kv_heads=2, d_ff=64, vocab_size=64,
                                dtype="float32"))
run = RunConfig(global_batch=A, seq_len=8, algorithm="edm", alpha=0.1,
                topology="ring", gossip_engine="ppermute", packed_bus=True,
                remat=False)
step = jax.jit(build_train_step(model, run, make_gossip_schedule(run, A),
                                use_fused_kernel=True, mesh=mesh,
                                agent_axes=axes))
batch = SyntheticLM(vocab_size=64, seq_len=8, n_agents=A).sample(
    jax.random.PRNGKey(1), 1)
state = init_state(model, run, A, jax.random.PRNGKey(0))
jaxpr = str(jax.make_jaxpr(step)(state, batch))
assert "name=bus_consensus" not in jaxpr
assert "name=edm_update" in jaxpr
for _ in range(2):
    state, m = step(state, batch)
want = float(metrics.bus_consensus(np.asarray(state["params"])))
np.testing.assert_allclose(float(m["consensus"]), want, rtol=1e-6)
assert want > 0
print("SPLIT_AGENTS_XLA_OK")
"""


def test_agents_split_across_devices_keep_the_xla_expression():
    env = {**ENV, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", _SPLIT_CODE], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=400)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "SPLIT_AGENTS_XLA_OK" in r.stdout
