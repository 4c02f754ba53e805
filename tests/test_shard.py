"""Shard-resident gossip (DESIGN §7): FSDP-sharded bus + shard-local
ppermute engine.

* layout math: ``shards=S`` rounds rows to ``block_rows·S`` so each shard's
  row block is griddable; the layout cache keys on the shard count;
* config resolution: ``packed_bus`` composes with ``agents="pod"`` and
  ``state_specs`` emits the ``P('pod', 'data')`` row-sharded bus specs;
* sharded ``ppermute == dense`` — on a real 2-pod × 4-shard (and 4 × 2)
  host mesh, the sharded engine matches both the plain dense oracle and the
  shard-resident all-gather oracle (``mix_dense_sharded``) across
  topologies × schedules × {fused, unfused} (8-device subprocess);
* HLO acceptance for the composed ``agents="pod"`` + packed-bus train step
  (sync and delayed overlap, fused and unfused): exactly one bus-shaped
  ``collective-permute`` per nonzero gossip term, and every one of them
  carries the **shard-local** ``(1, rows/S, 128)`` payload — an all-gather
  feeding a gossip permute would make the operand full-rows, so the shape
  pin is the "no all-gather ever precedes a gossip permute" guarantee in
  operand-dependency form (wire bytes per device drop by exactly S);
* sharding-independent checkpoints: save sharded → load gathered and
  vice versa (different shard counts pad rows differently; the on-disk
  logical tree is identical).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RunConfig
from repro.core import bus
from repro.train import state_specs, use_packed_bus

jax.config.update("jax_enable_x64", False)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ,
       "PYTHONPATH": os.path.join(REPO, "src")
       + (os.pathsep + os.environ["PYTHONPATH"]
          if os.environ.get("PYTHONPATH") else "")}


def _tree(A, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return {
        "emb": jax.random.normal(ks[0], (A, 17, 9)),
        "w": jax.random.normal(ks[1], (A, 33)),
        "head": jax.random.normal(ks[2], (A, 129)),
    }


# ---------------------------------------------------------------------------
# layout: shard rounding + cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shards", [1, 2, 4])
def test_layout_shard_rounding(shards):
    tree = _tree(2)
    layout = bus.make_layout(tree, block_rows=8, shards=shards)
    assert layout.shards == shards
    assert layout.rows % (8 * shards) == 0
    assert layout.shard_rows * shards == layout.rows
    assert layout.shard_rows % layout.block_rows == 0
    # logical content is shard-count-independent: pack under any shard
    # layout and the logical elements land at the same offsets
    packed = bus.pack_tree(layout, tree)
    assert packed.shape == (2, layout.rows, 128)
    back = bus.unpack_tree(layout, packed)
    for w, g in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_layout_cache_keys_on_shards():
    t = _tree(4)
    l1 = bus.make_layout(t, block_rows=8, shards=1)
    l2 = bus.make_layout(t, block_rows=8, shards=4)
    assert l1 is not l2
    assert bus.make_layout(_tree(4, key=7), block_rows=8, shards=4) is l2
    # a sharded layout never has FEWER rows than the unsharded one
    assert l2.rows >= l1.rows


# ---------------------------------------------------------------------------
# config resolution + specs
# ---------------------------------------------------------------------------

def test_packed_bus_composes_with_pod_agents():
    assert use_packed_bus(RunConfig(algorithm="edm",
                                    gossip_engine="ppermute", agents="pod"))
    assert use_packed_bus(RunConfig(algorithm="edm", packed_bus=True,
                                    agents="pod"))
    with pytest.raises(AssertionError):
        use_packed_bus(RunConfig(algorithm="dsgd", packed_bus=True,
                                 agents="pod"))


def test_state_specs_pod_bus_row_sharded():
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.train import init_state

    model = build_model(get_smoke_config("smollm_360m"))
    run = RunConfig(algorithm="edm", agents="pod", packed_bus=True,
                    overlap="delayed", remat=False)
    specs = state_specs(model, run, multi_pod=True)
    assert specs["params"] == P("pod", "data")
    assert specs["opt"]["m"] == P("pod", "data")
    assert specs["pipeline"]["slot"] == P(None, "pod", "data")
    assert specs["pipeline"]["parity"] == P()
    # structures line up with the real state (tree.map raises on mismatch)
    state = jax.eval_shape(
        lambda: init_state(model, run, 2, jax.random.PRNGKey(0), shards=4))
    jax.tree.map(lambda sds, sp: None, state, specs,
                 is_leaf=lambda x: isinstance(x, P))
    # single-pod fallback replicates the agent axis but keeps FSDP rows
    assert state_specs(model, run, multi_pod=False)["params"] == \
        P(None, "data")


def test_gossip_mesh_sharded_needs_devices():
    from repro.launch.mesh import make_gossip_mesh

    n_dev = jax.device_count()
    with pytest.raises(AssertionError):
        make_gossip_mesh(n_dev, pods=n_dev, shards=8)  # 8× too many
    with pytest.raises(AssertionError):
        make_gossip_mesh(4, pods=2, shards=2)  # pods must equal n_agents


# ---------------------------------------------------------------------------
# checkpoint: sharding-independence at the layout level (single device —
# a shards=4 layout pads differently from shards=1, yet the on-disk
# logical tree is identical and loads into either)
# ---------------------------------------------------------------------------

def test_checkpoint_shard_layout_independent(tmp_path):
    from repro.train import checkpoint

    tree = _tree(4)
    l_flat = bus.make_layout(tree, block_rows=8, shards=1)
    l_shard = bus.make_layout(tree, block_rows=8, shards=4)
    assert l_flat.rows != l_shard.rows or l_flat is not l_shard
    packed_s = bus.pack_tree(l_shard, tree)

    p = str(tmp_path / "sharded.npz")
    checkpoint.save(p, packed_s, layout=l_shard)
    # sharded-layout save restores into the flat layout...
    flat_bus = checkpoint.load(p, jnp.zeros((4, l_flat.rows, 128)),
                               layout=l_flat)
    np.testing.assert_array_equal(np.asarray(bus.unpack_tree(l_flat,
                                                             flat_bus)["w"]),
                                  np.asarray(tree["w"]))
    # ...and a flat save restores into the sharded layout
    p2 = str(tmp_path / "flat.npz")
    checkpoint.save(p2, bus.pack_tree(l_flat, tree), layout=l_flat)
    shard_bus = checkpoint.load(p2, jnp.zeros_like(packed_s), layout=l_shard)
    np.testing.assert_array_equal(np.asarray(shard_bus),
                                  np.asarray(packed_s))


# ---------------------------------------------------------------------------
# sharded ppermute == dense + HLO + checkpoint on a real pods × shards mesh
# (8-device subprocess)
# ---------------------------------------------------------------------------

_SHARD_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import re
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import (RoundRobinExp, StaticSchedule, exp_graph,
                        make_mixer, make_schedule_mixer, mix_dense,
                        mix_dense_sharded, ring)
from repro.launch.mesh import gossip_agent_axes, make_gossip_mesh

for A, S in ((2, 4), (4, 2)):
    mesh = make_gossip_mesh(A, pods=A, shards=S)
    assert gossip_agent_axes(mesh, sharded=True) == "pod"
    rows = 64
    x = jax.random.normal(jax.random.PRNGKey(0), (A, rows, 128))
    xs = jax.device_put(x, NamedSharding(mesh, P("pod", "data")))
    for topo in (ring(A), exp_graph(A)):
        for fused in (False, True):
            mix = make_mixer(topo, "ppermute", mesh=mesh, agent_axes="pod",
                             use_fused_kernel=fused, shard_axes="data")
            got = np.asarray(jax.jit(mix)(xs))
            want = np.asarray(mix_dense(topo, x))
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                err_msg=f"{A}x{S} {topo.name} fused={fused}")
            oracle = np.asarray(mix_dense_sharded(topo, mesh, "pod",
                                                  "data", xs))
            np.testing.assert_allclose(oracle, want, rtol=1e-5, atol=1e-6,
                err_msg=f"sharded-oracle {A}x{S} {topo.name}")
    for sched in (StaticSchedule(ring(A)), RoundRobinExp(A)):
        for fused in (False, True):
            mix = make_schedule_mixer(sched, "ppermute", mesh=mesh,
                                      agent_axes="pod", shard_axes="data",
                                      use_fused_kernel=fused)
            for s in range(sched.period):
                got = np.asarray(jax.jit(lambda t, s=s: mix(t, step=s))(xs))
                want = np.asarray(mix_dense(sched.round(s), x))
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                    err_msg=f"{A}x{S} {sched.name} step={s} fused={fused}")
    print(f"SHARD_EQUIV_OK {A}x{S}")

# --- composed agents="pod" train step: HLO + trajectory + checkpoint -------
from repro.configs.base import ModelConfig, RunConfig
from repro.data import SyntheticLM
from repro.models import build_model
from repro.train import (build_train_step, bus_layout_for, checkpoint,
                         init_state, make_gossip_schedule, state_specs)

cfg = ModelConfig(name="shard-tiny", family="dense", n_layers=1, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64,
                  dtype="float32")
model = build_model(cfg)
A, S = 2, 4
mesh = make_gossip_mesh(A, pods=A, shards=S)
batch = SyntheticLM(vocab_size=64, seq_len=8,
                    n_agents=A).sample(jax.random.PRNGKey(1), 1)

def build(overlap, fused, sharded=True):
    run = RunConfig(global_batch=A, seq_len=8, algorithm="edm", alpha=0.1,
                    agents="pod" if sharded else "data",
                    gossip_engine="ppermute", packed_bus=True,
                    overlap=overlap, remat=False)
    sched = make_gossip_schedule(run, A)
    state = init_state(model, run, A, jax.random.PRNGKey(0),
                       shards=S if sharded else 1)
    if sharded:
        shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                                 state_specs(model, run, multi_pod=True),
                                 is_leaf=lambda x: isinstance(x, P))
        state = jax.tree.map(jax.device_put, state, shardings)
        step = build_train_step(model, run, sched, mesh=mesh,
                                agent_axes="pod", shard_axes="data",
                                use_fused_kernel=fused)
    else:
        m1 = make_gossip_mesh(A)
        step = build_train_step(model, run, sched, mesh=m1,
                                agent_axes=gossip_agent_axes(m1),
                                use_fused_kernel=fused)
    return run, state, jax.jit(step, donate_argnums=(0,))

layout = bus_layout_for(model, A, shards=S)
n_perm = sum(1 for t in ring(A).terms if t.shift != 0)
for overlap in ("off", "delayed"):
    for fused in (False, True):
        run, state, step = build(overlap, fused)
        hlo = step.lower(state, batch).compile().as_text()
        # the gossip's own permutes (op_name .../ppermute) are bus-shaped:
        # f32[a, r, 128].  The shape pin IS the no-all-gather guarantee: a
        # gathered operand would be full-rows.  (Unpacking the row-sharded
        # bus into leaves moves small slices across shards with permutes
        # of its own; those are not gossip.)
        perms = re.findall(
            r"= f32\\[(\\d+),(\\d+),128\\]\\S* collective-permute\\(.*"
            r"op_name=\\"[^\\"]*/ppermute\\"", hlo)
        assert len(perms) == n_perm, (overlap, fused, perms, n_perm)
        for a, r in perms:
            assert int(r) == layout.shard_rows, \
                (overlap, fused, r, layout.shard_rows, layout.rows)
        print(f"SHARD_HLO_OK overlap={overlap} fused={fused} "
              f"rows_local={layout.shard_rows} rows={layout.rows}")

# sharded trajectory == unsharded trajectory (same model/data/init)
for fused in (False, True):
    _, s_sh, st_sh = build("off", fused)
    _, s_un, st_un = build("off", fused, sharded=False)
    for _ in range(3):
        s_sh, m_sh = st_sh(s_sh, batch)
        s_un, m_un = st_un(s_un, batch)
        np.testing.assert_allclose(float(m_sh["loss"]), float(m_un["loss"]),
                                   rtol=1e-5, atol=1e-6)
    from repro.core import bus as parambus
    got = parambus.unpack_tree(bus_layout_for(model, A, shards=S),
                               jax.device_get(s_sh["params"]))
    want = parambus.unpack_tree(bus_layout_for(model, A),
                                jax.device_get(s_un["params"]))
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)
print("SHARD_TRAJ_OK")

# checkpoint: save the SHARDED run, restore into a GATHERED (shards=1)
# data-mode run and vice versa — trajectories continue identically
import tempfile
run_sh, s_sh, st_sh = build("off", False)
for _ in range(2):
    s_sh, _ = st_sh(s_sh, batch)
run_un, s_un, st_un = build("off", False, sharded=False)
for _ in range(2):
    s_un, _ = st_un(s_un, batch)
with tempfile.TemporaryDirectory() as d:
    p = os.path.join(d, "sh.npz")
    checkpoint.save_state(p, s_sh, layout=bus_layout_for(model, A, shards=S))
    like = build("off", False, sharded=False)[1]
    restored = checkpoint.load_state(p, like,
                                     layout=bus_layout_for(model, A))
    np.testing.assert_allclose(np.asarray(restored["params"]),
                               np.asarray(jax.device_get(s_un["params"])),
                               rtol=1e-5, atol=1e-6)
    p2 = os.path.join(d, "un.npz")
    checkpoint.save_state(p2, s_un, layout=bus_layout_for(model, A))
    like_sh = build("off", False)[1]
    restored_sh = checkpoint.load_state(
        p2, jax.device_get(like_sh),
        layout=bus_layout_for(model, A, shards=S))
    np.testing.assert_allclose(
        np.asarray(restored_sh["params"]),
        np.asarray(jax.device_get(s_sh["params"])), rtol=1e-5, atol=1e-6)
print("SHARD_CKPT_OK")
"""


def test_sharded_gossip_subprocess():
    r = subprocess.run([sys.executable, "-c", _SHARD_CODE], cwd=REPO,
                       env=ENV, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    for marker in ("SHARD_EQUIV_OK 2x4", "SHARD_EQUIV_OK 4x2",
                   "SHARD_HLO_OK overlap=off fused=False",
                   "SHARD_HLO_OK overlap=delayed fused=True",
                   "SHARD_TRAJ_OK", "SHARD_CKPT_OK"):
        assert marker in r.stdout, (marker, r.stdout[-2000:])
