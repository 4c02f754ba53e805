"""Production gossip path: n-ary fused combine kernel + ppermute engine.

Hypothesis-free coverage (runs everywhere):

* the n-ary ``gossip_axpy`` Pallas kernel vs its jnp oracle, f32 and bf16,
  interpret mode;
* ``mix_ppermute == mix_dense`` on every shipped topology (flat *and*
  hierarchical, split and linearized agent axes, fused and unfused combine)
  on a multi-device host-platform mesh — run in a subprocess so the forced
  device count cannot leak into this test process;
* EDM composed with the fused ppermute mixer matches the dense-mixer run.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.edm_update import gossip_axpy_flat

jax.config.update("jax_enable_x64", False)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ,
       "PYTHONPATH": os.path.join(REPO, "src")
       + (os.pathsep + os.environ["PYTHONPATH"]
          if os.environ.get("PYTHONPATH") else "")}


# ---------------------------------------------------------------------------
# n-ary gossip_axpy kernel vs oracle
# ---------------------------------------------------------------------------

WEIGHT_SETS = [
    (0.5, 0.25, 0.25),                      # paper's ring
    (1.0,),                                 # identity / disconnected
    (0.4, 0.3, 0.2, 0.1),                   # asymmetric 4-term
    tuple([1.0 / 6] * 6),                   # hierarchical 6-term
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("weights", WEIGHT_SETS,
                         ids=lambda w: f"n{len(w)}")
def test_gossip_axpy_flat_nary_matches_ref(weights, dtype):
    shape = (512, 128)
    ks = jax.random.split(jax.random.PRNGKey(0), len(weights))
    operands = tuple(jax.random.normal(k, shape).astype(dtype) for k in ks)
    out = gossip_axpy_flat(operands, weights, interpret=True)
    want = ref.gossip_axpy_ref(operands, weights)
    assert out.dtype == dtype
    tol = 1e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_gossip_axpy_bf16_accumulates_in_f32():
    """bf16 path must round once (on the store), not per term: summing many
    small terms in bf16 would lose them to the large one."""
    n = 8
    big = jnp.full((512, 128), 1024.0, jnp.bfloat16)
    small = jnp.full((512, 128), 1.0, jnp.bfloat16)
    operands = (big,) + (small,) * (n - 1)
    weights = (1.0,) + (1.0,) * (n - 1)
    out = gossip_axpy_flat(operands, weights, interpret=True)
    # f32 accumulation: 1024 + 7 = 1031 → rounds to 1032 in bf16.
    # per-term bf16 accumulation would stick at 1024 (1 < ulp(1024)=8... each
    # add of 1 rounds away) — guard the f32-accumulate contract.
    np.testing.assert_allclose(np.asarray(out, np.float32), 1032.0)


@pytest.mark.parametrize("shape", [(7,), (130,), (3, 5, 17), (1000, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_gossip_axpy_arbitrary_shapes(shape, dtype):
    """ops.gossip_axpy packs any shape and returns the original layout/dtype."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    operands = tuple(jax.random.normal(k, shape).astype(dtype) for k in ks)
    weights = (0.5, 0.25, 0.25)
    out = ops.gossip_axpy(operands, weights, interpret=True)
    assert out.shape == shape and out.dtype == dtype
    want = ref.gossip_axpy_ref(operands, weights)
    tol = 1e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# (A, rows, 128) with whole 512-row tiles per agent: rolls via index maps;
# (A, 3, 17): rolled in XLA, then combined
@pytest.mark.parametrize("shape", [(4, 1024, 128), (4, 3, 17)],
                         ids=["tiled", "ragged"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_gossip_axpy_rolled_matches_rolls(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(2), shape).astype(dtype)
    shifts, weights = (0, 1, -1, 2), (0.4, 0.3, 0.2, 0.1)
    out = ops.gossip_axpy_rolled(x, shifts, weights, interpret=True)
    assert out.shape == shape and out.dtype == dtype
    want = ref.gossip_axpy_ref(
        [jnp.roll(x, s, axis=0) for s in shifts], weights)
    tol = 1e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("name", ["ring", "exp", "hier"])
def test_ppermute_all_agents_on_one_device_fused(name):
    """Blocked engine with every agent on one device: the fused combine
    reads each term's roll through its index map (hierarchical intra terms
    are no flat roll and take the materialized path) — both == dense."""
    from jax.sharding import Mesh

    from repro.core import exp_graph, hierarchical, ring
    from repro.core.mixing import mix_dense, mix_ppermute

    topo = {"ring": ring(4), "exp": exp_graph(4),
            "hier": hierarchical(2, 2)}[name]
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 1024, 128))
    got = mix_ppermute(topo, mesh, "data", x, use_fused_kernel=True,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(mix_dense(topo, x)),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# mix_ppermute == mix_dense over every shipped topology
# ---------------------------------------------------------------------------

_AGREEMENT_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.core import (disconnected, exp_graph, fully_connected,
                        hierarchical, make_mixer, ring, torus2d)
from repro.core.mixing import mix_dense, mix_ppermute

def submesh(shape, names):
    n = int(np.prod(shape))
    devs = np.array(jax.devices()[:n]).reshape(shape)
    return Mesh(devs, names)

# mirrors tests/test_core.py::TOPOLOGIES
TOPOLOGIES = [
    ring(8), ring(32), exp_graph(16), torus2d(2, 8), torus2d(4, 4),
    fully_connected(8), hierarchical(2, 16), hierarchical(4, 4, intra="ring"),
    disconnected(8),
]

for topo in TOPOLOGIES:
    A = topo.n_agents
    tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (A, 5)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (A, 2, 3))}
    want = mix_dense(topo, tree)
    meshes = [(submesh((A,), ("agents",)), "agents")]
    if topo.grid is not None:  # hierarchical: also the split (pod, data) mesh
        meshes.append((submesh(topo.grid, ("pod", "data")), ("pod", "data")))
    for mesh, axes in meshes:
        for fused in (False, True):
            mixer = make_mixer(topo, "ppermute", mesh=mesh, agent_axes=axes,
                               use_fused_kernel=fused)
            got = jax.jit(mixer)(tree)
            for k in tree:
                np.testing.assert_allclose(
                    np.asarray(got[k]), np.asarray(want[k]),
                    rtol=1e-5, atol=1e-6,
                    err_msg=f"{topo.name}-{A} axes={axes} fused={fused} {k}")
    print(f"AGREE {topo.name}-{A}")

# EDM composed with the fused ppermute mixer == EDM with the dense mixer
from repro.core import make_optimizer
topo = ring(8)
mesh, axes = submesh((8,), ("agents",)), "agents"
x0 = jax.random.normal(jax.random.PRNGKey(2), (8, 6))
g = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (8, 6))
runs = {}
for label, mixer in (
        ("dense", make_mixer(topo, "dense")),
        ("ppermute", make_mixer(topo, "ppermute", mesh=mesh, agent_axes=axes,
                                use_fused_kernel=True))):
    opt = make_optimizer("edm", alpha=0.05, beta=0.9, mix=mixer)
    x, st = x0, opt.init(x0)
    for _ in range(3):
        x, st = opt.step(x, g, st)
    runs[label] = x
np.testing.assert_allclose(np.asarray(runs["ppermute"]),
                           np.asarray(runs["dense"]), rtol=1e-5, atol=1e-6)
print("AGREEMENT_OK")
"""


def test_ppermute_agrees_with_dense_all_topologies():
    """Acceptance: make_mixer(engine="ppermute") matches mix_dense to 1e-5 on
    every topology in test_core.TOPOLOGIES, split and flat meshes, with and
    without the fused Pallas combine — and composes with the EDM optimizer."""
    r = subprocess.run([sys.executable, "-c", _AGREEMENT_CODE], cwd=REPO,
                       env=ENV, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "AGREEMENT_OK" in r.stdout


# ---------------------------------------------------------------------------
# time-varying gossip schedules (DESIGN §4)
# ---------------------------------------------------------------------------

def _shipped_schedules():
    from repro.core import (AlternatingHierarchical, RoundRobinExp,
                            StaticSchedule, exp_graph, hierarchical, ring)
    return [
        StaticSchedule(ring(8)),
        StaticSchedule(exp_graph(16)),
        StaticSchedule(hierarchical(2, 16)),
        RoundRobinExp(8),
        RoundRobinExp(12),          # non-power-of-two n
        RoundRobinExp(32),
        RoundRobinExp(32, seed=7),  # shuffled offset order
        AlternatingHierarchical(2, 16),
        AlternatingHierarchical(4, 4, intra_every=2),
        AlternatingHierarchical(4, 8, intra="full"),
    ]


@pytest.mark.parametrize("sched", _shipped_schedules(),
                         ids=lambda s: s.name.replace("(", "-").strip(")"))
def test_schedules_satisfy_assumption1(sched):
    """Schedule form of the paper's Assumption 1: every round doubly
    stochastic with positive diagonal, period product contracting."""
    sched.check_assumption1()


def test_round_robin_exp_one_permute_per_round():
    """Acceptance: every round of the n=32 one-peer schedule carries exactly
    one nonzero-shift term (one collective-permute per step), vs the static
    exp graph's O(log n) terms per step."""
    from repro.core import RoundRobinExp, StaticSchedule, exp_graph
    sched = RoundRobinExp(32)
    assert sched.period == 5  # offsets 1, 2, 4, 8, 16
    for rnd in sched.rounds:
        assert sum(1 for t in rnd.terms if t.shift != 0) == 1, rnd
    static_terms = sum(
        1 for t in exp_graph(32).terms if t.shift != 0)
    assert static_terms >= 5  # the per-step wire cut is >= period x
    stats = sched.product_spectral_stats()
    assert stats["permutes_per_step"] == 1


def test_round_robin_period_product_matches_static_exp_mixing():
    """The one-peer round-robin period product mixes at least as fast as
    `period` applications of the static exp graph — and for power-of-two n
    it is *exact* averaging (the product of (I + R_{2^j})/2 telescopes to
    (1/n)·11^T)."""
    from repro.core import RoundRobinExp, exp_graph
    n = 32
    sched = RoundRobinExp(n)
    P = sched.period_product()
    ones = np.full((n, n), 1.0 / n)
    # power-of-two n: exact averaging after one period
    np.testing.assert_allclose(P, ones, atol=1e-12)
    # ⇒ at least the static exp graph's contraction over the same steps
    W = exp_graph(n).dense_matrix()
    W_period = np.linalg.matrix_power(W, sched.period)
    assert np.linalg.norm(P - ones, 2) <= np.linalg.norm(W_period - ones, 2) + 1e-12
    # offset order never changes the product (circulants commute)
    P_shuf = type(sched)(n, seed=123).period_product()
    np.testing.assert_allclose(P, P_shuf, atol=1e-12)


def test_round_robin_non_power_of_two_still_contracts():
    from repro.core import RoundRobinExp
    sched = RoundRobinExp(12)
    assert sched.product_spectral_gap() > 0.1


def test_schedule_mixer_threads_step_through_trainer_mixing():
    """EDM driven by a schedule mixer (traced step, lax.switch) must equal
    EDM where each step's round is applied explicitly via the dense oracle —
    the per-step W-consistency rule of DESIGN §4."""
    from repro.core import (RoundRobinExp, make_mixer, make_optimizer,
                            make_schedule_mixer)
    sched = RoundRobinExp(8)
    x0 = jax.random.normal(jax.random.PRNGKey(2), (8, 6))
    g = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (8, 6))

    # reference: rebuild the optimizer each step with that round's mixer
    x_ref, st_ref = x0, make_optimizer(
        "edm", alpha=0.05, beta=0.9,
        mix=make_mixer(sched.rounds[0], "dense")).init(x0)
    for t in range(6):
        opt = make_optimizer("edm", alpha=0.05, beta=0.9,
                             mix=make_mixer(sched.round(t), "dense"))
        x_ref, st_ref = opt.step(x_ref, g, st_ref)

    # schedule mixer with a *traced* step, stepped under jit
    smix = make_schedule_mixer(sched, "dense")

    @jax.jit
    def step_fn(x, st, t):
        opt = make_optimizer("edm", alpha=0.05, beta=0.9,
                             mix=lambda tree: smix(tree, step=t))
        return opt.step(x, g, st)

    x_s, st_s = x0, make_optimizer(
        "edm", alpha=0.05, beta=0.9, mix=lambda t: t).init(x0)
    for t in range(6):
        x_s, st_s = step_fn(x_s, st_s, jnp.int32(t))
    np.testing.assert_allclose(np.asarray(x_s), np.asarray(x_ref),
                               rtol=1e-5, atol=1e-6)


def test_gossip_round_step_covers_all_rounds_under_gossip_every():
    """gossip_every=k must not alias against the schedule period: the round
    clock advances per executed gossip, so every round is eventually used
    even when gcd(k, period) > 1."""
    from repro.train import gossip_round_step
    for k, period in [(5, 5), (2, 2), (4, 2), (3, 5), (1, 5)]:
        gossip_steps = [t for t in range(20 * k * period)
                        if t % k == k - 1] if k > 1 else list(range(period))
        rounds = {int(gossip_round_step(t, k)) % period for t in gossip_steps}
        assert rounds == set(range(period)), (k, period, rounds)


def test_gossip_axpy_weights_traceable():
    """The advertised contract: weights are traced data — a jit-traced
    weight array must work at the public entry point."""
    shape = (40, 9)
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    operands = tuple(jax.random.normal(k, shape) for k in ks)

    @jax.jit
    def f(w):
        return ops.gossip_axpy(operands, w, interpret=True)

    out = f(jnp.array([0.25, 0.75]))
    want = ref.gossip_axpy_ref(operands, (0.25, 0.75))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_gossip_axpy_dynamic_weights_no_retrace():
    """Per-round arity without retracing: two weight sets of one arity share
    one compiled kernel (weights are traced SMEM data, not a jit key)."""
    ops._gossip_axpy_jit.clear_cache()
    shape = (64, 33)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    operands = tuple(jax.random.normal(k, shape) for k in ks)
    for weights in [(0.5, 0.25, 0.25), (0.4, 0.4, 0.2), (1.0, 0.0, 0.0)]:
        out = ops.gossip_axpy(operands, weights, interpret=True)
        want = ref.gossip_axpy_ref(operands, weights)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
    assert ops._gossip_axpy_jit._cache_size() == 1
    # a different arity is a new kernel — exactly one more cache entry
    ops.gossip_axpy(operands[:2], (0.7, 0.3), interpret=True)
    assert ops._gossip_axpy_jit._cache_size() == 2


def test_block_rows_knob():
    """BLOCK_ROWS is tunable per call and via REPRO_BLOCK_ROWS."""
    shape = (300, 7)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    operands = tuple(jax.random.normal(k, shape) for k in ks)
    weights = (0.6, 0.4)
    want = ref.gossip_axpy_ref(operands, weights)
    for br in (8, 128, 1024):
        out = ops.gossip_axpy(operands, weights, block_rows=br,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
    code = ("import os; os.environ['REPRO_BLOCK_ROWS']='256'; "
            "from repro.kernels.edm_update import BLOCK_ROWS; "
            "assert BLOCK_ROWS == 256, BLOCK_ROWS; print('ENV_OK')")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=ENV,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ENV_OK" in r.stdout


_SCHEDULE_ENGINE_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.core import (AlternatingHierarchical, RoundRobinExp,
                        StaticSchedule, exp_graph, make_schedule_mixer)
from repro.core.mixing import mix_dense

def flat_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("data",))

SCHEDULES = [RoundRobinExp(32), AlternatingHierarchical(4, 8),
             StaticSchedule(exp_graph(32))]

for sched in SCHEDULES:
    A = sched.n_agents
    tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (A, 5)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (A, 2, 3))}
    for apd in (1, 4):  # one agent per device, and blocked 32-on-8
        mesh = flat_mesh(A // apd)
        for fused in (False, True):
            mix = make_schedule_mixer(sched, "ppermute", mesh=mesh,
                                      agent_axes="data",
                                      use_fused_kernel=fused)
            for r in range(sched.period):   # every round index
                got = jax.jit(lambda t, r=r: mix(t, step=r))(tree)
                want = mix_dense(sched.rounds[r], tree)
                for k in tree:
                    np.testing.assert_allclose(
                        np.asarray(got[k]), np.asarray(want[k]),
                        rtol=1e-5, atol=1e-6,
                        err_msg=f"{sched.name} B={apd} fused={fused} "
                                f"round={r} {k}")
            # traced step routes through lax.switch over the permute plans
            t_tr = jnp.int32(sched.period + 1)
            got = jax.jit(mix)(tree, t_tr)
            want = mix_dense(sched.round(sched.period + 1), tree)
            for k in tree:
                np.testing.assert_allclose(
                    np.asarray(got[k]), np.asarray(want[k]),
                    rtol=1e-5, atol=1e-6,
                    err_msg=f"{sched.name} B={apd} fused={fused} traced")
    print(f"SCHED_AGREE {sched.name}")

# acceptance: one-peer round compiles to exactly ONE collective-permute,
# and the blocked A=32-on-8 engine emits real permutes (no shifts fallback)
sched = RoundRobinExp(32)
mix = make_schedule_mixer(sched, "ppermute", mesh=flat_mesh(32),
                          agent_axes="data")
x = {"w": jax.random.normal(jax.random.PRNGKey(0), (32, 4))}
hlo = jax.jit(lambda t: mix(t, step=0)).lower(x).compile().as_text()
assert hlo.count("collective-permute(") == 1, hlo.count("collective-permute(")

mix_b = make_schedule_mixer(sched, "ppermute", mesh=flat_mesh(8),
                            agent_axes="data")
hlo_b = jax.jit(lambda t: mix_b(t, step=0)).lower(x).compile().as_text()
assert hlo_b.count("collective-permute(") >= 1
print("SCHEDULE_ENGINES_OK")
"""


def test_schedule_engines_agree_every_round_and_blocked():
    """Acceptance: ppermute == dense oracle at every round index of every
    shipped schedule, on the 32-agent host mesh AND blocked 32-agents-on-8-
    devices (B=4), fused and unfused; the n=32 one-peer round compiles to
    exactly one collective-permute."""
    r = subprocess.run([sys.executable, "-c", _SCHEDULE_ENGINE_CODE],
                       cwd=REPO, env=ENV, capture_output=True, text=True,
                       timeout=560)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "SCHEDULE_ENGINES_OK" in r.stdout
