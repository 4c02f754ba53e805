"""Compile the main-path Pallas kernels for a TPU v5e that is described, not
attached.

The TPU compiler ships with jaxlib and compiles for a topology description
(``v5e:2x2``) on a CPU-only host.  That catches what interpret mode cannot:
block shapes Mosaic refuses, kernels XLA cannot partition, and programs that
do not fit a chip's HBM.  Nothing runs; results and times come from
``chip_smoke.py`` on a real chip.

The topology is described inside a module fixture (never at import, in a
``skipif`` or a ``parametrize`` argument): only one process may load the
TPU library, and every pytest-xdist worker imports this file.  The
persistent compilation cache is off around these compiles — an executable
for a described chip is written to it but cannot be read back here.
"""
from __future__ import annotations

import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import edm_update as ek

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import scopes  # noqa: E402

HBM_BYTES = 16 * 2**30          # one v5e chip
ROWS = 32768                    # a 4M-element bus slice: 64 grid tiles


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Make the ops wrappers emit Mosaic kernels, as they do on a TPU
    backend (here the default backend is the CPU)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, donate=()):
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


def _kernels(compiled) -> set:
    """Names of the Mosaic kernels in a compiled program."""
    return set(re.findall(r'op_name="[^"]*?/(\w+)/pallas_call',
                          compiled.as_text()))


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _smollm_bus_rows(n_agents: int) -> int:
    from repro.configs import get_config
    from repro.models import build_model
    from repro.train import bus_layout_for
    return bus_layout_for(build_model(get_config("smollm_360m")),
                          n_agents).rows


def test_edm_update_flat(one_chip):
    c = _compile(lambda *a: ek.edm_update_flat(*a, alpha=0.1, beta=0.9),
                 *[_sds(one_chip, (ROWS, 128))] * 4)
    assert _kernels(c) == {"edm_update"}


@pytest.mark.parametrize("fmt", ["bf16", "int8"])
def test_edm_update_ef_flat(one_chip, fmt):
    c = _compile(lambda *a: ek.edm_update_ef_flat(*a, alpha=0.1, beta=0.9,
                                                  fmt=fmt),
                 *[_sds(one_chip, (ROWS, 128))] * 5)
    assert _kernels(c) == {f"edm_update_ef_{fmt}"}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gossip_axpy_flat(one_chip, dtype):
    c = _compile(lambda w, *ops: ek.gossip_axpy_flat(ops, w),
                 _sds(one_chip, (3,)), *[_sds(one_chip, (ROWS, 128), dtype)] * 3)
    assert _kernels(c) == {"gossip_axpy"}


def test_gossip_axpy_rolled_full_width(one_chip, mosaic):
    """The one-chip ring combine over the two-agent smollm_360m bus: the
    neighbour term is read through the index map, so the program holds
    the bus and the sum, and no rolled copy."""
    from repro.kernels import ops
    bus = _sds(one_chip, (2, _smollm_bus_rows(2), 128))
    c = _compile(lambda x: ops.gossip_axpy_rolled(x, (0, 1), (0.5, 0.5)),
                 bus)
    assert _kernels(c) == {"gossip_axpy"}
    assert c.memory_analysis().temp_size_in_bytes < (64 << 20)


def test_gossip_axpy_q8_flat(one_chip):
    n_tiles = ROWS // ek.BLOCK_ROWS
    c = _compile(lambda coefs, *ops: ek.gossip_axpy_q8_flat(ops, coefs),
                 _sds(one_chip, (3, n_tiles)),
                 *[_sds(one_chip, (ROWS, 128), jnp.int8)] * 3)
    assert _kernels(c) == {"gossip_axpy_q8"}


# (kv heads, head dim): smollm_360m's 5 × 64, and 8 × 128
PAGED_WIDTHS = [(5, 64), (8, 128)]


VMEM_LIMIT = 16 << 20          # Mosaic's default scoped VMEM on a v5e

# (slots, kv heads, group, head dim, page, pool pages, pages a slot, pages
# a block): the PAGED_WIDTHS at a small pool (a slot is one block), and
# starcoder2_7b's serving cell (1 MiB blocks)
PAGED_DECODE = [pytest.param(8, K, 3, hd, 16, 33, 4, 4, id=f"{K}-{hd}")
                for K, hd in PAGED_WIDTHS] + [
    pytest.param(16, 4, 9, 128, 64, 1400, 64, 8, id="starcoder2_7b")]


@pytest.mark.parametrize("B,K,G,hd,page,num_pages,n_pages,block",
                         PAGED_DECODE)
def test_paged_attention(one_chip, B, K, G, hd, page, num_pages, n_pages,
                         block):
    from repro.kernels.paged_attention import (paged_attention_kernel_call,
                                               pages_per_block)
    bf = jnp.bfloat16
    c = _compile(
        lambda q, k, v, pt, ln: paged_attention_kernel_call(
            q, k, v, pt, ln, page_size=page),
        _sds(one_chip, (B, K, G, hd), bf),
        _sds(one_chip, (K, num_pages, page, hd), bf),
        _sds(one_chip, (K, num_pages, page, hd), bf),
        _sds(one_chip, (B, n_pages), jnp.int32),
        _sds(one_chip, (B,), jnp.int32))
    assert _kernels(c) == {"paged_attention"}
    ppb = pages_per_block(page_size=page, hd=hd, n_kv_heads=K,
                          n_pages=n_pages, itemsize=2)
    assert ppb == block
    # two buffers of a block of k and v pages, every kv head
    assert 2 * 2 * ppb * K * page * hd * 2 <= VMEM_LIMIT


@pytest.mark.parametrize("K,hd", PAGED_WIDTHS)
def test_paged_prefill(one_chip, K, hd):
    from repro.kernels.paged_prefill import paged_prefill_kernel_call
    G, C, page, num_pages, n_pages = 3, 16, 16, 33, 4
    bf = jnp.bfloat16
    c = _compile(
        lambda q, kc, vc, kp, vp, pt, meta: paged_prefill_kernel_call(
            q, kc, vc, kp, vp, pt, meta, page_size=page),
        _sds(one_chip, (K, C * G, hd), bf),
        _sds(one_chip, (K, C, hd), bf), _sds(one_chip, (K, C, hd), bf),
        _sds(one_chip, (K, num_pages, page, hd), bf),
        _sds(one_chip, (K, num_pages, page, hd), bf),
        _sds(one_chip, (n_pages,), jnp.int32),
        _sds(one_chip, (2,), jnp.int32))
    assert _kernels(c) == {"paged_prefill"}


def test_flash_attention(one_chip):
    from repro.kernels.flash_attention import flash_attention_kernel_call
    B, H, K, S, hd = 1, 15, 5, 1024, 64      # smollm_360m at seq 1024
    bf = jnp.bfloat16
    c = _compile(flash_attention_kernel_call,
                 _sds(one_chip, (B, H, S, hd), bf),
                 _sds(one_chip, (B, K, S, hd), bf),
                 _sds(one_chip, (B, K, S, hd), bf))
    assert _kernels(c) == {"flash_attention"}


def _update_donated(update):
    """``update`` with m, ψ, g donated in the order of the (m', ψ', φ)
    outputs they alias — jit hands each output the first unused donated
    buffer of its shape, as the train step's state dict does for m, ψ."""
    return lambda m, psi, g, x: update(x, g, m, psi)


def test_edm_update_bus_full_width_fits_one_chip(one_chip, mosaic):
    """The fused update over the two-agent smollm_360m bus, donated as the
    trainer donates it: m', ψ' and φ alias m, ψ and g, so it needs no
    bus-sized buffer beyond its four inputs."""
    from repro.kernels import ops
    bus = _sds(one_chip, (2, _smollm_bus_rows(2), 128))
    update = functools.partial(ops.edm_update_bus, alpha=0.1, beta=0.9)
    c = _compile(_update_donated(update), bus, bus, bus, bus,
                 donate=(0, 1, 2))
    assert _kernels(c) == {"edm_update"}
    mem = c.memory_analysis()
    bus_bytes = 2 * _smollm_bus_rows(2) * 128 * 4
    assert mem.alias_size_in_bytes == 3 * bus_bytes
    assert _bytes(c) <= 4 * bus_bytes + (64 << 20) < HBM_BYTES
    assert " copy(" not in c.as_text()


def test_fused_bus_update_data_mode_four_chips(topo, mosaic):
    """agents="data" on a 4-chip mesh: the trainer's shard_map wrapper runs
    the fused update per chip (XLA cannot partition a Mosaic kernel), one
    full-width smollm_360m agent each."""
    from repro.train import shard_local_edm_update
    mesh = Mesh(np.array(topo.devices), ("data",))
    spec = P("data")
    bus = _sds(NamedSharding(mesh, spec), (4, _smollm_bus_rows(4), 128))
    update = shard_local_edm_update(mesh, spec, alpha=0.1, beta=0.9,
                                    block_rows=ek.BLOCK_ROWS)
    c = _compile(_update_donated(update), bus, bus, bus, bus,
                 donate=(0, 1, 2))
    assert _kernels(c) == {"edm_update"}
    assert _bytes(c) < HBM_BYTES        # per device


@pytest.fixture(scope="module")
def one_chip_step_text(topo):
    """Optimized HLO of the step the one-chip training cells run
    (smollm_360m, two agents blocked on a chip, packed bus, fused kernels,
    seq 256), compiled for a v5e, with the ops wrappers emitting Mosaic
    kernels as they do on a TPU backend."""
    from repro.configs import get_config
    from repro.configs.base import RunConfig
    from repro.kernels import ops as kops
    from repro.models import build_model
    from repro.train import (build_train_step, init_state,
                             make_gossip_schedule, state_specs)
    A, S = 2, 256
    dev = topo.devices[0]
    mesh = Mesh(np.array([dev]), ("data",))
    model = build_model(get_config("smollm_360m"))
    run = RunConfig(global_batch=A, seq_len=S, agents="data",
                    algorithm="edm", alpha=1e-3, beta=0.9, topology="ring",
                    gossip_engine="ppermute", packed_bus=True,
                    agents_per_device=A)
    shapes = jax.eval_shape(lambda k: init_state(model, run, A, k),
                            jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda s, sp: _sds(NamedSharding(mesh, sp), s.shape, s.dtype),
        shapes, state_specs(model, run, multi_pod=False),
        is_leaf=lambda x: isinstance(x, P))
    tokens = _sds(SingleDeviceSharding(dev), (A, 1, S), jnp.int32)
    on_tpu = kops._on_tpu
    kops._on_tpu = lambda: True
    try:
        step = build_train_step(model, run, make_gossip_schedule(run, A),
                                use_fused_kernel=True, mesh=mesh,
                                agent_axes="data")
        return _compile(step, state, {"tokens": tokens},
                        donate=(0,)).as_text()
    finally:
        kops._on_tpu = on_tpu


def test_train_step_ops_lie_under_the_scopes(one_chip_step_text):
    """Every op of the one-chip training step that writes more than a MiB
    lies under one of the step's scopes, the way the benchmark attributes
    them (``bench/scopes.py``), and every scope owns some op."""
    text = one_chip_step_text
    comps, _ = scopes.parse_hlo(text)
    ops = scopes.hlo_ops(text)
    assert set(ops.values()) - {None} == set(scopes.SCOPES)
    for c in comps:
        for name, op in comps[c].items():
            if name in ops and ops[name] is None and op["kind"] not in (
                    "parameter", "constant", "tuple", "get-tuple-element",
                    "bitcast"):
                sizes = re.findall(r"\[([\d,]*)\]", op["shape"])
                elems = max(int(np.prod([int(d) for d in s.split(",") if d]))
                            for s in sizes) if sizes else 0
                assert elems * 4 <= 1 << 20, op["line"][:200]


def test_one_chip_train_step_runs_three_kernels_once(one_chip_step_text):
    """The one-chip fused step runs exactly three Mosaic kernels, each
    once a step: the EDM update, the gossip combine, and the consensus
    metric under ``step_metrics``."""
    calls = [l for l in one_chip_step_text.splitlines()
             if "tpu_custom_call" in l and "op_name=" in l]
    names = [re.search(r'op_name="[^"]*?/(\w+)/pallas_call', l).group(1)
             for l in calls]
    assert sorted(names) == ["bus_consensus", "edm_update", "gossip_axpy"]
    (cons,) = [l for l in calls if "/bus_consensus/" in l]
    assert "/step_metrics/" in cons


def test_bus_consensus_full_width_fits_one_chip(one_chip, mosaic):
    """The consensus kernel over the two-agent smollm_360m bus: one read
    of the bus, and no bus-sized temporary (the XLA expression makes the
    agent mean and its broadcast)."""
    from repro.kernels import ops
    bus = _sds(one_chip, (2, _smollm_bus_rows(2), 128))
    c = _compile(ops.bus_consensus, bus)
    assert _kernels(c) == {"bus_consensus"}
    bus_bytes = 2 * _smollm_bus_rows(2) * 128 * 4
    assert c.memory_analysis().temp_size_in_bytes < (64 << 20)
    assert _bytes(c) <= bus_bytes + (64 << 20) < HBM_BYTES


def test_expert_share_grouped_matmuls_at_published_widths(one_chip, mosaic):
    """DeepSeek-V2-Lite's MoE layer at one chip's share (8 of 64 experts,
    4096 tokens, top-6): the forward and backward of the grouped matmuls
    compile to the megablox kernels, three ``gmm`` forward, three for the
    rows' cotangent and three ``tgmm`` for the weights'."""
    from collections import Counter

    from repro.configs import get_config
    from repro.models.moe import apply_moe_dropless, init_moe

    cfg = get_config("deepseek_v2_lite")
    shapes = jax.eval_shape(lambda k: init_moe(k, cfg), jax.random.PRNGKey(0))
    p = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), shapes)
    x = _sds(one_chip, (1, 4096, cfg.d_model), jnp.bfloat16)

    def loss(p, x):
        y, aux, _ = apply_moe_dropless(p, cfg, x, cfg.norm_eps)
        return jnp.sum(y.astype(jnp.float32)) + aux

    text = _compile(jax.grad(loss, argnums=(0, 1)), p, x).as_text()
    kernels = Counter(re.findall(r"%(t?gmm)\.\d+ = [^\n]*tpu_custom_call",
                                 text))
    assert kernels == {"gmm": 6, "tgmm": 3}, kernels
