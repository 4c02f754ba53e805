"""Production mesh construction.

Functions, not module-level constants, so importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).

Target hardware: TPU v5e pods — 256 chips/pod (16×16), 2 pods = 512 chips.
Mesh axes:
  pod   — crosses DCI (slow inter-pod links); EDM's gossip edge in "pod" mode
  data  — data parallel / decentralized agents; ICI
  model — tensor/expert parallel inside one agent; ICI
"""
from __future__ import annotations

import jax
import numpy as np

__all__ = ["make_mesh", "make_production_mesh", "make_sim_mesh",
           "make_gossip_mesh", "gossip_agent_axes", "HW"]


# TPU v5e hardware constants used by the roofline analysis (per chip).
HW = {
    "peak_flops_bf16": 197e12,   # FLOP/s
    "hbm_bw": 819e9,             # B/s
    "ici_bw": 50e9,              # B/s per link
    "hbm_bytes": 16e9,
}


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the model code shards through
    ``with_sharding_constraint`` and GSPMD propagation, which Explicit axes
    (the ``jax.make_mesh`` default) reject."""
    from jax.sharding import AxisType
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_sim_mesh():
    """1-device mesh with the production axis names (CPU tests)."""
    return make_mesh((1, 1), ("data", "model"))


def make_gossip_mesh(n_agents: int, pods: int = 1,
                     agents_per_device: int = 1, shards: int = 1):
    """Mesh whose device grid carries the agent grid — a block of
    ``agents_per_device`` agents per device, as the ppermute engine requires
    (DESIGN §3–4).

    Builds over the first ``n_agents // agents_per_device`` devices so it
    also works on a host-platform mesh forced larger than needed
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N``).  One agent per
    device (the default) yields ``(pods, n_agents // pods)`` with axes
    ``('pod', 'data')`` for hierarchical topologies, else ``(n_agents,)``
    with ``('data',)``.  Blocked mode (``agents_per_device > 1`` — how the
    n=32 simulations run on 8-device hosts) always builds the single flat
    ``('data',)`` axis the blocked engine needs; hierarchical terms
    decompose inside the engine, not the mesh.

    Shard-resident mode (``shards > 1``, DESIGN §7): each agent spans a
    whole pod of ``shards`` FSDP devices — an ``(n_agents, shards)`` grid
    with axes ``('pod', 'data')`` where **'pod' is the agent axis and
    'data' the row-shard axis** (unlike the ``pods > 1`` grid above, where
    both axes carry agents).  Use agent_axes='pod', shard_axes='data'.
    """
    from jax.sharding import Mesh

    B = agents_per_device
    assert B >= 1 and n_agents % B == 0, (n_agents, B)
    assert n_agents % max(pods, 1) == 0, (n_agents, pods)
    assert shards >= 1, shards
    devices = jax.devices()
    if shards > 1:
        assert B == 1, "shard-resident gossip needs one agent per slice"
        assert pods in (1, n_agents), \
            "shards>1 makes every agent a pod — pods must equal n_agents"
        n_dev = n_agents * shards
        assert len(devices) >= n_dev, \
            f"need {n_dev} devices for {n_agents} pod-agents × {shards} " \
            f"shards, have {len(devices)}"
        grid = np.array(devices[:n_dev]).reshape(n_agents, shards)
        return Mesh(grid, ("pod", "data"))
    n_dev = n_agents // B
    assert len(devices) >= n_dev, \
        f"need {n_dev} devices for {B}-agent-per-device gossip, " \
        f"have {len(devices)}"
    if B > 1:
        return Mesh(np.array(devices[:n_dev]), ("data",))
    if pods > 1:
        grid = np.array(devices[:n_dev]).reshape(pods, n_dev // pods)
        return Mesh(grid, ("pod", "data"))
    return Mesh(np.array(devices[:n_dev]), ("data",))


def gossip_agent_axes(mesh, sharded: bool = False):
    """The agent_axes tuple/name the gossip engines consume on ``mesh``.

    ``sharded=True`` reads the mesh as a shard-resident pods × shards grid
    (DESIGN §7): only 'pod' carries agents — 'data' is the FSDP row-shard
    axis (pass it as ``shard_axes``)."""
    if sharded:
        assert "pod" in mesh.axis_names and "data" in mesh.axis_names, \
            mesh.axis_names
        return "pod"
    names = tuple(n for n in mesh.axis_names if n in ("pod", "data"))
    assert names, mesh.axis_names
    return names if len(names) > 1 else names[0]
