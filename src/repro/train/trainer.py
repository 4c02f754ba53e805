"""Decentralized trainer: EDM (or any registered algorithm) over a model.

The train state carries the full per-agent replica set:
    params : every leaf (A, *shape)   — A = number of agents
    opt    : algorithm state (same leading axis)
    step   : scalar

``build_train_step`` returns a pure function suitable for jax.jit with
explicit in/out shardings (see :func:`state_specs`).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import warnings
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig
from repro.core import (GossipSchedule, GroupPlan, StaticSchedule, Topology,
                        accumulate_f32, build_mixer, make_codec,
                        make_edm_bus, make_edm_bus_ef, make_group_mixer,
                        make_optimizer, make_schedule)
from repro.core.optimizers import DecOptimizer
from repro.core.wire import WIRE_FORMATS, encode_ef
from repro.core import bus as parambus
from repro.core.bus import GroupSpec
from repro.core.metrics import bus_consensus, bus_grad_norm, consensus_distance
from repro.models.api import Model
from repro.optim import scale_grads, warmup_cosine

__all__ = [
    "TrainState", "build_train_step", "init_state", "state_specs",
    "make_topology", "make_gossip_schedule", "gossip_round_step",
    "prepend_agent_axis", "batch_spec_tree", "Features", "resolve_features",
    "resolve_group_specs", "make_group_plans", "use_packed_bus",
    "use_overlap", "use_wire", "bus_layout_for", "shard_local_edm_update",
    "step_consensus",
]


TrainState = Dict[str, Any]  # {"params", "opt", "step"}


def make_topology(run: RunConfig, n_agents: int, pods: int = 1) -> Topology:
    from repro.core import exp_graph, fully_connected, hierarchical, ring, torus2d
    if run.topology == "ring":
        return ring(n_agents)
    if run.topology == "exp":
        return exp_graph(n_agents)
    if run.topology == "full":
        return fully_connected(n_agents)
    if run.topology == "torus":
        return torus2d(pods if pods > 1 else 1, n_agents // max(pods, 1))
    if run.topology == "hier":
        assert pods >= 1
        return hierarchical(pods, n_agents // pods)
    raise ValueError(run.topology)


def make_gossip_schedule(run: RunConfig, n_agents: int, pods: int = 1,
                         churn=None) -> GossipSchedule:
    """``RunConfig`` → step-indexed gossip schedule (DESIGN §4).

    ``gossip_schedule="static"`` wraps :func:`make_topology`'s W;
    ``"round_robin"`` / ``"alt_hier"`` build the time-varying schedules
    (``gossip_period``/``gossip_seed`` are their knobs).

    ``churn`` (DESIGN §8) wraps the result in an
    :class:`~repro.core.elastic.ElasticSchedule`: a
    :class:`~repro.core.elastic.DropPlan`, or anything
    ``DropPlan.from_json`` accepts (path, inline JSON, dict).  The
    degraded schedule re-checks Assumption 1 per liveness epoch here, so
    a plan that breaks mixing fails at build time, not mid-run.
    """
    topo = (make_topology(run, n_agents, pods)
            if run.gossip_schedule in ("static", "", None) else None)
    sched = make_schedule(run.gossip_schedule, n_agents, topo=topo, pods=pods,
                          period=run.gossip_period, seed=run.gossip_seed)
    if churn is not None:
        from repro.core import DropPlan, ElasticSchedule
        plan = (churn if isinstance(churn, DropPlan)
                else DropPlan.from_json(churn))
        sched = ElasticSchedule(sched, plan)
        sched.check_assumption1()
    return sched


def gossip_round_step(step, gossip_every: int):
    """Round-index clock for the gossip schedule.

    With ``gossip_every=k > 1`` gossip only executes on steps ≡ k−1 (mod k);
    indexing the schedule by the raw step would then alias against the
    period (any gcd(k, period) > 1 runs only a strict subset of rounds —
    e.g. k=5 on the n=32 round-robin schedule would gossip over offset 16
    forever, never reaching consensus).  Advance the schedule per *executed
    gossip* instead: round = (step // k) mod period cycles through every
    round regardless of k.
    """
    return step // gossip_every if gossip_every > 1 else step


@dataclasses.dataclass(frozen=True)
class Features:
    """The resolved feature matrix of a :class:`RunConfig` — what the
    train step will actually run (DESIGN §5/§6/§9/§12 fallback matrix,
    validated in ONE place by :func:`resolve_features`).

    ``packed_bus``: bus-resident EDM step.  ``overlap``: the delayed
    gossip pipeline.  ``wire``: the run-level error-feedback wire format
    ("f32" = byte-identical legacy wire).  ``groups``: the policy-group
    specs (empty tuple = the single default "dense" group, bit-identical
    to the ungrouped bus)."""

    packed_bus: bool
    overlap: bool
    wire: str
    groups: Tuple[GroupSpec, ...] = ()

    @property
    def grouped(self) -> bool:
        return bool(self.groups)


def resolve_group_specs(run: RunConfig) -> Tuple[GroupSpec, ...]:
    """Parse ``RunConfig.gossip_groups`` into :class:`GroupSpec`s.

    Accepts ``""`` (no groups — the default single-group bus), a JSON
    list (the ``--gossip-groups`` CLI payload, see
    :func:`repro.core.bus.group_specs_from_json`), or comma-separated
    presets: ``moe[:k]`` (expert leaves, default opt-out k=0) and
    ``ssm[:k]`` (conv/SSM state leaves, default local-only k=0) — ``k``
    is the group's ``gossip_every`` (0 = never gossip, k>1 slow-cycle).
    """
    spec = (run.gossip_groups or "").strip()
    if not spec:
        return ()
    if spec.startswith("["):
        return parambus.group_specs_from_json(json.loads(spec))
    specs = []
    for tok in spec.split(","):
        name, _, every = tok.strip().partition(":")
        k = int(every) if every else 0
        if name == "moe":
            from repro.models.moe import expert_group_spec
            specs.append(expert_group_spec(gossip_every=k))
        elif name == "ssm":
            from repro.models.mamba import ssm_state_group_spec
            specs.append(ssm_state_group_spec(gossip_every=k))
        else:
            raise AssertionError(
                f"unknown gossip-groups preset {name!r}: expected 'moe[:k]',"
                " 'ssm[:k]', or a JSON list of group specs "
                '([{"name": ..., "match": [...], "gossip_every": ..., '
                '"wire": ...}, ...])')
    return tuple(specs)


def resolve_features(run: RunConfig) -> Features:
    """Resolve a :class:`RunConfig` to its :class:`Features` — THE
    validation point for the feature compatibility matrix.

    * packed bus (DESIGN §5): explicit ``run.packed_bus`` wins; the None
      default turns it on for the production ``algorithm="edm"`` +
      ``gossip_engine="ppermute"`` combination.  Requires
      ``algorithm="edm"`` and ``agents in ("data", "pod")``.
    * overlap (DESIGN §6): ``"delayed"`` needs the packed bus (ONE
      in-flight buffer), ``gossip_every == 1`` (a payload in flight every
      step) and no ``gossip_dtype`` cast.
    * wire (DESIGN §9): bf16/int8 need the packed bus (bus-shaped EF
      residual) and exclude the ``gossip_dtype`` cast lever.
    * policy groups (DESIGN §12): need the packed bus (groups are row
      ranges of the superbuffer), run-level ``gossip_every == 1`` (the
      cadence moves into each group), an f32 run-level wire (per-group
      wire formats are stateless; the EF residual is a whole-bus,
      single-group feature), no overlap, and no ``gossip_dtype`` cast.

    Every violation raises with the lever to flip.  The legacy
    ``use_packed_bus`` / ``use_overlap`` / ``use_wire`` helpers are thin
    deprecated wrappers over this function.
    """
    if run.packed_bus is not None:
        packed = run.packed_bus
        if packed:
            assert run.algorithm == "edm", \
                f"packed_bus supports algorithm='edm', got " \
                f"{run.algorithm!r} — unset packed_bus or switch algorithm"
            assert run.agents in ("data", "pod"), \
                f"packed_bus supports agents='data'|'pod', got {run.agents!r}"
    else:
        packed = (run.algorithm == "edm" and run.gossip_engine == "ppermute"
                  and run.agents in ("data", "pod"))

    if run.overlap in ("off", "", None):
        overlap = False
    else:
        assert run.overlap == "delayed", \
            f"RunConfig.overlap must be 'off' or 'delayed', got " \
            f"{run.overlap!r}"
        assert packed, \
            "overlap='delayed' needs the packed bus (DESIGN §6): the " \
            "in-flight payload is one (A, rows, 128) buffer, not a leaf " \
            "set — use algorithm='edm' with gossip_engine='ppermute' or " \
            "packed_bus=True"
        assert run.gossip_every == 1, \
            "overlap='delayed' composes with gossip_every=1 only (the " \
            "pipeline keeps a payload in flight every step)"
        assert run.gossip_dtype in ("float32", "", None), \
            "overlap='delayed' rejects the gossip_dtype cast lever (a " \
            "synchronous-path lever; use the error-feedback wire codec " \
            "RunConfig.wire instead — it composes, DESIGN §6/§9 fallback " \
            "matrix)"
        overlap = True

    fmt = run.wire or "f32"
    assert fmt in WIRE_FORMATS, \
        f"RunConfig.wire must be one of {WIRE_FORMATS}, got {fmt!r}"
    if fmt != "f32":
        assert packed, \
            "wire != 'f32' needs the packed bus (DESIGN §9): the codec " \
            "and the bus-resident residual operate on the (A, rows, 128) " \
            "superbuffer"
        assert run.gossip_dtype in ("float32", "", None), \
            "wire != 'f32' is mutually exclusive with gossip_dtype != " \
            "float32 (the error-feedback codec replaces the cast-on-wire " \
            "lever)"

    groups = resolve_group_specs(run)
    if groups:
        assert packed, \
            "gossip_groups need the packed bus (DESIGN §12): policy " \
            "groups are row ranges of the (A, rows, 128) superbuffer — " \
            "use algorithm='edm' with gossip_engine='ppermute' or " \
            "packed_bus=True"
        assert run.gossip_every == 1, \
            "gossip_groups replace the run-level gossip_every: set " \
            "gossip_every=1 and put the cadence on each group's " \
            "gossip_every instead (DESIGN §12)"
        assert not overlap, \
            "gossip_groups do not compose with overlap='delayed' yet (the " \
            "pipeline carries ONE whole-bus payload; per-group staleness " \
            "is future work) — run overlap='off'"
        assert fmt == "f32", \
            "gossip_groups exclude the run-level error-feedback wire " \
            "(the EF residual is whole-bus); set per-group wire formats " \
            "in the group specs instead (stateless quantization)"
        assert run.gossip_dtype in ("float32", "", None), \
            "gossip_groups exclude the gossip_dtype cast lever; set " \
            "per-group wire formats in the group specs instead"
    return Features(packed, overlap, fmt, groups)


def use_packed_bus(run: RunConfig) -> bool:
    """Deprecated: use :func:`resolve_features`\\ ``(run).packed_bus``."""
    warnings.warn("use_packed_bus(run) is deprecated; use "
                  "resolve_features(run).packed_bus", DeprecationWarning,
                  stacklevel=2)
    return resolve_features(run).packed_bus


def use_overlap(run: RunConfig) -> bool:
    """Deprecated: use :func:`resolve_features`\\ ``(run).overlap``."""
    warnings.warn("use_overlap(run) is deprecated; use "
                  "resolve_features(run).overlap", DeprecationWarning,
                  stacklevel=2)
    return resolve_features(run).overlap


def use_wire(run: RunConfig) -> str:
    """Deprecated: use :func:`resolve_features`\\ ``(run).wire``."""
    warnings.warn("use_wire(run) is deprecated; use "
                  "resolve_features(run).wire", DeprecationWarning,
                  stacklevel=2)
    return resolve_features(run).wire


def bus_layout_for(model: Model, n_agents: int, shards: int = 1,
                   groups: Tuple[GroupSpec, ...] = ()) -> parambus.BusLayout:
    """Cached bus layout of ``model``'s parameter tree with a leading agent
    axis — the single layout object shared by ``init_state``, the train
    step and checkpointing (shape-only, no allocation).  ``shards`` is the
    FSDP row-shard count of the shard-resident mode (DESIGN §7);
    ``groups`` the policy-group specs (DESIGN §12, usually
    ``resolve_features(run).groups``)."""
    return parambus.layout_of(model, n_agents, shards=shards,
                              groups=tuple(groups) or None)


def make_group_plans(run: RunConfig, layout: parambus.BusLayout,
                     sched: GossipSchedule, pods: int = 1):
    """Resolve a grouped layout into per-group :class:`GroupPlan`s.

    Every gossiping group gets its schedule — the run's ``sched`` unless
    the group names an override — and **Assumption 1 is re-checked per
    group** (each group's round sequence must be doubly stochastic with
    positive diagonal and a positive period-product spectral gap on the
    gossiping block); a policy that breaks mixing for any group fails at
    build time.  Opt-out groups (``gossip_every == 0``) carry no schedule
    and no codec — the group mixer never builds collectives for their
    rows.  Per-group wire formats resolve to stateless codecs on the
    layout's block grid.
    """
    plans = []
    for g in layout.groups:
        if g.gossip_every == 0 or g.rows == 0:
            plans.append(GroupPlan(g, None, None))
            continue
        gsched = sched
        if g.schedule:
            grun = dataclasses.replace(run, gossip_schedule=g.schedule)
            gsched = make_gossip_schedule(grun, sched.n_agents, pods)
        gsched.check_assumption1()
        codec = (make_codec(g.wire, layout.block_rows)
                 if g.wire != "f32" else None)
        plans.append(GroupPlan(g, gsched, codec))
    return plans


def _cast_mixer(mix, dtype: Optional[str]):
    """Optionally gossip in a lower-precision payload (§Perf lever);
    ``accumulate_f32`` restores the original leaf dtypes on the way out."""
    if not dtype or dtype == "float32":
        return mix
    dt = jnp.dtype(dtype)
    return accumulate_f32(
        lambda tree: mix(jax.tree.map(lambda x: x.astype(dt), tree)))


def shard_local_edm_update(mesh, bus_spec, *, alpha: float, beta: float,
                           block_rows: int, fmt: str = "f32"):
    """The fused bus EDM update, ``shard_map``-wrapped over ``bus_spec``.

    Mosaic kernels cannot be partitioned by XLA, so wherever a mesh
    carries the agent axis the kernel runs per shard on its own
    ``(A_local, rows_local, 128)`` block — griddable by the layout
    contract.  ``bus_spec`` is ``P(agent_axes)`` (agents="data") or
    ``P(agent_axes, shard_axes)`` (agents="pod", DESIGN §7).  ``fmt="f32"``
    gives ``update(x, g, m, psi) -> (m', ψ', φ)``; ``"bf16"``/``"int8"``
    give the error-feedback variant ``update(x, g, m, psi, e) -> (m', ψ',
    payload, e')``, whose int8 scales are ``(A, nb)`` blocks sharded like
    the bus.
    """
    from repro.kernels import ops as kops

    if fmt == "f32":
        body = functools.partial(kops.edm_update_bus, alpha=alpha,
                                 beta=beta, block_rows=block_rows)
        in_specs, out_specs = (bus_spec,) * 4, (bus_spec,) * 3
    else:
        body = functools.partial(kops.edm_update_bus_ef, alpha=alpha,
                                 beta=beta, fmt=fmt, block_rows=block_rows)
        pay_spec = (bus_spec, bus_spec) if fmt == "int8" else bus_spec
        in_specs = (bus_spec,) * 5
        out_specs = (bus_spec, bus_spec, pay_spec, bus_spec)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def step_consensus(mesh, agent_axes, shard_axes, *, use_fused_kernel: bool,
                   block_rows: int) -> Callable:
    """The packed-bus step's consensus metric ‖X − X̄‖²_F, chosen once for
    both step bodies.

    The ``bus_consensus`` kernel reads the bus once, but each of its tiles
    holds every agent's copy of its rows, so it runs only where a device
    holds the whole bus: with the fused kernels on, and no mesh or a mesh
    whose agent axes have size 1 with the rows unsharded (``shard_map``-
    wrapped there, since a bare pallas_call cannot be partitioned).  Agents
    split across devices (a ring over chips, ``agents="pod"``) and the
    unfused path keep the XLA expression :func:`bus_consensus`, whose agent
    mean is a cross-device exchange there anyway.
    """
    from repro.kernels import ops as kops

    if not use_fused_kernel or shard_axes is not None:
        return bus_consensus
    kernel = functools.partial(kops.bus_consensus, block_rows=block_rows)
    if mesh is None:
        return kernel
    names = ((agent_axes,) if isinstance(agent_axes, str)
             else tuple(agent_axes or ()))
    if any(mesh.shape[n] != 1 for n in names):
        return bus_consensus
    return jax.shard_map(kernel, mesh=mesh, in_specs=(P(names),),
                         out_specs=P(), check_vma=False)


def build_train_step(model: Model, run: RunConfig, topo,
                     use_fused_kernel: bool = False, mesh=None,
                     agent_axes=None, shard_axes=None,
                     straggler_plan=None, pods: int = 1) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics).

    batch leaves: (A, per_agent_batch, ...).

    ``topo`` is a :class:`Topology` (wrapped into a period-1
    :class:`StaticSchedule`) or a :class:`GossipSchedule`; ``state["step"]``
    is threaded into the mixer so step t gossips over round t mod period —
    and because the mixer is bound per step, EDM's bias-corrected payload
    φ = ψ' + x − ψ is mixed with the *same* round's W that defines the
    step's combine, keeping the exact-diffusion consistency per step
    (DESIGN §4).

    ``run.gossip_engine`` selects the mixing engine; the ppermute engine
    additionally needs ``mesh``/``agent_axes`` (an agent block per mesh
    slice, see DESIGN §3–4) and honors ``use_fused_kernel`` for its combine,
    so ``engine="ppermute"`` + ``use_fused_kernel=True`` composes the fused
    gossip path with the fused EDM update end-to-end.

    With the packed bus active (:func:`use_packed_bus`, DESIGN §5) the step
    runs **bus-resident**: ``state["params"]`` / ``state["opt"]`` are
    ``(A, rows, 128)`` superbuffers, the tree is unpacked only for
    loss/grad, the EDM update is ONE kernel over the whole bus and the
    gossip ships one payload per term.  Jit the returned function with
    ``donate_argnums=(0,)`` so XLA aliases the bus buffers in place.

    With ``run.overlap="delayed"`` (:func:`use_overlap`, DESIGN §6) the
    step is restructured into **issue → compute → complete** phases: the
    live double-buffered payload φ(t) (``state["pipeline"]``) has its
    gossip permutes issued *before* the backward pass, gradients are
    evaluated at the pre-mix iterate φ(t) (the one-step-stale-mixing
    variant of EDM), and the combine + EDM update run after — so the wire
    sits in the backward pass's shadow instead of on the critical path.
    ``overlap="off"`` is bit-identical to the synchronous bus step.

    Both step bodies name their layers with ``jax.named_scope`` —
    ``bus_unpack``, ``grad``, ``bus_pack``, ``edm_update_bus`` and
    ``step_metrics`` (DESIGN §13) — which reaches the compiled step only as
    the ``op_name`` metadata a device trace is read by.

    With ``shard_axes`` set (``agents="pod"`` + FSDP, DESIGN §7) the bus's
    row axis is sharded over that mesh axis: the gossip permutes, the
    combine and the fused EDM update all run on each shard's own row block,
    and every bus-shaped intermediate is pinned to the
    ``P(agent_axes, shard_axes)`` sharding.  Whenever ``mesh`` carries the
    agent axis, in either mode, the fused EDM update is shard_map-wrapped
    (:func:`shard_local_edm_update`): a bare pallas_call cannot be
    partitioned.

    ``straggler_plan`` (a :class:`~repro.core.elastic.StragglerPlan`,
    DESIGN §8) composes with the overlap pipeline only: each step's late
    slot mask is threaded into ``complete``, degrading late gossip terms
    to self-weight instead of blocking on their payloads.  Churn rides in
    through ``topo`` itself — hand an
    :class:`~repro.core.elastic.ElasticSchedule` and every engine applies
    the liveness-degraded round of the step's epoch.
    """
    sched = topo if isinstance(topo, GossipSchedule) else StaticSchedule(topo)
    feats = resolve_features(run)
    overlap = feats.overlap
    kw = dict(use_fused_kernel=use_fused_kernel) if run.algorithm == "edm" else {}
    packed = feats.packed_bus
    shards = 1
    bus_spec = update_spec = None
    if mesh is not None and agent_axes is not None:
        agent_entry = (tuple(agent_axes)
                       if isinstance(agent_axes, (tuple, list)) else agent_axes)
        update_spec = P(agent_entry)
    if shard_axes is not None:
        assert packed, "shard_axes composes with the packed bus only"
        assert update_spec is not None, \
            "shard-resident gossip needs mesh= and agent_axes="
        shards = int(mesh.shape[shard_axes])
        bus_spec = update_spec = P(agent_entry, shard_axes)
    layout = (bus_layout_for(model, sched.n_agents, shards=shards,
                             groups=feats.groups)
              if packed else None)
    grouped = packed and layout.is_grouped
    wire_fmt = feats.wire
    # the codec's int8 scale blocks ARE the layout's (block_rows, 128) grid
    # tiles, and rows is a multiple of block_rows × shards — shard-local
    # encode/decode by construction (DESIGN §9).
    codec = (make_codec(wire_fmt, layout.block_rows)
             if packed and wire_fmt != "f32" else None)

    def pin_bus(b):
        """Keep bus-shaped intermediates row-sharded (no-op off pod mode)."""
        if bus_spec is None:
            return b
        from jax.sharding import NamedSharding
        return jax.lax.with_sharding_constraint(
            b, NamedSharding(mesh, bus_spec))

    fused_update = None
    fused_update_ef = None
    if packed and update_spec is not None and use_fused_kernel:
        fused_update = shard_local_edm_update(
            mesh, update_spec, alpha=run.alpha, beta=run.beta,
            block_rows=layout.block_rows)
        if codec is not None:
            fused_update_ef = shard_local_edm_update(
                mesh, update_spec, alpha=run.alpha, beta=run.beta,
                block_rows=layout.block_rows, fmt=codec.fmt)

    consensus_of = (step_consensus(mesh, agent_axes, shard_axes,
                                   use_fused_kernel=use_fused_kernel,
                                   block_rows=layout.block_rows)
                    if packed else None)

    base_mix = None
    if grouped:
        # group-aware bus mixer (DESIGN §12): one permute plan per active
        # group per step — opt-out rows never touch a collective, and each
        # group runs its own cadence / schedule / wire codec.  Assumption 1
        # is re-checked per group inside make_group_plans.
        base_mix = make_group_mixer(
            make_group_plans(run, layout, sched, pods),
            engine=run.gossip_engine, mesh=mesh, agent_axes=agent_axes,
            use_fused_kernel=use_fused_kernel, shard_axes=shard_axes)
    elif not overlap:
        base_mix = build_mixer(
            sched, mode="schedule", engine=run.gossip_engine, mesh=mesh,
            agent_axes=agent_axes, use_fused_kernel=use_fused_kernel,
            shard_axes=shard_axes, wire=codec)

    def opt_at(step, mix_override=None):
        """Algorithm with the mixer bound to ``step``'s gossip round (the
        bus-resident EDM when the packed bus is active; its EF-compressed
        variant when a wire codec is active, DESIGN §9)."""
        if packed and codec is not None:
            if mix_override is not None:
                # gossip-skipped local step (gossip_every > 1): plain EDM
                # recursion, nothing on the wire, so nothing is quantized
                # and the residual carries untouched to the next gossiping
                # step (cross-round carry, DESIGN §9).
                inner = make_edm_bus(run.alpha, run.beta, mix_override,
                                     block_rows=layout.block_rows,
                                     use_fused_kernel=use_fused_kernel,
                                     update=fused_update)

                def local_step(x, g, st):
                    x2, sub = inner.step(x, g, {"m": st["m"],
                                                "psi": st["psi"]})
                    return x2, {**sub, "e": st["e"]}

                return DecOptimizer("edm_bus_local", inner.init, local_step)
            return make_edm_bus_ef(run.alpha, run.beta,
                                   functools.partial(base_mix, step=step),
                                   codec, block_rows=layout.block_rows,
                                   use_fused_kernel=use_fused_kernel,
                                   update=fused_update_ef)
        mix = mix_override if mix_override is not None else _cast_mixer(
            functools.partial(base_mix, step=step), run.gossip_dtype)
        if packed:
            return make_edm_bus(run.alpha, run.beta, mix,
                                block_rows=layout.block_rows,
                                use_fused_kernel=use_fused_kernel,
                                update=fused_update)
        return make_optimizer(run.algorithm, alpha=run.alpha, beta=run.beta,
                              mix=mix, **kw)

    def agent_loss(params, batch):
        """(loss, counters): the model's step counters (``moe_rows_held``
        of a dropless expert share), summed over agents into the step's
        metrics."""
        if model.cfg.family == "encdec":
            return model.loss(params, batch, remat=run.remat), {}
        return model.loss(params, batch, remat=run.remat,
                          remat_policy=run.remat_policy, with_stats=True)

    grad_fn = jax.vmap(jax.value_and_grad(agent_loss, has_aux=True))

    def counters(stats):
        return {k: jnp.sum(v) for k, v in stats.items()}

    lr_sched = None
    if run.warmup_steps or run.total_steps:
        lr_sched = warmup_cosine(run.warmup_steps or 1,
                                 run.total_steps or 10**9)

    def scaled_grads(grads, step):
        """LR schedule as gradient scaling — the one call site both the
        synchronous and the overlapped step share."""
        if lr_sched is None:
            return grads
        return scale_grads(grads, step, lr_sched)

    assert straggler_plan is None or overlap, \
        "straggler_plan composes with overlap='delayed' only (the " \
        "synchronous step has no payload stack to degrade)"

    if overlap:
        issue, complete = build_mixer(
            sched, mode="overlap", engine=run.gossip_engine, mesh=mesh,
            agent_axes=agent_axes, use_fused_kernel=use_fused_kernel,
            shard_axes=shard_axes, wire=codec)
        if straggler_plan is not None:
            assert straggler_plan.n_terms == complete.n_terms, \
                f"StragglerPlan.n_terms={straggler_plan.n_terms} must match " \
                f"the overlap payload stack arity K={complete.n_terms}"
        # the delayed pipeline mixes FIRST (the in-flight payload), then
        # runs the local EDM recursion on the mixed iterate — so the
        # optimizer's own mix is the identity and the wire lives in the
        # issue/complete phases around the backward pass.
        local_opt = make_edm_bus(run.alpha, run.beta, mix=lambda t: t,
                                 block_rows=layout.block_rows,
                                 use_fused_kernel=use_fused_kernel,
                                 update=fused_update)

        def encode_pipeline(c):
            """Issue-time EF encode of the corrected payload c = φ + e
            (DESIGN §9: quantize at issue time, residual accounted at
            complete time).  Shard_map-wrapped in shard-resident mode so
            the per-block reductions never tempt GSPMD into a gather."""
            if bus_spec is None:
                return encode_ef(codec, c)
            pay_spec = ((bus_spec, bus_spec) if codec.fmt == "int8"
                        else bus_spec)
            return jax.shard_map(functools.partial(encode_ef, codec),
                                 mesh=mesh, in_specs=(bus_spec,),
                                 out_specs=(pay_spec, bus_spec),
                                 check_vma=False)(c)

        def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
            pipe = state["pipeline"]
            g_step = state["step"]          # gossip_every == 1 under overlap
            # ISSUE: put the round's permutes of φ(t) on the wire — nothing
            # below until `complete` depends on them.  With a wire codec the
            # payload is quantized HERE (φ(t) + e(t) encoded, residual split
            # off), so the in-flight bytes are already compressed; the
            # pipeline buffer itself stays f32 (checkpoint/resize shapes are
            # wire-independent).
            with jax.named_scope("edm_update_bus"):
                phi = parambus.pipeline_payload(pipe)
                if codec is not None:
                    c = pin_bus(phi + state["opt"]["e"])
                    enc, e_new = encode_pipeline(c)
                    payloads = issue(enc, g_step)
                else:
                    payloads = issue(phi, g_step)
            # COMPUTE: gradients at the pre-mix local iterate φ(t); the
            # whole fwd/bwd is independent of the in-flight permutes.
            with jax.named_scope("bus_unpack"):
                params_tree = parambus.unpack_tree(layout, phi)
            with jax.named_scope("grad"):
                (losses, stats), grads = grad_fn(params_tree, batch)
                grads = scaled_grads(grads, state["step"])
            with jax.named_scope("bus_pack"):
                g_bus = pin_bus(parambus.pack_tree(layout, grads))
            # COMPLETE: weighted combine of the landed payloads (decode
            # folded in when wire-coded), then the bus-resident EDM update
            # on the mixed iterate x(t) = W(t) φ̃(t).  Late slots
            # (straggler_plan) degrade to self-weight (DESIGN §8).
            with jax.named_scope("edm_update_bus"):
                late = (straggler_plan.late_at(g_step)
                        if straggler_plan is not None else None)
                x_mixed = complete(payloads, g_step, late=late)
                if codec is not None:
                    sub = {"m": state["opt"]["m"], "psi": state["opt"]["psi"]}
                    phi_new, new_opt = local_opt.step(x_mixed, g_bus, sub)
                    new_opt = {**new_opt, "e": e_new}
                else:
                    phi_new, new_opt = local_opt.step(x_mixed, g_bus,
                                                      state["opt"])
                new_pipe = parambus.pipeline_advance(pipe, phi_new)
            with jax.named_scope("step_metrics"):
                metrics = {
                    "loss": jnp.mean(losses),
                    "consensus": consensus_of(x_mixed),
                    "grad_norm": bus_grad_norm(g_bus),
                    **counters(stats),
                }
            return {"params": x_mixed, "opt": new_opt, "pipeline": new_pipe,
                    "step": state["step"] + 1}, metrics

        return train_step

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        with jax.named_scope("bus_unpack"):
            params_tree = (parambus.unpack_tree(layout, state["params"])
                           if packed else state["params"])
        with jax.named_scope("grad"):
            (losses, stats), grads = grad_fn(params_tree, batch)
            grads = scaled_grads(grads, state["step"])
        with jax.named_scope("bus_pack"):
            g_in = (pin_bus(parambus.pack_tree(layout, grads)) if packed
                    else grads)
        with jax.named_scope("edm_update_bus"):
            g_step = gossip_round_step(state["step"], run.gossip_every)
            opt = opt_at(g_step)
            if run.gossip_every > 1:
                # local-EDM: amortize gossip over k steps.  lax.cond — not a
                # dual-evaluation jnp.where — so skip steps execute only the
                # identity-mixer update and never pay the gossip collectives
                # (the round clock `g_step` is replicated, so both branches
                # stay SPMD-consistent).
                local_opt = opt_at(g_step, mix_override=lambda t: t)
                do_gossip = ((state["step"] % run.gossip_every)
                             == run.gossip_every - 1)
                new_params, new_opt = jax.lax.cond(
                    do_gossip,
                    lambda a: opt.step(*a),
                    lambda a: local_opt.step(*a),
                    (state["params"], g_in, state["opt"]))
            else:
                new_params, new_opt = opt.step(state["params"], g_in,
                                               state["opt"])
        with jax.named_scope("step_metrics"):
            if packed:
                # bus-path metrics over each superbuffer (pads are zero, so
                # these equal the per-leaf reductions): the consensus in one
                # pass where a device holds every agent (step_consensus).
                consensus = consensus_of(new_params)
                grad_norm = bus_grad_norm(g_in)
            else:
                consensus = consensus_distance(new_params)
                grad_norm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree.leaves(grads)))
            metrics = {
                "loss": jnp.mean(losses),
                "consensus": consensus,
                "grad_norm": grad_norm,
                **counters(stats),
            }
        return {"params": new_params, "opt": new_opt,
                "step": state["step"] + 1}, metrics

    return train_step


def init_state(model: Model, run: RunConfig, n_agents: int, key,
               shards: int = 1) -> TrainState:
    """All agents start from the same x(0) (paper's initialization).

    With the packed bus active the state is packed ONCE here (DESIGN §5):
    ``params`` is the ``(A, rows, 128)`` superbuffer and ``opt`` holds the
    bus-resident ``m``/``psi``; everything downstream stays in bus layout
    until checkpointing.  The overlapped pipeline (DESIGN §6) additionally
    carries ``pipeline`` — the double-buffered payload ``slot[2]`` with its
    parity bit, seeded with φ(0) = x(0) in the live slot (step 0 then
    reproduces the synchronous step exactly: W x(0) = x(0) at a replicated
    init).  ``shards`` must match the train step's FSDP shard count in
    shard-resident mode (DESIGN §7) so both sides build the same layout.
    """
    params1 = model.init(key)
    params = jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (n_agents,) + l.shape), params1)
    feats = resolve_features(run)
    if feats.packed_bus:
        layout = bus_layout_for(model, n_agents, shards=shards,
                                groups=feats.groups)
        x_bus = parambus.pack_tree(layout, params)
        opt = make_edm_bus(run.alpha, run.beta, mix=lambda t: t,
                           block_rows=layout.block_rows)
        opt_state = opt.init(x_bus)
        if feats.wire != "f32":
            # bus-shaped EF residual (DESIGN §9), e(0) = 0: step 0 then
            # sends Q(φ(0)) exactly like the synchronous compressed step.
            opt_state["e"] = jnp.zeros_like(x_bus)
        state = {"params": x_bus, "opt": opt_state,
                 "step": jnp.zeros((), jnp.int32)}
        if feats.overlap:
            state["pipeline"] = parambus.make_pipeline(x_bus)
        return state
    mix = build_mixer(make_topology(run, n_agents), mode="static")
    opt = make_optimizer(run.algorithm, alpha=run.alpha, beta=run.beta, mix=mix)
    return {"params": params, "opt": opt.init(params),
            "step": jnp.zeros((), jnp.int32)}


# ---------------------------------------------------------------------------
# sharding specs
# ---------------------------------------------------------------------------

def prepend_agent_axis(spec: P, agent_axis, fsdp_axis: Optional[str] = None) -> P:
    """(A, *shape) leaf spec: agent axis over `agent_axis`; optionally shard
    the first unsharded WEIGHT dim over `fsdp_axis` (agents="pod" mode).

    Stacked block leaves carry a leading layer-stack dim (spec entry 0 is
    None); FSDP must land on a weight dim, so skip entry 0 in that case —
    sharding the stack dim would be layer parallelism, and a 9-deep stack on
    a 16-way axis just gets sanitized away (weights silently replicated)."""
    entries = list(spec)
    if fsdp_axis is not None:
        start = 1 if (len(entries) > 1 and entries[0] is None) else 0
        for i in range(start, len(entries)):
            if entries[i] is None:
                entries[i] = fsdp_axis
                break
    return P(agent_axis, *entries)


def state_specs(model: Model, run: RunConfig, multi_pod: bool) -> Dict[str, Any]:
    """PartitionSpecs for the TrainState under the chosen agent granularity."""
    feats = resolve_features(run)
    if feats.packed_bus:
        if run.agents == "pod":
            # shard-resident bus (DESIGN §7): agent axis on 'pod', the
            # bus ROW axis FSDP-sharded over the pod-internal 'data' axis.
            agent_axis = "pod" if multi_pod else None
            spec = P(agent_axis, "data")
        else:
            # one (A, rows, 128) buffer per state slot, agent axis sharded
            # — rows/lane replicated (agents="data" has no FSDP axis free).
            agent_axis = ("pod", "data") if multi_pod else "data"
            spec = P(agent_axis)
        opt_specs = {"m": spec, "psi": spec}
        if feats.wire != "f32":
            opt_specs["e"] = spec   # bus-shaped residual shards like the bus
        specs = {"params": spec, "opt": opt_specs, "step": P()}
        if feats.overlap:
            # slot: (2, A, rows, 128) — the 2-slot dim replicated, then the
            # bus spec shifted right by one; parity is a replicated scalar.
            specs["pipeline"] = {"slot": P(None, *spec), "parity": P()}
        return specs

    base = model.param_specs()

    if run.agents == "data":
        agent_axis = ("pod", "data") if multi_pod else "data"
        fsdp = None
    elif run.agents == "pod":
        agent_axis = "pod" if multi_pod else None
        fsdp = "data"
    else:
        raise ValueError(run.agents)

    lift = lambda s: prepend_agent_axis(s, agent_axis, fsdp)
    pspecs = jax.tree.map(lift, base, is_leaf=lambda s: isinstance(s, P))

    opt_specs: Dict[str, Any] = {}
    # every optimizer state pytree mirrors the params tree
    n_slots = {"edm": ("m", "psi"), "edm_ef": ("m", "psi", "e"),
               "ed": ("m", "psi"), "dsgd": (),
               "dmsgd": ("m",), "dsgt": ("y", "g_prev"),
               "dsgt_hb": ("y", "g_prev", "m"), "decentlam": ("m",),
               "qg": ("m",)}[run.algorithm]
    for slot in n_slots:
        opt_specs[slot] = pspecs
    return {"params": pspecs, "opt": opt_specs, "step": P()}


def batch_spec_tree(model: Model, run: RunConfig, multi_pod: bool):
    """Specs for the (A, b, ...) training batch."""
    if run.agents == "data":
        agent_axis = ("pod", "data") if multi_pod else "data"
        inner = None
    else:
        agent_axis = "pod" if multi_pod else None
        inner = "data"
    cfg = model.cfg
    specs = {"tokens": P(agent_axis, inner, None)}
    if cfg.family in ("vlm", "encdec"):
        specs["frontend"] = P(agent_axis, inner, None, None)
    return specs
