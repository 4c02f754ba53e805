"""Training launcher.

Runs the decentralized EDM trainer: on a TPU at published widths (one
chip holds two blocked agents of smollm_360m — see ``chip_smoke.py``), on
the CPU with ``--smoke`` reduced configs and interpret-mode kernels, which
is how the examples and tests exercise it.

  PYTHONPATH=src python -m repro.launch.train --arch smollm_360m --smoke \
      --steps 20 --agents 4 --algorithm edm

:func:`main` takes an argv list and returns the run's record (per-step
losses, compile and step seconds, the compiled step, the final state), so
scripts drive exactly the CLI's path.
"""
from __future__ import annotations

import argparse
import functools
import time

import jax

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.configs.base import RunConfig
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.flags import add_run_flags, run_config_overrides
from repro.models import build_model
from repro.train import (build_train_step, bus_layout_for, checkpoint,
                         init_state, make_gossip_schedule, resolve_features,
                         state_specs)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--agents", default="4",
                    help="agent count (int, agents='data'), or 'pod' for "
                         "shard-resident pod agents (DESIGN §7): one agent "
                         "per pod of --shards FSDP devices, --pods agents "
                         "total, gossip over row-sharded buses")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--per-agent-batch", type=int, default=1)
    ap.add_argument("--pods", type=int, default=1,
                    help="pod count for torus/hier topologies; with "
                         "--agents pod, the number of pod-agents")
    ap.add_argument("--shards", type=int, default=0,
                    help="--agents pod: FSDP devices per pod-agent "
                         "(0 = device_count // pods)")
    ap.add_argument("--fused-kernel", action="store_true",
                    help="fused Pallas EDM update + gossip combine")
    # every RunConfig-backed lever (--algorithm, --topology, --gossip-*,
    # --packed-bus, --overlap, --wire, --gossip-groups, --alpha, --beta)
    # comes off the shared table — see repro.launch.flags.RUN_FLAGS
    add_run_flags(ap)
    ap.add_argument("--phi", type=float, default=0.2,
                    help="Dirichlet heterogeneity of the token streams")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--churn", default="",
                    help="liveness fault-injection plan (DESIGN §8): a JSON "
                         "file path or inline JSON DropPlan "
                         '({"n_agents": N, "epochs": [{"start": 0, '
                         '"down": [..]}, ..]}); wraps the gossip schedule '
                         "in an ElasticSchedule whose degraded rounds are "
                         "re-checked against Assumption 1 per epoch")
    ap.add_argument("--resume", default="",
                    help="checkpoint to resume from; the saved agent count "
                         "may differ from --agents (elastic join/leave): "
                         "surviving agents restore bit-exactly, re-admitted "
                         "agents join at the consensus mean with ψ := x")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    pod_agents = args.agents == "pod"
    if pod_agents:
        assert args.gossip_engine == "ppermute", \
            "--agents pod rides the shard-resident ppermute path " \
            "(set --gossip-engine ppermute)"
        n_agents = args.pods
        shards = args.shards or max(jax.device_count() // args.pods, 1)
    else:
        n_agents = int(args.agents)
        shards = 1
    run = RunConfig(global_batch=n_agents * args.per_agent_batch,
                    seq_len=args.seq,
                    agents="pod" if pod_agents else "data",
                    **run_config_overrides(args))
    feats = resolve_features(run)
    sched = make_gossip_schedule(run, n_agents,
                                 pods=1 if pod_agents else args.pods,
                                 churn=args.churn or None)
    mesh = agent_axes = shard_axes = None
    if args.gossip_engine == "ppermute":
        from repro.launch.mesh import gossip_agent_axes, make_gossip_mesh
        if pod_agents:
            mesh = make_gossip_mesh(n_agents, pods=n_agents, shards=shards)
            agent_axes = gossip_agent_axes(mesh, sharded=True)
            shard_axes = "data"
        else:
            mesh = make_gossip_mesh(n_agents, pods=args.pods,
                                    agents_per_device=args.agents_per_device)
            agent_axes = gossip_agent_axes(mesh)
    stats = sched.product_spectral_stats()
    # --topology only feeds the static schedule; don't print it otherwise
    topo_str = (f"topo={args.topology} " if args.gossip_schedule == "static"
                else "")
    shard_str = f"x{shards}shards" if pod_agents else ""
    print(f"arch={cfg.name} ({cfg.n_params()/1e6:.1f}M params) "
          f"agents={n_agents}{shard_str} {topo_str}"
          f"schedule={sched.name} period={sched.period} "
          f"λ_prod={stats['lambda']:.4f} "
          f"alg={args.algorithm} engine={args.gossip_engine}"
          f"{' +fused' if args.fused_kernel else ''}"
          f"{' +bus' if feats.packed_bus else ''}"
          f"{' +overlap' if feats.overlap else ''}"
          f"{' wire=' + feats.wire if feats.wire != 'f32' else ''}"
          f"{' groups=' + ','.join(g.name for g in feats.groups) if feats.groups else ''}")

    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       n_agents=n_agents, phi=args.phi)

    def sample(key):
        b = data.sample(key, args.per_agent_batch)
        if cfg.family in ("vlm", "encdec"):
            import jax.numpy as jnp
            b["frontend"] = jax.random.normal(
                jax.random.fold_in(key, 1),
                (n_agents, args.per_agent_batch, cfg.n_frontend_tokens,
                 cfg.d_model), dtype=jnp.dtype(cfg.dtype))
        return b

    layout = (bus_layout_for(model, n_agents, shards=shards,
                             groups=feats.groups)
              if feats.packed_bus else None)
    init = functools.partial(init_state, model, run, n_agents, shards=shards)
    shardings = None
    if pod_agents or (mesh is not None and feats.packed_bus):
        # build the bus state where it lives: agent axis over the mesh's
        # agent axes, rows FSDP-sharded over 'data' in pod mode
        # (state_specs, DESIGN §7) — a full-width state for several agents
        # does not fit one device before it is split
        from jax.sharding import NamedSharding, PartitionSpec
        shardings = jax.tree.map(
            lambda sp: NamedSharding(mesh, sp),
            state_specs(model, run,
                        multi_pod=pod_agents or "pod" in mesh.axis_names),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        init = jax.jit(init, out_shardings=shardings)
    state = init(jax.random.PRNGKey(0))
    if args.resume:
        # elastic join/leave: the checkpoint's agent count may differ from
        # this run's — survivors restore bit-exactly, joiners take the
        # consensus mean with ψ := x (DESIGN §8)
        state = checkpoint.load_state_resized(args.resume, state,
                                              layout=layout)
        print(f"resumed <- {args.resume} @ step {int(state['step'])}")
        if shardings is not None:
            state = jax.tree.map(jax.device_put, state, shardings)
    # bus-resident state: donate so XLA aliases the superbuffers in place
    # (params/m/psi update without a second HBM copy, DESIGN §5)
    donate = (0,) if feats.packed_bus else ()
    step = jax.jit(build_train_step(model, run, sched,
                                    use_fused_kernel=args.fused_kernel,
                                    mesh=mesh, agent_axes=agent_axes,
                                    shard_axes=shard_axes,
                                    pods=1 if pod_agents else args.pods),
                   donate_argnums=donate)
    key = jax.random.PRNGKey(1)
    key, kd = jax.random.split(key)
    batch = sample(kd)
    # compile ahead of the loop (the jit call below reuses this executable)
    # so compile time is reported apart from step time
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    print(f"compiled train step in {compile_s:.1f}s", flush=True)
    losses, consensus, step_s = [], [], []
    t0 = time.perf_counter()
    for t in range(args.steps):
        if t:
            key, kd = jax.random.split(key)
            batch = sample(kd)
        t1 = time.perf_counter()
        state, m = jax.block_until_ready(step(state, batch))
        step_s.append(time.perf_counter() - t1)
        losses.append(float(m["loss"]))
        consensus.append(float(m["consensus"]))
        if t % 5 == 0 or t == args.steps - 1:
            print(f"step {t:4d} loss={losses[-1]:.4f} "
                  f"consensus={consensus[-1]:.2e} "
                  f"({time.perf_counter()-t0:.1f}s)", flush=True)
    if args.ckpt:
        # full resumable state (params + opt + step + pipeline), stored as
        # logical trees — layout-, sharding- and overlap-mode-independent
        # on disk
        checkpoint.save_state(args.ckpt, state, layout=layout)
        print(f"checkpoint -> {args.ckpt}")
    return {"losses": losses, "consensus": consensus,
            "compile_s": compile_s, "step_s": step_s,
            "compiled": compiled, "state": state}


if __name__ == "__main__":
    main()
