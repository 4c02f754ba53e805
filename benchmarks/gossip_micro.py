"""Microbenchmarks of the gossip/optimizer hot path (CPU wall-clock; the
derived column carries the analytically modeled TPU HBM-traffic ratio).

Every multi-device sweep re-execs this module in a child pinned to
``JAX_PLATFORMS=cpu`` with a forced host device count: those children are
CPU counting benches by design (permutes, launches, modeled bytes), and
pinning keeps them off any accelerator — a child that reached for a chip
its parent holds would fail or hang.  None of these times is a chip time.

Three parts:

* in-process engine benches on the current device set (dense vs shifts,
  EDM step fused vs unfused);
* an engine × topology × fused sweep (``--sweep``) that needs one device
  per agent — ``run()`` launches it in a subprocess with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=32`` so it works
  regardless of the parent's device count.  This is the acceptance bench
  for the production ppermute path: on the paper's n=32 ring the
  fused-combine ppermute engine must come in at ≤ the shifts engine;
* an engine × *schedule* sweep (``--schedule``, DESIGN §4) reporting
  per-step wall time AND per-step wire bytes (the model from
  ``repro.core.schedule.wire_bytes_per_step``, now in both logical and
  ``_pack``-padded flavors — the padded column is what a packed payload
  actually ships) for the static exp graph vs the one-peer round-robin
  schedule vs alternating hierarchical — including the blocked
  A=32-on-8-devices ppermute case.  Results land in ``BENCH_gossip.json``
  at the repo root (the bench trajectory artifact CI uploads);
* an end-to-end EDM *step* sweep (``--e2e-step``, DESIGN §5): leaf-wise vs
  bus-resident full EDM steps (per-agent grads synthesized) across model
  sizes, reporting us/step, permutes/step, kernel launches/step and
  modeled HBM bytes padded vs logical for both paths, plus a numerical
  equivalence gate (bus vs leaf-wise on a smoke transformer — nonzero exit
  on divergence, the CI contract).  Results land in ``BENCH_edm_step.json``.
  The same sweep also times the **overlapped gossip pipeline**
  (DESIGN §6): ``overlap="delayed"`` vs the synchronous bus step per size,
  with the measured gossip-only us/step and the fraction of it the
  pipeline hides, written to ``BENCH_overlap.json`` — together with the
  delayed-vs-synchronous **loss-divergence gates** (trajectory envelope on
  the smoke transformer inside the sweep, plus the §E.1 quadratic and
  §E.2 logistic problems under a dense-oracle W; any gate failure raises,
  the CI contract);
* a BLOCK_ROWS autotune (``--autotune-block-rows``): sweeps the kernel
  grid-tile height over {128, 256, 512, 1024} for the fused EDM update and
  the 3-ary gossip combine across bus sizes and prints the argmin per size
  (the ROADMAP "tune BLOCK_ROWS" knob; wall-clock is interpret-mode on CPU
  — re-run on a real TPU for the production number);
* a **sharded vs gathered** gossip sweep (``--sharded``, DESIGN §7): the
  row-sharded ``P('pod', 'data')`` bus vs the rows-replicated pre-§7
  layout on a 2-pod × 4-shard host mesh — us/step and wire bytes/step
  (per-device permute payload drops by the shard factor), with the
  sharded == dense-oracle equivalence gate raising on divergence (the CI
  contract of the ``pod-fsdp-smoke`` job).  Results land in
  ``BENCH_shard.json``;
* a **quantized-wire** sweep (``--wire``, DESIGN §9): f32 vs bf16 vs int8
  gossip wire on an 8-agent host ring — us/step, codec-derived wire
  bytes/step (every byte column in this module now derives from the wire
  codec's ``payload_bytes`` instead of a hardcoded 4 B/elem) and the
  ``compression_ratio`` column, behind oracle/masked/sharded equivalence
  gates; plus the modeled n=32 byte cut (bf16 ≥ 2×, int8 ≥ 3.5× at an
  unchanged permute count) and the §E.1/§E.2 error-feedback divergence
  gates with naive-quantization negative-control rows.  Results land in
  ``BENCH_wire.json``;
* a **policy-group** sweep (``--groups``, DESIGN §12): per
  ``--gossip-groups`` config (ungrouped baseline, 2-group all-gossip,
  expert opt-out, expert slow-cycle) on the smoke MoE transformer —
  group-mixer us/step and the modeled per-group wire bytes over an
  8-step window, behind the segment-composition gates (2-group
  all-gossip == whole-bus mixer bit-exactly; opt-out expert rows come
  back untouched) and the byte-accounting gates (opt-out strictly under
  the baseline; all-gossip − opt-out delta == the experts group's
  modeled bytes exactly).  Results land in ``BENCH_groups.json``.

CLI::

    python -m benchmarks.gossip_micro --schedule round_robin --steps 8
    python -m benchmarks.gossip_micro --schedule all --block-rows 256
    python -m benchmarks.gossip_micro --e2e-step
    python -m benchmarks.gossip_micro --autotune-block-rows
    python -m benchmarks.gossip_micro --sharded
    python -m benchmarks.gossip_micro --wire
    python -m benchmarks.gossip_micro --groups
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(REPO, "BENCH_gossip.json")
BENCH_EDM_JSON = os.path.join(REPO, "BENCH_edm_step.json")
BENCH_OVERLAP_JSON = os.path.join(REPO, "BENCH_overlap.json")
BENCH_SHARD_JSON = os.path.join(REPO, "BENCH_shard.json")
BENCH_ELASTIC_JSON = os.path.join(REPO, "BENCH_elastic.json")
BENCH_WIRE_JSON = os.path.join(REPO, "BENCH_wire.json")
BENCH_GROUPS_JSON = os.path.join(REPO, "BENCH_groups.json")
_SWEEP_MARKER = "SWEEP_CSV_JSON:"
_SCHED_MARKER = "SCHED_JSON:"
_E2E_MARKER = "E2E_JSON:"
_SHARD_MARKER = "SHARD_JSON:"
_ELASTIC_MARKER = "ELASTIC_JSON:"
_WIRE_MARKER = "WIRE_JSON:"
_GROUPS_MARKER = "GROUPS_JSON:"


def _sweep_cases():
    from repro.core import hierarchical, ring
    return [
        ("ring32", ring(32), 1),
        ("hier2x16", hierarchical(2, 16), 2),
        ("hier4x4_ring", hierarchical(4, 4, intra="ring"), 4),
    ]


def sweep(d: int = 1 << 16, iters: int = 20) -> List[str]:
    """Engine × topology × fused sweep; requires >= 32 devices."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import make_mixer
    from repro.launch.mesh import gossip_agent_axes, make_gossip_mesh
    from .common import csv_row, timeit_us

    lines: List[str] = []
    for name, topo, pods in _sweep_cases():
        A = topo.n_agents
        mesh = make_gossip_mesh(A, pods=pods)
        axes = gossip_agent_axes(mesh)
        x = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(0), (A, d)),
            NamedSharding(mesh, P(axes)))
        engines = {
            "shifts": make_mixer(topo, "shifts"),
            "ppermute": make_mixer(topo, "ppermute", mesh=mesh,
                                   agent_axes=axes),
            "ppermute_fused": make_mixer(topo, "ppermute", mesh=mesh,
                                         agent_axes=axes,
                                         use_fused_kernel=True),
        }
        us_shifts = None
        for ename, mixer in engines.items():
            us = timeit_us(jax.jit(mixer), x, iters=iters)
            if ename == "shifts":
                us_shifts = us
            lines.append(csv_row(
                f"gossip/{name}/{ename}", us,
                f"n={A};d={d};terms={len(topo.terms)};"
                f"speedup_vs_shifts={us_shifts / us:.2f}x"))
    return lines


def _schedule_cases(which: str):
    from repro.core import (AlternatingHierarchical, RoundRobinExp,
                            StaticSchedule, exp_graph)
    cases = {
        "static": StaticSchedule(exp_graph(32)),
        "round_robin": RoundRobinExp(32),
        "alt_hier": AlternatingHierarchical(4, 8),
    }
    if which != "all":
        cases = {which: cases[which]}
    return cases


def schedule_sweep(which: str = "all", steps: int = 8, d: int = 1 << 16,
                   iters: int = 20, block_rows: int = 0,
                   wire_fmt: str = "f32") -> List[dict]:
    """Engine × schedule sweep: us/step and wire bytes/step over ``steps``
    consecutive schedule steps (each distinct round is compiled and timed
    once, then weighted by how often it occurs in the window — so steps=8
    over a period-5 schedule weights rounds 0–2 twice).

    Needs 32 host devices.  The blocked config packs the 32 agents onto 8
    devices (B = 4) — the multi-agent-per-device path.  ``block_rows``
    reaches the fused kernel via REPRO_BLOCK_ROWS, which the parent process
    exports before this subprocess imports the kernels; the recorded value
    is the effective one.  ``wire_fmt`` selects the modeled wire format
    (DESIGN §9): the wire-bytes column derives from the codec's payload
    bytes (bf16 = 2 B/elem, int8 = 1 B/elem + per-block scales) instead of
    the pre-§9 hardcoded 4 B/elem; the timed mixers stay f32 here — the
    quantized engines are timed and gated by :func:`wire_sweep`.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import make_schedule_mixer, wire_bytes_per_step
    from repro.core.wire import make_codec
    from repro.kernels.edm_update import BLOCK_ROWS
    from repro.launch.mesh import gossip_agent_axes, make_gossip_mesh
    from .common import timeit_us

    if block_rows:
        assert block_rows == BLOCK_ROWS, \
            (block_rows, BLOCK_ROWS, "REPRO_BLOCK_ROWS not exported?")
    codec = make_codec(wire_fmt, 8)
    results = []
    for sname, sched in _schedule_cases(which).items():
        A = sched.n_agents
        configs = {
            "shifts": dict(engine="shifts", apd=1),
            "ppermute": dict(engine="ppermute", apd=1),
            "ppermute_fused": dict(engine="ppermute", apd=1, fused=True),
            "ppermute_fused_b4": dict(engine="ppermute", apd=4, fused=True),
        }
        for cname, c in configs.items():
            apd = c["apd"]
            mesh = axes = None
            if c["engine"] == "ppermute":
                mesh = make_gossip_mesh(A, agents_per_device=apd)
                axes = gossip_agent_axes(mesh)
            mix = make_schedule_mixer(sched, c["engine"], mesh=mesh,
                                      agent_axes=axes,
                                      use_fused_kernel=c.get("fused", False))
            x = jax.random.normal(jax.random.PRNGKey(0), (A, d))
            if mesh is not None:
                x = jax.device_put(x, NamedSharding(mesh, P(axes)))
            # one jitted application per distinct round (concrete step →
            # no switch), weighted over the `steps`-step window
            us_round = {r: timeit_us(jax.jit(lambda t, r=r: mix(t, step=r)),
                                     x, iters=max(iters // sched.period, 2))
                        for r in range(sched.period)}
            us = sum(us_round[t % sched.period] for t in range(steps)) / steps
            wire = sum(wire_bytes_per_step(sched, t, elems_per_agent=d,
                                           agents_per_device=apd,
                                           engine=c["engine"], codec=codec)
                       for t in range(steps)) / steps
            # pad-waste accounting: the wire ships *logical* payloads (the
            # permutes run on raw leaves), but the fused combine kernel
            # streams each per-device shard padded to whole
            # (BLOCK_ROWS, 128) grid tiles by kernels/ops._pack — the
            # padded column is the combine's true HBM traffic, which the
            # logical model undercounts for any d not tile-aligned.
            from repro.kernels.ops import padded_size
            n_dev = A // apd
            n_streams = sum(len(sched.round(t).terms) + 1
                            for t in range(steps)) / steps
            combine_logical = int(n_streams * A * d * 4)
            combine_padded = (int(n_streams * n_dev
                                  * padded_size(apd * d, BLOCK_ROWS) * 4)
                              if c.get("fused") else combine_logical)
            results.append({
                "schedule": sname, "config": cname, "engine": c["engine"],
                "agents": A, "agents_per_device": apd, "d": d,
                "period": sched.period, "steps": steps,
                "block_rows": BLOCK_ROWS,
                "us_per_step": round(us, 1),
                "wire_format": wire_fmt,
                "wire_bytes_per_step": int(wire),
                "compression_ratio": round(codec.compression_ratio(d), 3),
                "combine_hbm_bytes_per_step": combine_logical,
                "combine_hbm_bytes_padded_per_step": combine_padded,
                "permutes_per_step": max(
                    sum(1 for t in rnd.terms if t.shift != 0)
                    for rnd in sched.rounds),
            })
    return results


# ---------------------------------------------------------------------------
# end-to-end EDM step: leaf-wise vs bus-resident (DESIGN §5)
# ---------------------------------------------------------------------------

# model size per benchmarked config (dense family): depth scales the
# parameter set at fixed width, isolating the per-leaf launch/permute
# overhead the bus amortizes from width-bound grad compute.  This repo's
# models stack layers into scanned leaves, so the leaf count stays
# moderate (L=12) and the measured delta is a LOWER bound on what an
# unstacked ~100-leaf tree gains from the bus.
E2E_SIZES = {
    "small": dict(n_layers=2, d_model=64, d_ff=128),
    "medium": dict(n_layers=6, d_model=64, d_ff=128),
    "large": dict(n_layers=12, d_model=64, d_ff=128),
}


def e2e_step_sweep(iters: int = 6) -> List[dict]:
    """Leaf-wise vs bus-resident **full train step** (fwd + bwd + EDM update
    + gossip; ppermute engine, n=8 ring) across model sizes.

    Wall-clock times the integrated jitted ``build_train_step`` of each
    path (the per-step ``unpack``/``pack`` the bus pays for loss/grad is
    inside the timed region; the grad computation is identical in both, so
    the delta is the update+gossip machinery).  The unfused update chains
    are timed — interpret-mode Pallas is not representative on CPU — while
    the modeled columns carry what matters on TPU: permutes/step, kernel
    launches/step, and fused-path HBM bytes **padded** (what the kernels
    actually stream after ``_pack`` pad-to-grid) vs **logical** (data
    bytes).  The bus pays one tail pad for the whole tree; the leaf-wise
    path pads every leaf to a whole (BLOCK_ROWS, 128) tile.

    Also runs the numerical equivalence gates (bus == leaf-wise losses on
    every size, fused == unfused on the bus) — any divergence raises,
    which is the CI contract.

    Needs 8 host devices (use the ``--e2e-step`` outer flag for the
    subprocess wrapper).
    """
    import time

    import numpy as np

    from repro.configs.base import ModelConfig, RunConfig
    from repro.core import (bus as parambus, make_edm_bus,
                            make_schedule_mixer, ring)
    from .common import timeit_us
    from repro.data import SyntheticLM
    from repro.kernels.edm_update import BLOCK_ROWS
    from repro.kernels.ops import padded_size
    from repro.launch.mesh import gossip_agent_axes, make_gossip_mesh
    from repro.models import build_model
    from repro.train import (build_train_step, bus_layout_for, init_state,
                             make_gossip_schedule)

    A = 8
    topo = ring(A)
    mesh = make_gossip_mesh(A)
    axes = gossip_agent_axes(mesh)
    n_terms = len(topo.terms)
    n_perm = sum(1 for t in topo.terms if t.shift != 0)

    results = []
    overlap_rows = []
    for size, dims in E2E_SIZES.items():
        cfg = ModelConfig(name=f"bus-e2e-{size}", family="dense",
                          n_heads=2, n_kv_heads=2, vocab_size=256,
                          dtype="float32", **dims)
        model = build_model(cfg)
        batch = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16,
                            n_agents=A).sample(jax.random.PRNGKey(1), 1)
        layout = bus_layout_for(model, A)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        leaf_elems = [int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)]
        L = len(leaf_elems)
        n_logical = sum(leaf_elems)

        us = {}
        losses = {}
        for mode in ("leafwise", "bus", "bus_delayed"):
            packed = mode != "leafwise"
            run = RunConfig(global_batch=A, seq_len=16, algorithm="edm",
                            alpha=0.2, gossip_engine="ppermute",
                            packed_bus=packed,
                            overlap="delayed" if mode == "bus_delayed"
                            else "off", remat=False)
            sched = make_gossip_schedule(run, A)
            state = init_state(model, run, A, jax.random.PRNGKey(0))
            step = jax.jit(build_train_step(model, run, sched, mesh=mesh,
                                            agent_axes=axes),
                           donate_argnums=(0,) if packed else ())
            state, m = step(state, batch)  # compile
            traj = [float(m["loss"])]
            jax.block_until_ready(m["loss"])
            t0 = time.perf_counter()
            for _ in range(iters):
                state, m = step(state, batch)
            jax.block_until_ready(m["loss"])
            us[mode] = (time.perf_counter() - t0) / iters * 1e6
            traj.append(float(m["loss"]))
            losses[mode] = traj
        # equivalence gate: identical data + init ⇒ identical losses up to
        # f32 reassociation drift over the iters-step trajectory (the two
        # paths reduce in different orders; tests/test_bus.py pins 3 steps
        # at 1e-5 — a real divergence, e.g. the naive-bf16 bias, is ~1e-2+)
        np.testing.assert_allclose(
            losses["bus"], losses["leafwise"], rtol=1e-4, atol=1e-5,
            err_msg=f"bus vs leaf-wise losses diverged at size={size}")

        # overlap divergence gate (DESIGN §6): the delayed pipeline's loss
        # at step t is evaluated at the pre-mix iterate φ(t) — between the
        # synchronous x(t) and x(t+1) — so gate the 8-step trajectory
        # against the synchronous envelope [loss(t+1), loss(t)] ± 5%.
        # Gate runs at a stable α=0.05: one-step staleness at an
        # aggressive LR degrades per-step progress by design (the §E.1/E.2
        # floor gates below cover the convergence claim); the envelope
        # checks the *semantics* — φ(t) must sit between x(t) and x(t+1).
        traj_sync = _e2e_loss_traj(model, batch, mesh, axes, A, "off",
                                   steps=9)
        traj_del = _e2e_loss_traj(model, batch, mesh, axes, A, "delayed")
        assert abs(traj_del[0] - traj_sync[0]) < 1e-5, \
            (size, "overlap step 0 must match the synchronous step exactly")
        for t in range(len(traj_sync) - 1):
            lo = min(traj_sync[t], traj_sync[t + 1])
            hi = max(traj_sync[t], traj_sync[t + 1])
            tol = 0.05 * abs(traj_sync[t])
            assert lo - tol <= traj_del[t] <= hi + tol, (
                f"overlap divergence gate failed at size={size} step={t}: "
                f"delayed={traj_del[t]:.5f} outside sync envelope "
                f"[{lo:.5f}, {hi:.5f}] ± {tol:.5f}")

        # gossip-only wall time of the synchronous path on this size's bus
        # (the wire+combine the delayed pipeline moves off the critical
        # path); pct_gossip_hidden = how much of it the overlap recovered.
        run_g = RunConfig(global_batch=A, seq_len=16, algorithm="edm",
                          alpha=0.2, gossip_engine="ppermute",
                          packed_bus=True, remat=False)
        sched_g = make_gossip_schedule(run_g, A)
        mix_g = make_schedule_mixer(sched_g, "ppermute", mesh=mesh,
                                    agent_axes=axes)
        bus0 = init_state(model, run_g, A, jax.random.PRNGKey(0))["params"]
        gossip_us = timeit_us(jax.jit(lambda b: mix_g(b, step=0)), bus0,
                              iters=max(iters * 3, 10))
        hidden = (us["bus"] - us["bus_delayed"]) / max(gossip_us, 1e-9)
        overlap_rows.append({
            "size": size, "agents": A, "elems_per_agent": n_logical,
            "block_rows": layout.block_rows,
            "us_per_step_off": round(us["bus"], 1),
            "us_per_step_delayed": round(us["bus_delayed"], 1),
            "speedup_off_to_delayed":
                round(us["bus"] / us["bus_delayed"], 3),
            "gossip_us_per_step": round(gossip_us, 1),
            # share of the synchronous step the wire occupies on THIS
            # backend — the ceiling of what overlap can recover here; on
            # the CPU host mesh it is single-digit %, so pct_gossip_hidden
            # is dominated by step-time variance (the TPU ICI share is the
            # number that matters, see DESIGN §6).
            "gossip_pct_of_step": round(100.0 * gossip_us / us["bus"], 1),
            "pct_gossip_hidden": round(100.0 * hidden, 1),
            "divergence_gate": "pass",
        })

        # fused-path HBM model (f32): the EDM update streams 7 buffers of
        # the full per-agent set, the n-ary combine n_terms + 1 — padded to
        # _pack's grid tiles per launch (per leaf, or once for the bus).
        streams = 7 + n_terms + 1
        hbm_logical = streams * A * n_logical * 4
        leaf_padded = (7 * sum(padded_size(A * n, BLOCK_ROWS)
                               for n in leaf_elems)
                       + (n_terms + 1) * A * sum(padded_size(n, BLOCK_ROWS)
                                                 for n in leaf_elems)) * 4
        bus_padded = streams * A * layout.padded_elems * 4
        # wire bytes derive from the run's wire codec (DESIGN §9) — this
        # sweep ships the f32 bus (identity codec), so payload_bytes is
        # 4 B/elem here; the quantized formats are swept by wire_sweep
        from repro.core.wire import make_codec
        wire_pb = make_codec("f32", layout.block_rows).payload_bytes
        common = {"size": size, "n_leaves": L, "agents": A,
                  "elems_per_agent": n_logical,
                  "block_rows": layout.block_rows,
                  "wire_format": "f32",
                  "wire_bytes_logical": n_perm * A * wire_pb(n_logical)}
        results.append({**common, "path": "leafwise",
                        "us_per_step": round(us["leafwise"], 1),
                        "permutes_per_step": L * n_perm,
                        "kernel_launches_per_step": 2 * L,
                        "hbm_bytes_logical": hbm_logical,
                        "hbm_bytes_padded": leaf_padded,
                        "wire_bytes_padded": n_perm * A * wire_pb(n_logical)})
        results.append({**common, "path": "bus",
                        "us_per_step": round(us["bus"], 1),
                        "permutes_per_step": n_perm,
                        "kernel_launches_per_step": 2,
                        "hbm_bytes_logical": hbm_logical,
                        "hbm_bytes_padded": bus_padded,
                        "wire_bytes_padded":
                            n_perm * A * wire_pb(layout.padded_elems),
                        "speedup_vs_leafwise":
                            round(us["leafwise"] / us["bus"], 2)})

        # gate 2 (smallest size only): fused bus kernel == unfused bus at
        # the optimizer level.
        if size == "small":
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P

            from repro.core import make_mixer
            mix = make_mixer(topo, "ppermute", mesh=mesh, agent_axes=axes)
            params1 = model.init(jax.random.PRNGKey(0))
            params = jax.device_put(
                jax.tree.map(
                    lambda l: jnp.broadcast_to(l[None], (A,) + l.shape),
                    params1),
                NamedSharding(mesh, P("data")))
            xb = parambus.pack_tree(layout, params)
            gb = parambus.pack_tree(
                layout, jax.tree.map(lambda x: 0.1 * x, params))
            o_un = make_edm_bus(0.05, 0.9, mix,
                                block_rows=layout.block_rows)
            o_fu = make_edm_bus(0.05, 0.9, mix,
                                block_rows=layout.block_rows,
                                use_fused_kernel=True)
            stb = o_un.init(xb)
            x_un, _ = o_un.step(xb, gb, stb)
            x_fu, _ = o_fu.step(xb, gb, stb)
            np.testing.assert_allclose(
                np.asarray(x_fu), np.asarray(x_un), rtol=1e-5, atol=1e-5,
                err_msg="fused bus kernel vs unfused bus diverged")
    return {"rows": results, "overlap": overlap_rows}


def _e2e_loss_traj(model, batch, mesh, axes, A, overlap, steps: int = 8):
    """Fresh-state loss trajectory of the packed-bus train step with the
    given overlap mode at a stable α — the divergence-gate input."""
    from repro.configs.base import RunConfig
    from repro.train import build_train_step, init_state, make_gossip_schedule

    run = RunConfig(global_batch=A, seq_len=16, algorithm="edm", alpha=0.05,
                    gossip_engine="ppermute", packed_bus=True,
                    overlap=overlap, remat=False)
    sched = make_gossip_schedule(run, A)
    state = init_state(model, run, A, jax.random.PRNGKey(0))
    step = jax.jit(build_train_step(model, run, sched, mesh=mesh,
                                    agent_axes=axes))
    traj = []
    for _ in range(steps):
        state, m = step(state, batch)
        traj.append(float(m["loss"]))
    return traj


def _bench_subprocess(argv: List[str], marker: str, devices: int,
                      label: str, extra_env: Dict | None = None):
    """Re-exec this module on the CPU platform with a forced host device
    count and parse the marker-prefixed JSON line — the one subprocess
    wrapper behind every multi-device sweep (XLA_FLAGS must be set before
    jax initializes, so the sweeps cannot run in-process)."""
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PYTHONPATH": os.path.join(REPO, "src")
           + (os.pathsep + os.environ["PYTHONPATH"]
              if os.environ.get("PYTHONPATH") else ""),
           **(extra_env or {})}
    r = subprocess.run([sys.executable, "-m", "benchmarks.gossip_micro"]
                       + argv, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=900)
    for line in r.stdout.splitlines():
        if line.startswith(marker):
            return json.loads(line[len(marker):])
    raise RuntimeError(f"{label} failed:\n{r.stdout[-2000:]}"
                       f"\n{r.stderr[-2000:]}")


def _e2e_subprocess(iters: int = 6) -> dict:
    """Run :func:`e2e_step_sweep` under an 8-device host platform."""
    return _bench_subprocess(["--e2e-inner", "--iters", str(iters)],
                             _E2E_MARKER, 8, "e2e step sweep")


# ---------------------------------------------------------------------------
# shard-resident gossip: sharded vs gathered (DESIGN §7)
# ---------------------------------------------------------------------------

SHARD_ROWS_SIZES = (2048, 8192, 16384)


def sharded_sweep(iters: int = 20) -> List[dict]:
    """Sharded vs gathered gossip on a 2-pod × 4-shard host mesh
    (DESIGN §7): per bus size, us/step and wire bytes/step for

    * ``sharded``  — the bus row-sharded ``P('pod', 'data')``; every
      permute ships each shard's own ``rows/S`` block (shard-local);
    * ``gathered`` — the pre-§7 composition: rows replicated over the
      shard axis (``P('pod', None)``), so every shard ships the FULL
      per-agent payload and the wire carries S× the bytes.

    Includes the equivalence gate (sharded ppermute == dense oracle ==
    shard-resident all-gather oracle — any divergence raises, the CI
    contract for the pod-fsdp path).  Needs 8 host devices (use the
    ``--sharded`` outer flag for the subprocess wrapper).
    """
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import (make_mixer, mix_dense, mix_dense_sharded, ring)
    from repro.launch.mesh import make_gossip_mesh
    from .common import timeit_us

    A, S = 2, 4
    topo = ring(A)
    n_perm = sum(1 for t in topo.terms if t.shift != 0)
    mesh = make_gossip_mesh(A, pods=A, shards=S)
    results = []
    for rows in SHARD_ROWS_SIZES:
        x = jax.random.normal(jax.random.PRNGKey(rows), (A, rows, 128))
        want = np.asarray(mix_dense(topo, x))
        for mode in ("sharded", "gathered"):
            spec = P("pod", "data") if mode == "sharded" else P("pod")
            xs = jax.device_put(x, NamedSharding(mesh, spec))
            for fused in (False, True):
                kw = dict(mesh=mesh, agent_axes="pod",
                          use_fused_kernel=fused)
                if mode == "sharded":
                    kw["shard_axes"] = "data"
                mix = jax.jit(make_mixer(topo, "ppermute", **kw))
                # equivalence gate: both layouts must match the oracle
                np.testing.assert_allclose(
                    np.asarray(mix(xs)), want, rtol=1e-5, atol=1e-6,
                    err_msg=f"sharded-gossip gate: {mode} fused={fused} "
                            f"rows={rows} diverged from the dense oracle")
                if mode == "sharded" and not fused:
                    np.testing.assert_allclose(
                        np.asarray(mix_dense_sharded(topo, mesh, "pod",
                                                     "data", xs)),
                        want, rtol=1e-5, atol=1e-6,
                        err_msg=f"shard-resident oracle gate rows={rows}")
                us = timeit_us(mix, xs, iters=iters)
                rows_wire = rows // S if mode == "sharded" else rows
                # bytes derive from the wire codec (DESIGN §9; f32 here —
                # the quantized × sharded composition is gated by
                # wire_sweep's pod gate)
                from repro.core.wire import make_codec
                wire_pb = make_codec("f32", 8).payload_bytes
                results.append({
                    "mode": mode, "fused": fused, "agents": A, "shards": S,
                    "rows": rows, "elems_per_agent": rows * 128,
                    "us_per_step": round(us, 1),
                    "permutes_per_step": n_perm,
                    "wire_format": "f32",
                    # per-device payload of ONE gossip permute — the number
                    # that drops by the shard factor S (sharded mode keeps
                    # each FSDP shard's own row block on the wire)
                    "wire_bytes_per_device_per_term":
                        wire_pb(rows_wire * 128),
                    # summed over the S shards of every agent
                    "wire_bytes_per_step":
                        n_perm * A * S * wire_pb(rows_wire * 128),
                    "divergence_gate": "pass",
                })
    return results


def write_shard_bench_json(results: List[dict]) -> str:
    """Persist the sharded-vs-gathered sweep to BENCH_shard.json."""
    payload = {
        "bench": "gossip_sharded_vs_gathered",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "note": (
            "Shard-resident gossip (DESIGN §7) on a 2-pod x 4-shard host "
            "mesh: 'sharded' permutes each FSDP shard's own rows/S block "
            "(P('pod','data')); 'gathered' is the pre-composition layout "
            "with rows replicated over the shard axis, so every shard "
            "ships the full per-agent payload — S x the wire bytes and, "
            "with real FSDP state, an all-gather before every permute.  "
            "CPU wall-clock bounds structure only; the "
            "wire_bytes_per_device_per_term column is the modeled TPU "
            "claim, and the divergence gate (sharded == dense oracle) is "
            "the backend-independent contract."),
        "results": results,
    }
    with open(BENCH_SHARD_JSON, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return BENCH_SHARD_JSON


def _shard_csv_rows(rows: List[dict]) -> List[str]:
    from .common import csv_row
    return [csv_row(
        f"gossip_shard/rows={row['rows']}/{row['mode']}"
        f"{'_fused' if row['fused'] else ''}",
        row["us_per_step"],
        f"A={row['agents']};S={row['shards']};"
        f"wire_dev_term={row['wire_bytes_per_device_per_term']};"
        f"wire_step={row['wire_bytes_per_step']}") for row in rows]


def _shard_subprocess(iters: int = 20) -> List[dict]:
    """Run :func:`sharded_sweep` under an 8-device host platform."""
    return _bench_subprocess(["--sharded-inner", "--iters", str(iters)],
                             _SHARD_MARKER, 8, "sharded sweep")


# ---------------------------------------------------------------------------
# overlap divergence gates (DESIGN §6) — dense-oracle W, single device
# ---------------------------------------------------------------------------

def _edm_sync_vs_delayed(grad_fn, x0, W, *, alpha: float, beta: float,
                         steps: int, seed: int, eval_fn):
    """Eval trajectories of synchronous EDM vs the delayed (one-step-stale
    mixing) pipeline variant under a dense W, driven by the SAME noise keys
    — the only difference is where the gradient is evaluated: at the mixed
    iterate x(t) = W φ(t) (sync) vs the pre-mix φ(t) (delayed)."""
    import jax.numpy as jnp

    Wj = jnp.asarray(W, jnp.float32)

    def sync_body(carry, key):
        x, m, psi = carry
        g = grad_fn(x, key)
        m2 = beta * m + (1.0 - beta) * g
        psi2 = x - alpha * m2
        phi = psi2 + x - psi
        x2 = Wj @ phi
        return (x2, m2, psi2), eval_fn(x2)

    def delayed_body(carry, key):
        phi, m, psi = carry
        x = Wj @ phi               # complete: the in-flight payload's mix
        g = grad_fn(phi, key)      # compute: grads at the pre-mix iterate
        m2 = beta * m + (1.0 - beta) * g
        psi2 = x - alpha * m2
        phi2 = psi2 + x - psi
        return (phi2, m2, psi2), eval_fn(x)

    keys = jax.random.split(jax.random.PRNGKey(seed), steps)
    z = jnp.zeros_like(x0)
    _, e_sync = jax.lax.scan(sync_body, (x0, z, x0), keys)
    _, e_del = jax.lax.scan(delayed_body, (x0, z, x0), keys)
    import numpy as np
    return np.asarray(e_sync), np.asarray(e_del)


def overlap_divergence_gates(verbose: bool = True) -> dict:
    """The §E.1 quadratic and §E.2 logistic gates for ``overlap="delayed"``:
    the stale-mixing variant must converge to (near) the synchronous floor.
    Raises on failure — the CI contract for the overlap pipeline."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ring
    from repro.data import logistic_problem, quadratic_problem

    gates = {}
    n = 32
    W = ring(n).dense_matrix()

    stoch, _, x_opt, zeta2 = quadratic_problem(n, d=10, p=20, c=1.0,
                                               sigma=0.05, seed=0)
    x0 = jnp.zeros((n, 10))
    err = lambda x: jnp.mean(jnp.sum((x - x_opt[None]) ** 2, -1))
    e_sync, e_del = _edm_sync_vs_delayed(stoch, x0, W, alpha=0.05, beta=0.9,
                                         steps=1500, seed=0, eval_fn=err)
    floor_s = float(np.mean(e_sync[-150:]))
    floor_d = float(np.mean(e_del[-150:]))
    assert floor_d <= 2.0 * floor_s + 1e-8, \
        f"quadratic overlap gate: delayed floor {floor_d:.3e} vs " \
        f"sync {floor_s:.3e}"
    assert floor_d < float(e_del[0]), "quadratic overlap gate: no progress"
    gates["quadratic"] = {"steps": 1500, "zeta2": zeta2,
                          "floor_sync": floor_s, "floor_delayed": floor_d,
                          "ratio": round(floor_d / max(floor_s, 1e-12), 3)}
    if verbose:
        print(f"  overlap gate quadratic: sync={floor_s:.3e} "
              f"delayed={floor_d:.3e} ratio={gates['quadratic']['ratio']}")

    stoch, _, mean_loss = logistic_problem(n, d=20, m=500, seed=0)
    x0 = jnp.zeros((n, 20))
    lloss = lambda x: mean_loss(jnp.mean(x, axis=0))
    l_sync, l_del = _edm_sync_vs_delayed(stoch, x0, W, alpha=0.1, beta=0.9,
                                         steps=800, seed=1, eval_fn=lloss)
    fin_s = float(np.mean(l_sync[-80:]))
    fin_d = float(np.mean(l_del[-80:]))
    assert fin_d <= 1.05 * fin_s + 1e-8, \
        f"logistic overlap gate: delayed {fin_d:.4f} vs sync {fin_s:.4f}"
    gates["logistic"] = {"steps": 800, "loss_sync": fin_s,
                         "loss_delayed": fin_d,
                         "ratio": round(fin_d / max(fin_s, 1e-12), 4)}
    if verbose:
        print(f"  overlap gate logistic: sync={fin_s:.4f} "
              f"delayed={fin_d:.4f} ratio={gates['logistic']['ratio']}")
    return gates


def write_overlap_bench_json(overlap_rows: List[dict], gates: dict) -> str:
    """Persist the overlap pipeline sweep + divergence gates to
    BENCH_overlap.json at the repo root."""
    payload = {
        "bench": "gossip_overlap_pipeline",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "note": (
            "CPU host-mesh wall clock validates structure and parity only: "
            "XLA CPU executes collectives inline, so the wire cannot hide "
            "behind compute here and gossip is a single-digit % of the "
            "step (gossip_pct_of_step).  The overlap claim is the TPU "
            "half: the delayed step's permute-starts precede the backward "
            "pass and the payload stack is complete()'s only wire "
            "dependency (DESIGN §6); divergence_gates carry the "
            "backend-independent correctness contract."),
        "results": overlap_rows,
        "divergence_gates": gates,
    }
    with open(BENCH_OVERLAP_JSON, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return BENCH_OVERLAP_JSON


def _overlap_csv_rows(rows: List[dict]) -> List[str]:
    from .common import csv_row
    return [csv_row(
        f"edm_step/{row['size']}/bus_delayed", row["us_per_step_delayed"],
        f"off={row['us_per_step_off']};"
        f"speedup={row['speedup_off_to_delayed']}x;"
        f"gossip_us={row['gossip_us_per_step']};"
        f"hidden={row['pct_gossip_hidden']}%") for row in rows]


# ---------------------------------------------------------------------------
# elastic fault-tolerant gossip: churn sweep + divergence gates (DESIGN §8)
# ---------------------------------------------------------------------------

ELASTIC_DROP_RATES = (0.0, 0.1, 0.25)


def elastic_sweep(iters: int = 20, d: int = 1 << 16,
                  drops=ELASTIC_DROP_RATES) -> List[dict]:
    """Churn fault-injection sweep (DESIGN §8): us/step and wire bytes/step
    vs. drop rate for the liveness-masked schedules, {static ring,
    round_robin} × {plain, fused} ppermute on 8 agents / 8 devices.

    Per drop rate a deterministic :class:`DropPlan` (epoch length = the
    base period, so masks are period-aligned) wraps the base schedule in an
    :class:`ElasticSchedule`; every schedule built here re-checks
    Assumption 1 per degraded epoch, and every distinct degraded round is
    gated masked-ppermute == dense-oracle before it is timed — any
    divergence raises (the CI contract for the elastic path).  Timing
    follows :func:`schedule_sweep`: one jitted application per distinct
    round, weighted over one full plan cycle.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import (DropPlan, ElasticSchedule, RoundRobinExp,
                            StaticSchedule, make_schedule_mixer, ring,
                            wire_bytes_per_step)
    from repro.launch.mesh import gossip_agent_axes, make_gossip_mesh
    from .common import timeit_us

    A = 8
    n_epochs = 3
    mesh = make_gossip_mesh(A)
    axes = gossip_agent_axes(mesh)
    results = []
    for sname, make_base in (("static_ring", lambda: StaticSchedule(ring(A))),
                             ("round_robin", lambda: RoundRobinExp(A))):
        for drop in drops:
            base = make_base()
            plan = DropPlan.random(A, drop, seed=7, n_epochs=n_epochs,
                                   epoch_len=base.period)
            sched = ElasticSchedule(base, plan)
            sched.check_assumption1()
            stats = sched.product_spectral_stats()
            window = n_epochs * base.period   # one full plan cycle
            mix_oracle = make_schedule_mixer(sched, "dense")
            for cname, fused in (("ppermute", False),
                                 ("ppermute_fused", True)):
                mix = make_schedule_mixer(sched, "ppermute", mesh=mesh,
                                          agent_axes=axes,
                                          use_fused_kernel=fused)
                x = jax.random.normal(jax.random.PRNGKey(0), (A, d))
                xs = jax.device_put(x, NamedSharding(mesh, P(axes)))
                us_round = {}
                for r in range(sched.period):
                    got = jax.jit(lambda t, r=r: mix(t, step=r))(xs)
                    import numpy as np
                    np.testing.assert_allclose(
                        np.asarray(got), np.asarray(mix_oracle(x, step=r)),
                        rtol=2e-5, atol=1e-5,
                        err_msg=f"elastic gate: {sname} drop={drop} "
                                f"{cname} round {r} != dense oracle")
                    us_round[r] = timeit_us(
                        jax.jit(lambda t, r=r: mix(t, step=r)), xs,
                        iters=max(iters // sched.period, 2))
                us = sum(us_round[int(sched.round_index(t))]
                         for t in range(window)) / window
                wire = sum(wire_bytes_per_step(sched, t, elems_per_agent=d,
                                               engine="ppermute")
                           for t in range(window)) / window
                results.append({
                    "schedule": sname, "config": cname,
                    "drop_rate": drop, "agents": A, "d": d,
                    "base_period": base.period, "epochs": n_epochs,
                    "us_per_step": round(us, 1),
                    "wire_bytes_per_step": int(wire),
                    "permutes_per_step": stats["permutes_per_step"],
                    "lambda_max": round(stats["lambda"], 4),
                    "gap_min": round(stats["gap"], 4),
                })
    return results


def _step_W_table(sched, steps: int):
    """(steps, n, n) float32 per-step dense mixing matrices — the oracle
    for schedules whose W varies with the step (ElasticSchedule)."""
    import numpy as np
    mats, idx = {}, []
    for t in range(steps):
        r = int(sched.round_index(t))
        if r not in mats:
            mats[r] = sched.round(t).dense_matrix()
        idx.append(r)
    return np.stack([mats[r] for r in idx]).astype(np.float32)


def _edm_churn_trajectory(grad_fn, x0, W_steps, *, alpha: float, beta: float,
                          seed: int, eval_fn):
    """Synchronous EDM under a per-step W table (all agents keep computing
    local updates — churn only degrades the mixing, which is exactly what
    the liveness-masked trainer does)."""
    import jax.numpy as jnp
    import numpy as np

    Wj = jnp.asarray(W_steps, jnp.float32)

    def body(carry, inp):
        key, W = inp
        x, m, psi = carry
        g = grad_fn(x, key)
        m2 = beta * m + (1.0 - beta) * g
        psi2 = x - alpha * m2
        phi = psi2 + x - psi
        x2 = W @ phi
        return (x2, m2, psi2), eval_fn(x2)

    keys = jax.random.split(jax.random.PRNGKey(seed), Wj.shape[0])
    z = jnp.zeros_like(x0)
    _, e = jax.lax.scan(body, (x0, z, x0), (keys, Wj))
    return np.asarray(e)


def churn_divergence_gates(verbose: bool = True) -> dict:
    """The §E.1 quadratic and §E.2 logistic gates under a 10 %-drop
    :class:`DropPlan`: the churned run (same noise keys, W degraded per
    epoch) must stay within the neighborhood envelope of the no-churn run,
    evaluated on the always-alive agents (dead agents freeze — correct, but
    not progress).  Raises on failure — the CI contract for ``--churn``."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import DropPlan, ElasticSchedule, StaticSchedule, ring
    from repro.data import logistic_problem, quadratic_problem

    gates = {}
    n = 32
    base = StaticSchedule(ring(n))

    # --- §E.1 quadratic: consensus floor within envelope -------------------
    steps = 1500
    plan = DropPlan.random(n, 0.10, seed=3, n_epochs=6, epoch_len=250)
    sched = ElasticSchedule(base, plan)
    sched.check_assumption1()
    alive = plan.always_alive()
    aj = jnp.asarray(alive)
    W_churn = _step_W_table(sched, steps)
    W_flat = np.broadcast_to(ring(n).dense_matrix().astype(np.float32),
                             (steps, n, n))
    stoch, _, x_opt, zeta2 = quadratic_problem(n, d=10, p=20, c=1.0,
                                               sigma=0.05, seed=0)
    x0 = jnp.zeros((n, 10))
    err = lambda x: jnp.mean(jnp.sum((x[aj] - x_opt[None]) ** 2, -1))
    e_flat = _edm_churn_trajectory(stoch, x0, W_flat, alpha=0.05, beta=0.9,
                                   seed=0, eval_fn=err)
    e_churn = _edm_churn_trajectory(stoch, x0, W_churn, alpha=0.05, beta=0.9,
                                    seed=0, eval_fn=err)
    floor_f = float(np.mean(e_flat[-150:]))
    floor_c = float(np.mean(e_churn[-150:]))
    assert floor_c <= 3.0 * floor_f + 1e-8, \
        f"quadratic churn gate: churned floor {floor_c:.3e} vs " \
        f"no-churn {floor_f:.3e}"
    assert floor_c < float(e_churn[0]), "quadratic churn gate: no progress"
    gates["quadratic"] = {
        "steps": steps, "zeta2": zeta2, "drop_rate": 0.10,
        "always_alive": int(len(alive)),
        "floor_nochurn": floor_f, "floor_churn": floor_c,
        "ratio": round(floor_c / max(floor_f, 1e-12), 3)}
    if verbose:
        print(f"  churn gate quadratic: nochurn={floor_f:.3e} "
              f"churn={floor_c:.3e} ratio={gates['quadratic']['ratio']}")

    # --- §E.2 logistic: mean-iterate loss within envelope -------------------
    steps = 800
    plan = DropPlan.random(n, 0.10, seed=5, n_epochs=5, epoch_len=160)
    sched = ElasticSchedule(base, plan)
    sched.check_assumption1()
    alive = plan.always_alive()
    aj = jnp.asarray(alive)
    W_churn = _step_W_table(sched, steps)
    W_flat = np.broadcast_to(ring(n).dense_matrix().astype(np.float32),
                             (steps, n, n))
    stoch, _, mean_loss = logistic_problem(n, d=20, m=500, seed=0)
    x0 = jnp.zeros((n, 20))
    lloss = lambda x: mean_loss(jnp.mean(x[aj], axis=0))
    l_flat = _edm_churn_trajectory(stoch, x0, W_flat, alpha=0.1, beta=0.9,
                                   seed=1, eval_fn=lloss)
    l_churn = _edm_churn_trajectory(stoch, x0, W_churn, alpha=0.1, beta=0.9,
                                    seed=1, eval_fn=lloss)
    fin_f = float(np.mean(l_flat[-80:]))
    fin_c = float(np.mean(l_churn[-80:]))
    assert fin_c <= 1.10 * fin_f + 1e-8, \
        f"logistic churn gate: churned {fin_c:.4f} vs no-churn {fin_f:.4f}"
    gates["logistic"] = {
        "steps": steps, "drop_rate": 0.10, "always_alive": int(len(alive)),
        "loss_nochurn": fin_f, "loss_churn": fin_c,
        "ratio": round(fin_c / max(fin_f, 1e-12), 4)}
    if verbose:
        print(f"  churn gate logistic: nochurn={fin_f:.4f} "
              f"churn={fin_c:.4f} ratio={gates['logistic']['ratio']}")
    return gates


def write_elastic_bench_json(rows: List[dict], gates: dict) -> str:
    """Persist the churn sweep + divergence gates to BENCH_elastic.json at
    the repo root."""
    payload = {
        "bench": "gossip_elastic_churn",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "note": (
            "Liveness-masked gossip under deterministic churn (DESIGN §8). "
            "Every row's schedule passed the per-epoch Assumption-1 "
            "transfer check (degraded rounds doubly stochastic, positive "
            "diagonal, dead rows/cols identity, survivor period product "
            "contracting) and the masked-ppermute == dense-oracle "
            "equivalence gate before timing.  wire_bytes_per_step drops "
            "with the drop rate because dead agents' rows leave the wire "
            "(one permute per nonzero survivor shift); divergence_gates "
            "carry the backend-independent convergence contract under a "
            "10% drop plan, evaluated on the always-alive agents."),
        "results": rows,
        "divergence_gates": gates,
    }
    with open(BENCH_ELASTIC_JSON, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return BENCH_ELASTIC_JSON


def _elastic_csv_rows(rows: List[dict]) -> List[str]:
    from .common import csv_row
    return [csv_row(
        f"gossip_elastic/{row['schedule']}/{row['config']}"
        f"/drop={row['drop_rate']}",
        row["us_per_step"],
        f"A={row['agents']};wire_step={row['wire_bytes_per_step']};"
        f"permutes={row['permutes_per_step']};gap={row['gap_min']}")
        for row in rows]


def _elastic_subprocess(iters: int = 20) -> List[dict]:
    """Run :func:`elastic_sweep` under an 8-device host platform."""
    return _bench_subprocess(["--churn-inner", "--iters", str(iters)],
                             _ELASTIC_MARKER, 8, "elastic churn sweep")


# ---------------------------------------------------------------------------
# quantized gossip wire: codec sweep + EF divergence gates (DESIGN §9)
# ---------------------------------------------------------------------------

WIRE_SWEEP_ROWS = 512   # bus rows/agent in the measured wire sweep


def wire_sweep(iters: int = 6) -> List[dict]:
    """Wire-format × fused sweep on an 8-agent ring (8 host devices):
    us/step, codec-derived wire bytes/step and compression ratio for the
    f32 / bf16 / int8 gossip wire (DESIGN §9), each behind three built-in
    equivalence gates (the CI contract for the wire path):

    * **oracle** — the wire-coded ppermute engine (fused and unfused)
      must equal the dense oracle applied to the quantized payload,
      ``mix_dense(topo, Q(x))`` — permutes commute with decode, so the
      match is exact, not approximate;
    * **masked** — same oracle identity on a liveness-degraded round
      (one dead agent), so quantized payloads compose with the elastic
      masks of DESIGN §8;
    * **sharded** — same identity on a 2-pod × 4-shard ``P('pod','data')``
      bus, so the int8 scale blocks stay shard-local (DESIGN §7 × §9).

    Any divergence raises.  Timing is CPU wall-clock (the int8 fused
    combine runs interpret-mode off-TPU — structure only); the byte
    columns are the modeled TPU wire claim.
    """
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import (StaticSchedule, make_mixer, mix_dense, ring,
                            wire_bytes_per_step)
    from repro.core.elastic import degrade_round
    from repro.core.wire import WIRE_FORMATS, make_codec
    from repro.launch.mesh import gossip_agent_axes, make_gossip_mesh
    from .common import timeit_us

    A, rows, br = 8, WIRE_SWEEP_ROWS, 8
    elems = rows * 128
    topo = ring(A)
    n_perm = sum(1 for t in topo.terms if t.shift != 0)
    mesh = make_gossip_mesh(A)
    axes = gossip_agent_axes(mesh)
    x = jax.random.normal(jax.random.PRNGKey(0), (A, rows, 128))
    xs = jax.device_put(x, NamedSharding(mesh, P(axes)))
    results = []
    for fmt in WIRE_FORMATS:
        codec = make_codec(fmt, br)
        want = np.asarray(mix_dense(topo, codec.quantize(x)))
        enc = jax.jit(codec.encode)(xs)
        for fused in (False, True):
            mix = jax.jit(make_mixer(topo, "ppermute", mesh=mesh,
                                     agent_axes=axes,
                                     use_fused_kernel=fused, wire=codec))
            np.testing.assert_allclose(
                np.asarray(mix(enc)), want, rtol=1e-5, atol=1e-5,
                err_msg=f"wire gate: {fmt} fused={fused} ppermute "
                        f"!= dense oracle on Q(x)")
            us = timeit_us(mix, enc, iters=iters)
            results.append({
                "wire_format": fmt, "fused": fused, "agents": A,
                "rows": rows, "elems_per_agent": elems, "block_rows": br,
                "us_per_step": round(us, 1),
                "wire_bytes_per_step": int(wire_bytes_per_step(
                    StaticSchedule(topo), 0, elems_per_agent=elems,
                    engine="ppermute", codec=codec)),
                "compression_ratio":
                    round(codec.compression_ratio(elems), 3),
                "permutes_per_step": n_perm,
                "divergence_gate": "pass",
            })

    # masked gate: one dead agent's degraded round, int8 wire, both engines
    alive = [a != 3 for a in range(A)]
    mt = degrade_round(topo, alive)
    codec = make_codec("int8", br)
    want = np.asarray(mix_dense(mt, codec.quantize(x)))
    enc = jax.jit(codec.encode)(xs)
    for fused in (False, True):
        mix = jax.jit(make_mixer(mt, "ppermute", mesh=mesh, agent_axes=axes,
                                 use_fused_kernel=fused, wire=codec))
        np.testing.assert_allclose(
            np.asarray(mix(enc)), want, rtol=1e-5, atol=1e-5,
            err_msg=f"wire masked gate: int8 fused={fused} degraded round "
                    f"!= dense oracle on Q(x)")

    # sharded gate: 2-pod × 4-shard P('pod','data') bus, int8 wire — the
    # scale blocks must stay shard-local (DESIGN §7 × §9)
    Ap, S = 2, 4
    pmesh = make_gossip_mesh(Ap, pods=Ap, shards=S)
    ptopo = ring(Ap)
    xp = jax.random.normal(jax.random.PRNGKey(1), (Ap, rows, 128))
    want = np.asarray(mix_dense(ptopo, codec.quantize(xp)))
    xps = jax.device_put(xp, NamedSharding(pmesh, P("pod", "data")))
    enc = jax.jit(codec.encode)(xps)
    mix = jax.jit(make_mixer(ptopo, "ppermute", mesh=pmesh,
                             agent_axes="pod", shard_axes="data",
                             wire=codec))
    np.testing.assert_allclose(
        np.asarray(mix(enc)), want, rtol=1e-5, atol=1e-5,
        err_msg="wire sharded gate: int8 P('pod','data') != dense oracle")
    return results


def wire_modeled_rows(n: int = 32, rows: int = WIRE_SWEEP_ROWS,
                      block_rows: int = 8) -> List[dict]:
    """Modeled wire bytes/step on the paper's n=32 ring per wire format —
    the acceptance numbers of DESIGN §9 (no devices needed).  Asserts the
    byte-cut floors (bf16 ≥ 2×, int8 ≥ 3.5× vs f32) and that the permute
    count is format-independent (compression changes bytes, not topology).
    """
    from repro.core import StaticSchedule, ring, wire_bytes_per_step
    from repro.core.wire import WIRE_FORMATS, make_codec

    sched = StaticSchedule(ring(n))
    elems = rows * 128
    n_perm = sum(1 for t in sched.round(0).terms if t.shift != 0)
    base = wire_bytes_per_step(sched, 0, elems_per_agent=elems,
                               engine="ppermute")
    out = []
    for fmt in WIRE_FORMATS:
        codec = make_codec(fmt, block_rows)
        b = wire_bytes_per_step(sched, 0, elems_per_agent=elems,
                                engine="ppermute", codec=codec)
        out.append({
            "modeled": True, "agents": n, "rows": rows,
            "elems_per_agent": elems, "block_rows": block_rows,
            "wire_format": fmt, "wire_bytes_per_step": int(b),
            "reduction_vs_f32": round(base / b, 3),
            "compression_ratio":
                round(codec.compression_ratio(elems), 3),
            "permutes_per_step": n_perm,
        })
    by = {r["wire_format"]: r for r in out}
    assert by["bf16"]["reduction_vs_f32"] >= 2.0, by["bf16"]
    assert by["int8"]["reduction_vs_f32"] >= 3.5, by["int8"]
    assert len({r["permutes_per_step"] for r in out}) == 1, out
    return out


def _padded_quantizer(fmt: str):
    """Quantize an ``(n, d)`` iterate through the bus wire codec by padding
    each agent's d-vector into whole ``(8, 128)`` scale blocks — the
    reference wire for the low-dimensional §E problems (the pad tail
    encodes to exact zero, so it never pollutes the scale: the codec's
    absmax sees the real coordinates only when d fills the first rows,
    and zero blocks yield scale 0)."""
    import jax.numpy as jnp

    from repro.core.wire import make_codec

    codec = make_codec(fmt, 8)
    lane, blk = 128, 8 * 128

    def quant(x):
        n, d = x.shape
        rows = 8 * (-(-d // blk))      # whole scale blocks
        buf = jnp.zeros((n, rows * lane), x.dtype).at[:, :d].set(x)
        qd = codec.quantize(buf.reshape(n, rows, lane))
        return qd.reshape(n, rows * lane)[:, :d]
    return quant


def _edm_wire_trajectory(grad_fn, x0, W, *, alpha: float, beta: float,
                         steps: int, seed: int, eval_fn, quant=None,
                         error_feedback: bool = True):
    """Synchronous EDM under a dense W with the gossip payload φ pushed
    through a quantizer — either with the bus-resident error-feedback
    residual (send Q(φ+e), carry e := φ+e − Q(φ+e); DESIGN §9) or naively
    (send Q(φ), no residual — the negative control).  ``quant=None`` is
    the exact f32 wire."""
    import jax.numpy as jnp
    import numpy as np

    Wj = jnp.asarray(W, jnp.float32)

    def body(carry, key):
        x, m, psi, e = carry
        g = grad_fn(x, key)
        m2 = beta * m + (1.0 - beta) * g
        psi2 = x - alpha * m2
        phi = psi2 + x - psi
        if quant is None:
            pay, e2 = phi, e
        elif error_feedback:
            c = phi + e
            pay = quant(c)
            e2 = c - pay
        else:
            pay, e2 = quant(phi), e
        x2 = Wj @ pay
        return (x2, m2, psi2, e2), eval_fn(x2)

    keys = jax.random.split(jax.random.PRNGKey(seed), steps)
    z = jnp.zeros_like(x0)
    _, ev = jax.lax.scan(body, (x0, z, x0, z), keys)
    return np.asarray(ev)


def wire_divergence_gates(verbose: bool = True) -> dict:
    """The §E.1 quadratic and §E.2 logistic gates for the quantized wire:
    per format, the error-feedback run must land within 1.05× of the f32
    floor/loss, and the naive-quantization run (same codec, no residual)
    is recorded as the negative control — it must be strictly worse than
    EF on the quadratic floor, or compression would be free and EF dead
    weight.  Raises on failure — the CI contract for ``--wire``."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ring
    from repro.data import logistic_problem, quadratic_problem

    gates = {}
    n = 32
    W = ring(n).dense_matrix()

    # --- §E.1 quadratic: consensus floor within 1.05x of f32 ---------------
    # σ=0.2 (vs the overlap/churn gates' 0.05): EF removes the *bias*
    # amplification — the naive rows' (1−λ)⁻¹ floor blowup — but int8's
    # per-round quantization variance is α- and σ-independent (it scales
    # with absmax(φ) ≈ ‖x‖∞), so the floor-ratio claim is stated in the
    # noise-dominated regime the paper's floor analysis lives in; at
    # σ=0.05 the same EF run sits ≈1.14× of f32 (variance-, not
    # bias-limited) while naive int8 is ~800× — the contrast the
    # negative-control rows pin.
    stoch, _, x_opt, zeta2 = quadratic_problem(n, d=10, p=20, c=1.0,
                                               sigma=0.2, seed=0)
    x0 = jnp.zeros((n, 10))
    err = lambda x: jnp.mean(jnp.sum((x - x_opt[None]) ** 2, -1))
    kw = dict(alpha=0.05, beta=0.9, steps=1500, seed=0, eval_fn=err)
    floor = lambda e: float(np.mean(e[-150:]))
    f32_floor = floor(_edm_wire_trajectory(stoch, x0, W, **kw))
    fmts = {}
    for fmt in ("bf16", "int8"):
        q = _padded_quantizer(fmt)
        ef = floor(_edm_wire_trajectory(stoch, x0, W, quant=q, **kw))
        naive = floor(_edm_wire_trajectory(stoch, x0, W, quant=q,
                                           error_feedback=False, **kw))
        assert ef <= 1.05 * f32_floor + 1e-10, \
            f"quadratic wire gate: {fmt}+EF floor {ef:.3e} vs " \
            f"f32 {f32_floor:.3e}"
        assert naive > ef, \
            f"quadratic wire gate: naive {fmt} {naive:.3e} not worse " \
            f"than EF {ef:.3e} — negative control failed"
        fmts[fmt] = {"floor_ef": ef, "floor_naive": naive,
                     "ratio_ef": round(ef / max(f32_floor, 1e-12), 3),
                     "ratio_naive":
                         round(naive / max(f32_floor, 1e-12), 3)}
        if verbose:
            print(f"  wire gate quadratic {fmt}: f32={f32_floor:.3e} "
                  f"ef={ef:.3e} (x{fmts[fmt]['ratio_ef']}) "
                  f"naive={naive:.3e} (x{fmts[fmt]['ratio_naive']})")
    gates["quadratic"] = {"steps": 1500, "zeta2": zeta2,
                          "floor_f32": f32_floor, "formats": fmts}

    # --- §E.2 logistic: mean-iterate loss within 1.05x of f32 --------------
    stoch, _, mean_loss = logistic_problem(n, d=20, m=500, seed=0)
    x0 = jnp.zeros((n, 20))
    lloss = lambda x: mean_loss(jnp.mean(x, axis=0))
    kw = dict(alpha=0.1, beta=0.9, steps=800, seed=1, eval_fn=lloss)
    fin = lambda l: float(np.mean(l[-80:]))
    f32_loss = fin(_edm_wire_trajectory(stoch, x0, W, **kw))
    fmts = {}
    for fmt in ("bf16", "int8"):
        q = _padded_quantizer(fmt)
        ef = fin(_edm_wire_trajectory(stoch, x0, W, quant=q, **kw))
        naive = fin(_edm_wire_trajectory(stoch, x0, W, quant=q,
                                         error_feedback=False, **kw))
        assert ef <= 1.05 * f32_loss + 1e-10, \
            f"logistic wire gate: {fmt}+EF loss {ef:.4f} vs " \
            f"f32 {f32_loss:.4f}"
        fmts[fmt] = {"loss_ef": ef, "loss_naive": naive,
                     "ratio_ef": round(ef / max(f32_loss, 1e-12), 4),
                     "ratio_naive":
                         round(naive / max(f32_loss, 1e-12), 4)}
        if verbose:
            print(f"  wire gate logistic {fmt}: f32={f32_loss:.4f} "
                  f"ef={ef:.4f} (x{fmts[fmt]['ratio_ef']}) "
                  f"naive={naive:.4f} (x{fmts[fmt]['ratio_naive']})")
    gates["logistic"] = {"steps": 800, "loss_f32": f32_loss, "formats": fmts}
    return gates


def write_wire_bench_json(rows: List[dict], modeled: List[dict],
                          gates: dict) -> str:
    """Persist the wire sweep + modeled n=32 bytes + EF divergence gates
    to BENCH_wire.json at the repo root."""
    payload = {
        "bench": "gossip_wire_formats",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "note": (
            "Quantized gossip wire (DESIGN §9): bf16 / int8 per-block-"
            "scaled bus payloads with bus-resident EDM error feedback.  "
            "'results' are measured on an 8-agent host ring behind the "
            "oracle/masked/sharded equivalence gates (CPU wall-clock "
            "bounds structure only — the int8 fused combine runs "
            "interpret-mode off-TPU); 'modeled_n32' carries the paper-"
            "scale byte claim: same permute count per format, bytes cut "
            "2x (bf16) and ~4x (int8 + per-block scales) vs the f32 "
            "wire.  divergence_gates are the backend-independent "
            "convergence contract: EDM with the error-feedback wire "
            "lands within 1.05x of the f32 floor on the §E.1 quadratic "
            "and §E.2 logistic problems, while the naive-quantization "
            "negative-control rows show the persistent-bias floor "
            "inflation EF removes."),
        "results": rows,
        "modeled_n32": modeled,
        "divergence_gates": gates,
    }
    with open(BENCH_WIRE_JSON, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return BENCH_WIRE_JSON


def _wire_csv_rows(rows: List[dict]) -> List[str]:
    from .common import csv_row
    return [csv_row(
        f"gossip_wire/{row['wire_format']}"
        f"{'_fused' if row['fused'] else ''}",
        row["us_per_step"],
        f"A={row['agents']};wire_step={row['wire_bytes_per_step']};"
        f"ratio={row['compression_ratio']};"
        f"permutes={row['permutes_per_step']}") for row in rows]


def _wire_subprocess(iters: int = 6) -> List[dict]:
    """Run :func:`wire_sweep` under an 8-device host platform."""
    return _bench_subprocess(["--wire-inner", "--iters", str(iters)],
                             _WIRE_MARKER, 8, "wire sweep")


# ---------------------------------------------------------------------------
# gossip policy groups: per-group cadence / schedule / wire (DESIGN §12)
# ---------------------------------------------------------------------------

GROUPS_SWEEP_WINDOW = 8  # byte-model window; a multiple of every cadence


def groups_sweep(iters: int = 6) -> dict:
    """Policy-group sweep on the smoke MoE transformer (8 host devices,
    DESIGN §12): per ``--gossip-groups`` config, us/step of the group
    mixer on the real grouped bus layout plus the modeled per-group wire
    bytes over a :data:`GROUPS_SWEEP_WINDOW`-step window, behind two
    built-in gates (the CI contract of the ``moe-gossip-smoke`` job):

    * **segment composition** — on the 2-group all-gossip layout
      (``moe:1``) the group mixer must equal the whole-bus schedule mixer
      bit-exactly (ring mixing is row-independent, so slicing the bus into
      contiguous group segments and mixing each cannot change a bit), and
      on the opt-out layout (``moe``) the expert rows must come back
      untouched while the dense rows match the whole-bus mix of their
      slice;
    * **byte accounting** — the opt-out config ships strictly fewer wire
      bytes per window than the ungrouped all-gossip baseline, and on the
      shared 2-group layout the all-gossip − opt-out delta equals the
      experts group's modeled bytes EXACTLY (the group byte model of
      ``repro.core.schedule.group_wire_bytes_per_step``); the slow-cycle
      config (``moe:4``) lands in between, shipping expert bytes on 1-in-4
      steps only.

    Timing is CPU wall-clock (structure only); the byte columns are the
    modeled TPU wire claim.  Any gate failure raises.
    """
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_smoke_config
    from repro.configs.base import RunConfig
    from repro.core import group_wire_bytes_per_step, make_group_mixer
    from repro.core.mixing import make_schedule_mixer
    from repro.launch.mesh import gossip_agent_axes, make_gossip_mesh
    from repro.models import build_model
    from repro.train import (bus_layout_for, make_gossip_schedule,
                             make_group_plans, resolve_features)
    from .common import timeit_us

    A, W = 8, GROUPS_SWEEP_WINDOW
    cfg = get_smoke_config("deepseek_moe_16b")
    model = build_model(cfg)
    mesh = make_gossip_mesh(A)
    axes = gossip_agent_axes(mesh)

    configs = [
        ("baseline_all_gossip", ""),       # one dense group, legacy path
        ("grouped_all_gossip", "moe:1"),   # 2-group layout, both every step
        ("moe_opt_out", "moe"),            # experts never gossip
        ("moe_slow_cycle", "moe:4"),       # experts gossip 1-in-4 steps
    ]
    rows_out, by_label = [], {}
    for label, gspec in configs:
        run = RunConfig(global_batch=A, seq_len=8, algorithm="edm",
                        gossip_engine="ppermute", gossip_groups=gspec,
                        remat=False)
        feats = resolve_features(run)
        layout = bus_layout_for(model, A, groups=feats.groups)
        sched = make_gossip_schedule(run, A)
        plans = make_group_plans(run, layout, sched)
        scheds = {p.group.name: p.sched for p in plans
                  if p.sched is not None}
        per_step = [group_wire_bytes_per_step(layout.groups, scheds, t)
                    for t in range(W)]
        window = {g.name: sum(s[g.name] for s in per_step)
                  for g in layout.groups}
        window["total"] = sum(s["total"] for s in per_step)

        mix = make_group_mixer(plans, engine="ppermute", mesh=mesh,
                               agent_axes=axes)
        bus = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(0), (A, layout.rows, 128)),
            NamedSharding(mesh, P(axes)))
        # time a gossip step for every group (the max-cadence step W-1) and
        # a skip step (step 0 — for slow-cycle/opt-out the inactive groups'
        # rows are pure slices there)
        mix_on = jax.jit(lambda b: mix(b, W - 1))
        mix_off = jax.jit(lambda b: mix(b, 0))
        us_on = timeit_us(mix_on, bus, iters=iters)
        us_off = timeit_us(mix_off, bus, iters=iters)
        row = {
            "config": label, "gossip_groups": gspec, "agents": A,
            "rows": layout.rows,
            "group_rows": {g.name: g.rows for g in layout.groups},
            "group_gossip_every": {g.name: g.gossip_every
                                   for g in layout.groups},
            "window_steps": W,
            "wire_bytes_window": {k: int(v) for k, v in window.items()},
            "wire_bytes_per_step_avg": round(window["total"] / W, 1),
            "us_per_step_gossip": round(us_on, 1),
            "us_per_step_skip": round(us_off, 1),
        }
        rows_out.append(row)
        by_label[label] = dict(row, layout=layout, sched=sched, mix=mix,
                               bus=bus)

    # --- segment-composition gates (bit-exact, any divergence raises) ---
    ga = by_label["grouped_all_gossip"]
    whole = make_schedule_mixer(ga["sched"], "ppermute", mesh=mesh,
                                agent_axes=axes)
    for t in range(4):
        want = np.asarray(jax.jit(lambda b, t=t: whole(b, t))(ga["bus"]))
        got = np.asarray(jax.jit(lambda b, t=t: ga["mix"](b, t))(ga["bus"]))
        np.testing.assert_array_equal(
            got, want, err_msg=f"groups gate: 2-group all-gossip mixer != "
                               f"whole-bus schedule mixer at step {t}")
    oo = by_label["moe_opt_out"]
    (eg,) = [g for g in oo["layout"].groups if g.name == "experts"]
    got = np.asarray(jax.jit(lambda b: oo["mix"](b, 0))(oo["bus"]))
    src = np.asarray(oo["bus"])
    np.testing.assert_array_equal(
        got[:, eg.row:eg.row + eg.rows], src[:, eg.row:eg.row + eg.rows],
        err_msg="groups gate: opt-out expert rows were touched by gossip")
    want_dense = np.asarray(jax.jit(lambda b: whole(b, 0))(oo["bus"]))
    dense_rows = [slice(g.row, g.row + g.rows) for g in oo["layout"].groups
                  if g.name != "experts"]
    for sl in dense_rows:
        np.testing.assert_array_equal(
            got[:, sl], want_dense[:, sl],
            err_msg="groups gate: opt-out dense rows != whole-bus mix")

    # --- byte-accounting gates ---
    base = by_label["baseline_all_gossip"]["wire_bytes_window"]["total"]
    all2 = ga["wire_bytes_window"]["total"]
    opt = oo["wire_bytes_window"]["total"]
    slow = by_label["moe_slow_cycle"]["wire_bytes_window"]["total"]
    experts = ga["wire_bytes_window"]["experts"]
    assert opt < base, (opt, base)
    assert all2 - opt == experts, (all2, opt, experts)
    assert opt < slow < all2, (opt, slow, all2)
    assert slow - opt == experts // 4, (slow, opt, experts)
    gates = {
        "segment_composition": "pass",
        "opt_out_rows_untouched": "pass",
        "opt_out_lt_baseline": {"opt_out": int(opt), "baseline": int(base),
                                "status": "pass"},
        "delta_eq_expert_bytes": {"all_gossip": int(all2),
                                  "opt_out": int(opt),
                                  "experts_window": int(experts),
                                  "status": "pass"},
        "slow_cycle_between": {"slow": int(slow), "status": "pass"},
    }
    return {"rows": rows_out, "gates": gates}


def write_groups_bench_json(rows: List[dict], gates: dict) -> str:
    """Persist the policy-group sweep + byte/composition gates to
    BENCH_groups.json at the repo root."""
    payload = {
        "bench": "gossip_policy_groups",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "note": (
            "Gossip policy groups (DESIGN §12): per-leaf-group schedules, "
            "cadences and wire formats over one packed superbuffer.  "
            "'results' are measured on the 8-agent smoke MoE transformer "
            "behind the segment-composition gates (2-group all-gossip == "
            "whole-bus mixer bit-exactly; opt-out expert rows untouched); "
            "the byte columns carry the modeled wire claim: expert "
            "opt-out ships strictly fewer bytes than the all-gossip "
            "baseline, with the all-gossip - opt-out delta equal to the "
            "experts group's modeled bytes exactly, and slow-cycle "
            "(moe:4) in between at 1-in-4 expert steps."),
        "results": rows,
        "gates": gates,
    }
    with open(BENCH_GROUPS_JSON, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return BENCH_GROUPS_JSON


def _groups_csv_rows(rows: List[dict]) -> List[str]:
    from .common import csv_row
    return [csv_row(
        f"gossip_groups/{row['config']}",
        row["us_per_step_gossip"],
        f"A={row['agents']};rows={row['rows']};"
        f"wire_window={row['wire_bytes_window']['total']};"
        f"avg_step={row['wire_bytes_per_step_avg']}") for row in rows]


def _groups_subprocess(iters: int = 6) -> dict:
    """Run :func:`groups_sweep` under an 8-device host platform."""
    return _bench_subprocess(["--groups-inner", "--iters", str(iters)],
                             _GROUPS_MARKER, 8, "groups sweep")


# ---------------------------------------------------------------------------
# BLOCK_ROWS autotune (ROADMAP "tune BLOCK_ROWS", CPU-measurable half)
# ---------------------------------------------------------------------------

def autotune_block_rows(candidates=(128, 256, 512, 1024),
                        rows_sizes=(1024, 4096, 8192),
                        iters: int = 5, verbose: bool = True) -> List[dict]:
    """Sweep the Pallas grid-tile height for the fused EDM update and the
    3-ary gossip combine over per-agent bus sizes; prints the argmin per
    size.  On CPU the kernels run in interpret mode — the sweep machinery
    and the printed table are the portable half; re-run on a real TPU for
    the production argmin (REPRO_BLOCK_ROWS / --block-rows set it)."""
    import jax.numpy as jnp

    from repro.kernels.edm_update import edm_update_flat, gossip_axpy_flat
    from .common import timeit_us

    interpret = jax.default_backend() != "tpu"
    out = []
    for rows in rows_sizes:
        ks = jax.random.split(jax.random.PRNGKey(rows), 4)
        bufs = [jax.random.normal(k, (rows, 128), jnp.float32) for k in ks]
        row = {"rows": rows, "elems": rows * 128,
               "backend": jax.default_backend(),
               "interpret": interpret, "candidates": list(candidates)}
        for kernel in ("edm_update", "gossip_axpy"):
            us = {}
            for br in candidates:
                if rows % br:
                    continue
                if kernel == "edm_update":
                    fn = jax.jit(lambda a, b, c, d, br=br: edm_update_flat(
                        a, b, c, d, alpha=0.05, beta=0.9, block_rows=br,
                        interpret=interpret))
                    args = bufs
                else:
                    fn = jax.jit(lambda a, b, c, br=br: gossip_axpy_flat(
                        (a, b, c), (0.5, 0.25, 0.25), block_rows=br,
                        interpret=interpret))
                    args = bufs[:3]
                us[br] = timeit_us(fn, *args, iters=iters)
            best = min(us, key=us.get)
            row[kernel] = {"us": {str(k): round(v, 1) for k, v in us.items()},
                           "best": best}
            if verbose:
                table = " ".join(f"{br}:{u:.0f}us" for br, u in us.items())
                print(f"  block_rows/{kernel}/rows={rows}: {table} "
                      f"-> argmin={best}")
        out.append(row)
    return out


def write_edm_bench_json(results: List[dict]) -> str:
    """Persist the e2e EDM step sweep to BENCH_edm_step.json."""
    payload = {
        "bench": "edm_step_leafwise_vs_bus",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "results": results,
    }
    with open(BENCH_EDM_JSON, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return BENCH_EDM_JSON


def _e2e_csv_rows(rows: List[dict]) -> List[str]:
    from .common import csv_row
    out = []
    for row in rows:
        if row.get("path") == "equivalence":
            continue
        extra = (f";speedup={row['speedup_vs_leafwise']}x"
                 if "speedup_vs_leafwise" in row else "")
        out.append(csv_row(
            f"edm_step/{row['size']}/{row['path']}", row["us_per_step"],
            f"L={row['n_leaves']};permutes={row['permutes_per_step']};"
            f"launches={row['kernel_launches_per_step']};"
            f"hbm_padded={row['hbm_bytes_padded']}{extra}"))
    return out


def _schedule_subprocess(which: str, steps: int,
                         block_rows: int = 0) -> List[dict]:
    """Run :func:`schedule_sweep` under a 32-device host platform."""
    extra = {"REPRO_BLOCK_ROWS": str(block_rows)} if block_rows else None
    return _bench_subprocess(
        ["--schedule-inner", which, "--steps", str(steps),
         "--block-rows", str(block_rows)],
        _SCHED_MARKER, 32, "schedule sweep", extra_env=extra)


def _sched_csv_rows(rows: List[dict]) -> List[str]:
    from .common import csv_row
    return [csv_row(
        f"gossip_sched/{row['schedule']}/{row['config']}",
        row["us_per_step"],
        f"n={row['agents']};B={row['agents_per_device']};"
        f"wire_bytes={row['wire_bytes_per_step']};"
        f"permutes={row['permutes_per_step']}") for row in rows]


def write_bench_json(results: List[dict]) -> str:
    """Persist the schedule sweep to BENCH_gossip.json at the repo root."""
    payload = {
        "bench": "gossip_schedule_sweep",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "results": results,
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return BENCH_JSON


def _sweep_subprocess() -> List[str]:
    """Run :func:`sweep` under a 32-device host platform (one per agent)."""
    return _bench_subprocess(["--sweep"], _SWEEP_MARKER, 32, "engine sweep")


def run(verbose: bool = True) -> Dict:
    from repro.core import make_mixer, ring
    from repro.core.optimizers import make_edm
    from .common import csv_row, timeit_us

    results: Dict = {}
    lines = []
    topo = ring(8)
    d = 1 << 20
    x = jax.random.normal(jax.random.PRNGKey(0), (8, d))

    mix_dense = jax.jit(make_mixer(topo, "dense"))
    mix_shift = jax.jit(make_mixer(topo, "shifts"))
    us_d = timeit_us(mix_dense, x)
    us_s = timeit_us(mix_shift, x)
    lines.append(csv_row("gossip/dense_W", us_d, f"n=8;d={d}"))
    lines.append(csv_row("gossip/shift_rolls", us_s,
                         f"n=8;d={d};speedup_vs_dense={us_d / us_s:.2f}x"))

    # EDM unfused vs fused-kernel step (interpret-mode Pallas on CPU — the
    # derived column reports the modeled HBM-stream ratio, which is what
    # matters on TPU: unfused ≈ 11 streams vs fused 7).
    params = {"w": x}
    grads = {"w": 0.1 * x}
    o_un = make_edm(0.05, 0.9, make_mixer(topo), use_fused_kernel=False)
    st = o_un.init(params)
    step_un = jax.jit(lambda p, g, s: o_un.step(p, g, s))
    us_un = timeit_us(step_un, params, grads, st)
    lines.append(csv_row("edm_step/unfused_jnp", us_un,
                         "hbm_streams=11(x,g,m,psi->m,psi,phi + mix)"))
    lines.append(csv_row("edm_step/fused_pallas", float("nan"),
                         "hbm_streams=7;modeled_traffic_ratio=0.64;"
                         "validated=interpret_mode"))

    # engine × topology × fused sweep, one device per agent
    try:
        lines.extend(_sweep_subprocess())
    except Exception as e:  # pragma: no cover - environment-dependent
        lines.append(csv_row("gossip/engine_sweep", float("nan"),
                             f"skipped:{type(e).__name__}"))
        if verbose:
            print(f"  [engine sweep skipped: {e}]")

    # engine × schedule sweep (static vs round_robin vs alt_hier) + wire bytes
    try:
        sched_rows = _schedule_subprocess("all", steps=8)
        lines.extend(_sched_csv_rows(sched_rows))
        results["bench_json"] = write_bench_json(sched_rows)
        if verbose:
            print(f"  [schedule sweep -> {results['bench_json']}]")
    except Exception as e:  # pragma: no cover - environment-dependent
        lines.append(csv_row("gossip_sched/sweep", float("nan"),
                             f"skipped:{type(e).__name__}"))
        if verbose:
            print(f"  [schedule sweep skipped: {e}]")

    results["csv"] = lines
    if verbose:
        print("\n".join("  " + l for l in lines))
    return results


def _cli() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sweep", action="store_true",
                    help="(inner) engine×topology sweep; needs 32 devices")
    ap.add_argument("--schedule-inner", default=None,
                    help="(inner) engine×schedule sweep; needs 32 devices")
    ap.add_argument("--schedule", default=None,
                    choices=["static", "round_robin", "alt_hier", "all"],
                    help="run the engine×schedule sweep (in a 32-device "
                         "subprocess) and write BENCH_gossip.json")
    ap.add_argument("--steps", type=int, default=8,
                    help="steps per schedule config")
    ap.add_argument("--block-rows", type=int, default=0,
                    help="Pallas BLOCK_ROWS override for the fused combine "
                         "(0 = REPRO_BLOCK_ROWS / default)")
    ap.add_argument("--e2e-step", action="store_true",
                    help="leaf-wise vs bus-resident vs overlapped EDM step "
                         "sweep (in an 8-device subprocess) + equivalence "
                         "and overlap divergence gates; writes "
                         "BENCH_edm_step.json and BENCH_overlap.json")
    ap.add_argument("--e2e-inner", action="store_true",
                    help="(inner) e2e step sweep; needs 8 devices")
    ap.add_argument("--iters", type=int, default=6,
                    help="timing iterations per e2e config")
    ap.add_argument("--autotune-block-rows", action="store_true",
                    help="sweep the kernel BLOCK_ROWS tile over "
                         "{128,256,512,1024} per bus size and print the "
                         "argmin (interpret-mode wall clock off-TPU)")
    ap.add_argument("--sharded", action="store_true",
                    help="sharded vs gathered gossip sweep (DESIGN §7; in "
                         "an 8-device 2-pod x 4-shard subprocess) + the "
                         "sharded==dense equivalence gate; writes "
                         "BENCH_shard.json")
    ap.add_argument("--sharded-inner", action="store_true",
                    help="(inner) sharded sweep; needs 8 devices")
    ap.add_argument("--churn", action="store_true",
                    help="elastic churn sweep (DESIGN §8; in an 8-device "
                         "subprocess): us/step + wire bytes vs drop rate "
                         "with the masked==dense equivalence gate, plus "
                         "the churn divergence gates; writes "
                         "BENCH_elastic.json")
    ap.add_argument("--churn-inner", action="store_true",
                    help="(inner) elastic churn sweep; needs 8 devices")
    ap.add_argument("--wire", action="store_true",
                    help="quantized-wire sweep (DESIGN §9; in an 8-device "
                         "subprocess): us/step + codec-derived wire bytes "
                         "and compression ratio per format with the "
                         "oracle/masked/sharded equivalence gates, plus "
                         "the modeled n=32 byte cut and the EF divergence "
                         "gates; writes BENCH_wire.json")
    ap.add_argument("--wire-inner", action="store_true",
                    help="(inner) wire format sweep; needs 8 devices")
    ap.add_argument("--groups", action="store_true",
                    help="gossip policy-group sweep (DESIGN §12; in an "
                         "8-device subprocess): us/step + modeled per-group "
                         "wire bytes on the smoke MoE transformer per "
                         "--gossip-groups config, behind the segment-"
                         "composition and byte-accounting gates; writes "
                         "BENCH_groups.json")
    ap.add_argument("--groups-inner", action="store_true",
                    help="(inner) policy-group sweep; needs 8 devices")
    args = ap.parse_args()

    if args.sweep:
        print(_SWEEP_MARKER + json.dumps(sweep()))
    elif args.groups_inner:
        print(_GROUPS_MARKER + json.dumps(groups_sweep(iters=args.iters)))
    elif args.groups:
        payload = _groups_subprocess(iters=args.iters)
        print("\n".join(_groups_csv_rows(payload["rows"])))
        print(f"wrote {write_groups_bench_json(payload['rows'], payload['gates'])}")
    elif args.wire_inner:
        print(_WIRE_MARKER + json.dumps(wire_sweep(iters=args.iters)))
    elif args.wire:
        rows = _wire_subprocess(iters=args.iters)
        print("\n".join(_wire_csv_rows(rows)))
        modeled = wire_modeled_rows()
        gates = wire_divergence_gates()
        print(f"wrote {write_wire_bench_json(rows, modeled, gates)}")
    elif args.churn_inner:
        print(_ELASTIC_MARKER + json.dumps(elastic_sweep(iters=args.iters)))
    elif args.churn:
        rows = _elastic_subprocess(iters=args.iters)
        print("\n".join(_elastic_csv_rows(rows)))
        gates = churn_divergence_gates()
        print(f"wrote {write_elastic_bench_json(rows, gates)}")
    elif args.sharded_inner:
        print(_SHARD_MARKER + json.dumps(sharded_sweep(iters=args.iters)))
    elif args.sharded:
        rows = _shard_subprocess(iters=args.iters)
        print("\n".join(_shard_csv_rows(rows)))
        print(f"wrote {write_shard_bench_json(rows)}")
    elif args.autotune_block_rows:
        autotune_block_rows()
    elif args.e2e_inner:
        print(_E2E_MARKER + json.dumps(e2e_step_sweep(iters=args.iters)))
    elif args.e2e_step:
        payload = _e2e_subprocess(iters=args.iters)
        rows, overlap_rows = payload["rows"], payload["overlap"]
        print("\n".join(_e2e_csv_rows(rows)))
        print("\n".join(_overlap_csv_rows(overlap_rows)))
        gates = overlap_divergence_gates()
        print(f"wrote {write_edm_bench_json(rows)}")
        print(f"wrote {write_overlap_bench_json(overlap_rows, gates)}")
    elif args.schedule_inner:
        print(_SCHED_MARKER + json.dumps(schedule_sweep(
            args.schedule_inner, steps=args.steps,
            block_rows=args.block_rows)))
    elif args.schedule:
        rows = _schedule_subprocess(args.schedule, steps=args.steps,
                                    block_rows=args.block_rows)
        print("\n".join(_sched_csv_rows(rows)))
        print(f"wrote {write_bench_json(rows)}")
    else:
        print("\n".join(run()["csv"]))


if __name__ == "__main__":
    _cli()
