"""Driver of the ``train_steps`` traffic kind: decentralized EDM training.

The mix's file gives the agents and how they sit on the chips, the
per-agent batch, the sequence length, the heterogeneity of the token
streams and the optimizer; the configuration's file gives the model.
The system under test is the program's own training path, as its CLI
drives it: ``build_model``, ``init_state``, ``make_gossip_schedule``,
``make_gossip_mesh`` and ``build_train_step`` on the packed bus with the
fused kernels, jitted with the state donated.

Set-up makes the weights on the device from the seed, builds the state
and the step, and drives that same step through its first
``check_steps`` steps on the window's own feed: those steps compile it,
and their loss, first gradient and parameter change are what the
reference is compared with once the window has closed.  The window then
runs steps back to back for ``--seconds``, at most ``inflight`` steps
ahead of the device, and ends with one wait for the last step.

Tokens come from a seeded order-1 Markov backbone tilted per agent by a
Dirichlet(φ) unigram mix (a copy of the program's ``SyntheticLM``),
sampled on the device for every step, as the training CLI does.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import gc
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import checks, trace as btrace
from bench.harness import CompileCounter, memory_peak
from bench.reference import edm as edm_ref
from bench.weights import make_weights, seed_key

WEIGHTS, DATA = 1, 2


# ---------------------------------------------------------------------------
# token streams
# ---------------------------------------------------------------------------

def stream_tables(seed: int, vocab: int, n_agents: int, p: Dict):
    """Per-seed Markov logits (V, V) and per-agent tilt logits (A, V)."""
    rng = np.random.default_rng(int(seed))
    V = min(vocab, int(p["active_vocab"]))
    trans = rng.normal(size=(V, V)).astype(np.float32) * p["sharpness"]
    tilt = rng.dirichlet(np.full(V, p["phi"]), size=n_agents)
    return (jnp.asarray(trans),
            jnp.asarray(np.log(tilt + 1e-8).astype(np.float32)))


def sample_tokens(tables, key, *, batch: int, seq_len: int, mix: float):
    """(A, batch, seq_len) int32 token rows, one stream per agent."""
    trans, tilt = tables
    V = trans.shape[0]

    def agent_stream(key, tilt_a):
        def step(tok, k):
            logits = trans[tok] * (1 - mix) + tilt_a[None] * mix
            nxt = jax.random.categorical(k, logits, axis=-1)
            return nxt, nxt
        k0, k1 = jax.random.split(key)
        tok0 = jax.random.randint(k0, (batch,), 0, V)
        _, toks = jax.lax.scan(step, tok0, jax.random.split(k1, seq_len - 1))
        return jnp.concatenate([tok0[None], toks], 0).T

    keys = jax.random.split(key, tilt.shape[0])
    return jax.vmap(agent_stream)(keys, tilt).astype(jnp.int32)


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------

class Cell:
    """The program's training step for one configuration and mix, built
    once; seeds are started on it one at a time."""

    def __init__(self, model_cfg: Dict, p: Dict, devices):
        from repro.configs.base import ModelConfig, RunConfig
        from repro.launch.mesh import gossip_agent_axes, make_gossip_mesh
        from repro.models import build_model
        from repro.train import (build_train_step, bus_layout_for,
                                 init_state, make_gossip_schedule,
                                 state_specs)
        from repro.core import bus as parambus
        from jax.sharding import NamedSharding, PartitionSpec

        self.m, self.p, self.devices = model_cfg, p, devices
        self.A, self.apd = int(p["agents"]), int(p["agents_per_device"])
        self.b, self.S = int(p["per_agent_batch"]), int(p["seq_len"])
        cfg = ModelConfig(**model_cfg)
        model = build_model(cfg)
        self.run_cfg = RunConfig(
            global_batch=self.A * self.b, seq_len=self.S, agents="data",
            algorithm="edm", alpha=p["alpha"], beta=p["beta"],
            topology=p["topology"], gossip_engine="ppermute",
            packed_bus=True, agents_per_device=self.apd, wire=p["wire"],
            overlap=p["overlap"])
        sched = make_gossip_schedule(self.run_cfg, self.A)
        mesh = make_gossip_mesh(self.A, agents_per_device=self.apd)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        self.weights = jax.jit(functools.partial(make_weights, shapes))
        model = dataclasses.replace(
            model, init=functools.partial(make_weights, shapes))
        shardings = jax.tree.map(
            lambda sp: NamedSharding(mesh, sp),
            state_specs(model, self.run_cfg,
                        multi_pod="pod" in mesh.axis_names),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        self.init = jax.jit(functools.partial(init_state, model,
                                              self.run_cfg, self.A),
                            out_shardings=shardings)
        self.step = jax.jit(
            build_train_step(model, self.run_cfg, sched,
                             use_fused_kernel=bool(p["fused_kernel"]),
                             mesh=mesh, agent_axes=gossip_agent_axes(mesh)),
            donate_argnums=(0,))
        self.feed = jax.jit(
            lambda tables, key, t: {"tokens": sample_tokens(
                tables, jax.random.fold_in(key, t), batch=self.b,
                seq_len=self.S, mix=p["mix"])})
        layout = bus_layout_for(model, self.A)

        def leaf_norms(bus):
            views = jax.tree.leaves(parambus.leaf_views(layout, bus))
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
                v.reshape(v.shape[0], -1)), axis=1)) for v in views], 1)

        def change_norms(bus, w):
            views = jax.tree.leaves(parambus.leaf_views(layout, bus))
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
                (v - x0[None].astype(jnp.float32)).reshape(v.shape[0], -1)),
                axis=1)) for v, x0 in zip(views, jax.tree.leaves(w))], 1)

        self.leaf_norms = jax.jit(leaf_norms)
        self.change_norms = jax.jit(change_norms)

    @property
    def tokens_per_step(self) -> int:
        return self.A * self.b * self.S

    def start(self, seed: int):
        """State, token tables and data key of one seed."""
        tables = stream_tables(seed, self.m["vocab_size"], self.A, self.p)
        return {"state": self.init(seed_key(seed, WEIGHTS)),
                "tables": tables, "dkey": seed_key(seed, DATA),
                "seed": seed, "t": 0}

    def one_step(self, run):
        """The window's own call: feed step t's rows, dispatch the step."""
        with jax.profiler.TraceAnnotation("bench.feed"):
            batch = self.feed(run["tables"], run["dkey"], run["t"])
        with jax.profiler.TraceAnnotation("bench.step_dispatch"):
            run["state"], metrics = self.step(run["state"], batch)
        run["t"] += 1
        return metrics

    def first_steps(self, run) -> Dict[str, np.ndarray]:
        """Drive the first ``check_steps`` steps; the program's readings."""
        n = int(self.p["check_steps"])
        losses, grad_norms = [], None
        for i in range(n):
            metrics = self.one_step(run)
            losses.append(float(metrics["loss"]))
            if i == 0:
                grad_norms = np.asarray(
                    self.leaf_norms(run["state"]["opt"]["m"])) \
                    / (1.0 - self.p["beta"])
        w0 = self.weights(seed_key(run["seed"], WEIGHTS))
        change = np.asarray(self.change_norms(run["state"]["params"], w0))
        del w0
        return {"losses": np.asarray(losses), "grad_norms": grad_norms,
                "change_norms": change}

    def window(self, run, *, seconds: float = 0.0, steps: int = 0):
        """Steps back to back until ``seconds`` have passed (or ``steps``
        are dispatched), at most ``inflight`` ahead; one wait at the end.
        Returns (steps, wall seconds, losses)."""
        inflight = int(self.p["inflight"])
        pending, losses = collections.deque(), []
        n = 0
        with jax.profiler.TraceAnnotation(btrace.WINDOW_SPAN):
            t0 = time.perf_counter()
            while True:
                metrics = self.one_step(run)
                n += 1
                pending.append(metrics["loss"])
                losses.append(metrics["loss"])
                if len(pending) > inflight:
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        pending.popleft().block_until_ready()
                if (steps and n >= steps) or (
                        not steps and time.perf_counter() - t0 >= seconds):
                    break
            with jax.profiler.TraceAnnotation("bench.drain"):
                jax.block_until_ready((run["state"], metrics))
            wall = time.perf_counter() - t0
        return n, wall, np.asarray([float(l) for l in losses])

    def reference(self, seed: int, **variant) -> Dict[str, np.ndarray]:
        """The plain reference's readings of the same first steps."""
        n = int(self.p["check_steps"])
        tables = stream_tables(seed, self.m["vocab_size"], self.A, self.p)
        dkey = seed_key(seed, DATA)
        batches = [self.feed(tables, dkey, t)["tokens"] for t in range(n)]
        w0 = self.weights(seed_key(seed, WEIGHTS))
        return edm_ref.run(w0, self.m, batches, alpha=self.p["alpha"],
                           beta=self.p["beta"], topology=self.p["topology"],
                           steps=n, devices=list(self.devices)
                           if len(self.devices) > 1 else None, **variant)

    def counts(self) -> Dict:
        terms = 1 if self.A == 1 else (2 if self.A == 2 else 3)
        return {"agents": self.A, "agents_per_device": self.apd,
                "per_agent_batch": self.b, "seq_len": self.S,
                "tokens_per_step": self.tokens_per_step,
                "gossip_terms": terms}


def run(ctx) -> Dict:
    spec = ctx.spec
    phases = {"imports": time.perf_counter() - ctx.t_start}
    cell = Cell(spec.config["model"], spec.traffic, ctx.devices)
    run_ = cell.start(ctx.seed)
    jax.block_until_ready(run_["state"])
    phases["weights_and_state"] = time.perf_counter() - ctx.t_start
    prog = cell.first_steps(run_)
    ctx.setup_done()
    phases["first_steps"] = ctx.setup_s
    out: Dict = {"counts": cell.counts(), "trace": None, "e2e": {}}
    with CompileCounter() as compiles:
        if ctx.trace:
            box = {}

            def traced():
                # one step, finished, before the window: the first dispatch
                # under the profiler blocks the host for tens of ms
                jax.block_until_ready(cell.one_step(run_))
                box["r"] = cell.window(run_, steps=int(spec.traffic[
                    "trace_steps"]))

            events = btrace.capture(traced)
            n, wall, losses = box["r"]
            out["trace"] = btrace.Reduction(events)
            out["counts"]["steps_traced"] = n
        else:
            n, wall, losses = cell.window(run_, seconds=ctx.seconds)
            out["e2e"]["train_tokens_per_s"] = n * cell.tokens_per_step / wall
    out["memory_peak_bytes"] = memory_peak(ctx.devices)
    out["attempted"] = n
    out["failed"] = int(np.sum(~np.isfinite(losses)))
    out["info"] = {"setup_s": ctx.setup_s, "window_steps": n,
                   "window_s": wall, "compiles_in_window": compiles.n,
                   "loss_at_window_end": float(losses[-1]),
                   "peak_bytes_in_use": out["memory_peak_bytes"],
                   "first_losses": [float(x) for x in prog["losses"]],
                   "setup_phases_s": phases}
    del run_
    gc.collect()
    t0 = time.perf_counter()
    ref = cell.reference(ctx.seed)
    readings = checks.train_readings(prog, ref)
    out["checks"] = checks.with_limits(readings, spec.limits)
    out["info"]["reference_s"] = time.perf_counter() - t0
    out["info"]["reference_losses"] = [float(x) for x in ref["losses"]]
    return out
