"""Driver of the ``train_ep`` traffic kind: decentralized EDM training of a
model whose expert layers hold one chip's share of their experts.

Everything is the ``train_steps`` kind's (``bench/traffic/train_steps.py``:
the same program path, feed, window and checks) but three things:

- the reference is ``bench/reference/mla_moe.py`` (MLA, leading dense
  layers, the expert share, the sequence-wise auxiliary loss);
- the mix's ``shape_seed`` makes the weights and the token streams'
  tables (the Markov backbone and each agent's tilt), the run's seed
  draws the tokens from them: how many assignments the held experts get
  follows the router's weights and the tokens' distribution, and with
  both drawn per seed a step's time moved with it, so every seed times
  the same expert load up to sampling (the serving mix fixes its schedule
  with ``shape_seed`` the same way);
- the step's ``moe_rows_held`` (assignments the held experts computed,
  summed over layers) is read from every window step and reported in the
  counts, for ``moe_train_mfu`` and ``moe_gmm_roofline``.
"""
from __future__ import annotations

import gc
import time
from typing import Dict

import jax
import numpy as np

from bench import checks, trace as btrace
from bench.harness import BENCH, CompileCounter, load_module, memory_peak
from bench.reference import mla_moe
from bench.weights import seed_key

steps = load_module(BENCH / "traffic" / "train_steps.py")


class Cell(steps.Cell):
    """``train_steps.Cell`` with the expert-share reference, weights and
    token tables from the mix's ``shape_seed``, and the step's held-row
    counter kept."""

    def tables(self):
        return steps.stream_tables(int(self.p["shape_seed"]),
                                   self.m["vocab_size"], self.A, self.p)

    def start(self, seed: int):
        """State and token tables from the shape seed, the data key from
        ``seed``.  ``run["seed"]`` is the seed the weights come from
        (``first_steps`` remakes x(0) from it)."""
        shape = int(self.p["shape_seed"])
        return {"state": self.init(seed_key(shape, steps.WEIGHTS)),
                "tables": self.tables(), "dkey": seed_key(seed, steps.DATA),
                "seed": shape, "t": 0, "rows": []}

    def one_step(self, run):
        metrics = super().one_step(run)
        run["rows"].append(metrics["moe_rows_held"])
        return metrics

    def reference(self, seed: int, **variant) -> Dict[str, np.ndarray]:
        n = int(self.p["check_steps"])
        tables, dkey = self.tables(), seed_key(seed, steps.DATA)
        batches = [self.feed(tables, dkey, t)["tokens"] for t in range(n)]
        w0 = self.weights(seed_key(int(self.p["shape_seed"]), steps.WEIGHTS))
        return mla_moe.run(w0, self.m, batches, alpha=self.p["alpha"],
                           beta=self.p["beta"], topology=self.p["topology"],
                           steps=n, devices=list(self.devices)
                           if len(self.devices) > 1 else None, **variant)

    def counts(self, rows=()) -> Dict:
        """The ``train_steps`` counts, and ``moe_rows_held``: the held
        experts' assignments over the given steps' counters."""
        return dict(super().counts(),
                    moe_rows_held=int(sum(int(r) for r in rows)))


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
    out: Dict = {"trace": None, "e2e": {}}
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
        else:
            n, wall, losses = cell.window(run_, seconds=ctx.seconds)
            out["e2e"]["train_tokens_per_s"] = n * cell.tokens_per_step / wall
    out["counts"] = cell.counts(run_["rows"][-n:])
    if ctx.trace:
        out["counts"]["steps_traced"] = n
    out["memory_peak_bytes"] = memory_peak(ctx.devices)
    out["attempted"] = n
    out["failed"] = int(np.sum(~np.isfinite(losses)))
    out["info"] = {"setup_s": ctx.setup_s, "window_steps": n,
                   "window_s": wall, "compiles_in_window": compiles.n,
                   "loss_at_window_end": float(losses[-1]),
                   "peak_bytes_in_use": out["memory_peak_bytes"],
                   "moe_rows_held_per_step": out["counts"]["moe_rows_held"]
                   / n,
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
