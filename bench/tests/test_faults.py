"""The comparison that decides ``correct`` has to catch a broken timed
path.  Each fault test drives a whole run of a cell on the CPU at a tiny
size, past the harness's look for a chip, with the program's timed path
broken underneath, and sees ``correct`` come out false; a sound run
beside them comes out true.  The control test reads, at the same tiny
size, the reference computed in float8 in the program's place, and sees
it fail the cell's committed limits where the program passes them.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_faults.py
"""
from __future__ import annotations

import dataclasses

import pytest

import cpu_run
from bench import checks, control, harness

CELL = "smollm_360m.train.seq1024"
SEED = 2**31 + 101


def broken(fault):
    import repro.train as rt
    original = rt.build_train_step

    def build(model, run, sched, **kw):
        if fault == "no_gossip":       # the exchange between agents left out
            run = dataclasses.replace(run, gossip_every=10**6)
        step = original(model, run, sched, **kw)
        if fault == "unchanged":       # the step returns its state unchanged
            return lambda state, batch: (state, step(state, batch)[1])
        if fault == "half_batch":      # half of each row left out
            def half(state, batch):
                toks = batch["tokens"]
                return step(state, {"tokens": toks[..., :toks.shape[-1] // 2
                                                   + 1]})
            return half
        return step

    return build


@pytest.mark.parametrize("fault", ["none", "unchanged", "half_batch",
                                   "no_gossip"])
def test_a_broken_step_is_not_correct(fault, monkeypatch):
    import repro.train as rt
    monkeypatch.setattr(rt, "build_train_step", broken(fault))
    line, _ = cpu_run.run_cell(CELL, SEED, model=cpu_run.TINY_LM,
                               traffic=cpu_run.TINY_TRAIN, seconds=0.2)
    assert line["correct"] is (fault == "none"), line["checks"]


SERVE_CELL = "starcoder2_7b.serve.code"


@pytest.mark.parametrize("fault", ["none", "altered_token", "dropped"])
def test_an_altered_served_token_is_not_correct(fault, monkeypatch):
    from repro.serve.scheduler import ContinuousBatchingEngine
    original = ContinuousBatchingEngine.step

    def step(self):
        original(self)
        if fault == "altered_token":     # every decoded token off by one
            for st in self.live.values():
                st.emitted[-1] = (st.emitted[-1] + 1) % cpu_run.TINY_CODER[
                    "vocab_size"]
        if fault == "dropped":           # one answer never comes
            self.completed.pop(0, None)

    monkeypatch.setattr(ContinuousBatchingEngine, "step", step)
    line, _ = cpu_run.run_cell(SERVE_CELL, SEED, model=cpu_run.TINY_CODER,
                               traffic=cpu_run.TINY_CODE, seconds=0.3)
    assert line["correct"] is (fault == "none"), line["checks"]


def readings(cell, model, traffic):
    import jax
    spec = cpu_run.spec_for(cell, model, traffic)
    drv = harness.load_module(spec.driver_path)
    c = drv.Cell(spec.config["model"], spec.traffic, jax.devices()[:1])
    if spec.kind == "open_loop":
        rows = control.serve_readings(c, [SEED], {SEED}, log=lambda _: None)
    else:
        rows = control.readings_table(c, [SEED], {SEED}, log=lambda _: None)
    return spec, {r["kind"]: r for r in rows}


def passes(reading, limits) -> bool:
    return all(c["value"] <= c["limit"] for c in
               checks.with_limits(reading, limits).values())


@pytest.mark.parametrize("cell,model,traffic", [
    (CELL, cpu_run.TINY_LM, cpu_run.TINY_TRAIN),
    (SERVE_CELL, cpu_run.TINY_CODER, cpu_run.TINY_CODE)])
def test_the_float8_control_is_not_correct(cell, model, traffic):
    spec, rows = readings(cell, model, traffic)
    assert passes(rows["program"], spec.limits), rows["program"]
    assert not passes(rows["control_fp8"], spec.limits), rows["control_fp8"]
