"""The comparison that decides ``correct`` has to catch a broken timed
path.  Each fault test drives a whole run of a cell on the CPU at a tiny
size, past the harness's look for a chip, with the program's timed path
broken underneath, and sees ``correct`` come out false; a sound run
beside them comes out true.  The control test reads, at the same tiny
size, the reference computed in float8 in the program's place, and sees
it fail the cell's limits where the program passes them.

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
# The serving cell is not in BENCHMARK.json yet (PERF.md section 7): its
# limit comes from chip readings when it is added.  Here it runs as it
# will be added, with a limit between the tiny program's widest gaps
# (under 0.01) and its float8 control's (0.19 and more).
SERVE_ENTRY = {"name": SERVE_CELL, "config": "starcoder2_7b",
               "traffic": "serve_code", "chips": 1, "why": "x"}
SERVE_LIMITS = {"logit_gap": 0.1}
TINY_CODER = {"n_layers": 2, "d_model": 128, "n_heads": 2, "n_kv_heads": 1,
              "head_dim": 64, "d_ff": 256, "vocab_size": 512}
TINY_CODE = {"rate": 40.0, "prompt": [40, 0.5, 8, 96],
             "output": [6, 0.5, 2, 12], "prefill_chunk": 16,
             "page_size": 16, "max_slots": 8, "max_context": 128,
             "num_pages": 80, "drain_seconds": 30, "trace_seconds": 0.3}


@pytest.fixture(scope="module")
def serve_root(tmp_path_factory):
    return cpu_run.root_with(tmp_path_factory.mktemp("serve"), SERVE_ENTRY,
                             "bench/configs/starcoder2_7b.json", SERVE_LIMITS)


@pytest.mark.parametrize("fault", ["none", "altered_token"])
def test_an_altered_served_token_is_not_correct(fault, monkeypatch,
                                                serve_root):
    from repro.serve.scheduler import ContinuousBatchingEngine
    original = ContinuousBatchingEngine.step

    def step(self):
        original(self)
        if fault == "altered_token":     # every decoded token off by one
            for st in self.live.values():
                st.emitted[-1] = (st.emitted[-1] + 1) % TINY_CODER[
                    "vocab_size"]

    monkeypatch.setattr(ContinuousBatchingEngine, "step", step)
    line, _ = cpu_run.run_cell(SERVE_CELL, SEED, model=TINY_CODER,
                               traffic=TINY_CODE, seconds=0.3,
                               root=serve_root)
    assert line["correct"] is (fault == "none"), line["checks"]


def readings(cell, model, traffic, root):
    import jax
    spec = cpu_run.spec_for(cell, model, traffic, root)
    drv = harness.load_module(spec.driver_path)
    c = drv.Cell(spec.config["model"], spec.traffic, jax.devices()[:1])
    if spec.kind == "open_loop":
        rows = control.serve_readings(c, [SEED], {SEED}, 0.3,
                                      log=lambda _: None)
    else:
        rows = control.readings_table(c, [SEED], {SEED}, log=lambda _: None)
    return spec, {r["kind"]: r for r in rows}


def passes(reading, limits) -> bool:
    return all(c["value"] <= c["limit"] for c in
               checks.with_limits(reading, limits).values())


@pytest.mark.parametrize("cell,model,traffic", [
    (CELL, cpu_run.TINY_LM, cpu_run.TINY_TRAIN),
    (SERVE_CELL, TINY_CODER, TINY_CODE)])
def test_the_float8_control_is_not_correct(cell, model, traffic, request):
    root = (request.getfixturevalue("serve_root") if cell == SERVE_CELL
            else cpu_run.ROOT)
    spec, rows = readings(cell, model, traffic, root)
    assert passes(rows["program"], spec.limits), rows["program"]
    assert not passes(rows["control_fp8"], spec.limits), rows["control_fp8"]
