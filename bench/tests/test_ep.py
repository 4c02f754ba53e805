"""The expert-share training cell (``train_ep`` kind) on the CPU at a tiny
size: a sound run is correct and broken steps are not, the float8 control
and the half-batch fault fail the committed limits, the new scopes are
found in the compiled step, and the readers' counts are the hand counts.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_ep.py
"""
from __future__ import annotations

import collections

import pytest

import cpu_run
from bench import ep_scopes, harness, scopes
from test_faults import broken, passes

CELL = "deepseek_v2_lite.train.seq4096"
SEED = 2**31 + 211
TINY = {"n_layers": 3, "d_model": 128, "n_heads": 2, "n_kv_heads": 2,
        "kv_lora_rank": 32, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
        "v_head_dim": 32, "d_ff": 64, "dense_d_ff": 128, "vocab_size": 512,
        "n_experts": 16, "experts_per_token": 4, "n_experts_held": 4,
        "expert_first": 4, "rope_original_max_pos": 64}
TRAFFIC = {"seq_len": 128, "trace_steps": 2}


@pytest.mark.parametrize("fault", ["none", "unchanged", "half_batch"])
def test_a_broken_expert_share_step_is_not_correct(fault, monkeypatch):
    import repro.train as rt
    monkeypatch.setattr(rt, "build_train_step", broken(fault))
    line, out = cpu_run.run_cell(CELL, SEED, model=TINY, traffic=TRAFFIC,
                                 seconds=0.2)
    assert line["correct"] is (fault == "none"), line["checks"]
    assert out["info"]["compiles_in_window"] == 0
    assert out["counts"]["moe_rows_held"] > 0


def test_the_control_and_the_faults_fail_the_committed_limits():
    """The float8 control and the half-batch fault, planted in the
    reference, fail the cell's limits where the program passes them.  One
    agent has no exchange, so the no-gossip fault is the sound reference
    itself and reads 0."""
    import jax
    from bench import control
    spec = cpu_run.spec_for(CELL, TINY, TRAFFIC)
    drv = harness.load_module(spec.driver_path)
    cell = drv.Cell(spec.config["model"], spec.traffic, jax.devices()[:1])
    rows = {r["kind"]: r for r in control.readings_table(
        cell, [SEED], {SEED}, log=lambda _: None)}
    assert passes(rows["program"], spec.limits), rows["program"]
    for kind in ("control_fp8", "fault_half_batch"):
        assert not passes(rows[kind], spec.limits), (kind, rows[kind])
    assert all(rows["fault_no_gossip"][k] == 0.0
               for k in spec.limits["limits"])


def test_the_expert_share_scopes_are_in_the_compiled_step():
    import jax
    spec = cpu_run.spec_for(CELL, TINY, TRAFFIC)
    texts = ep_scopes.compiled_texts(spec.config["model"], spec.traffic,
                                     jax.devices()[:1])
    with ep_scopes._with_ep_scopes():
        maps = scopes.program_maps(texts)
    assert set(ep_scopes.EP_SCOPES) <= set(maps["scopes"])
    found = collections.Counter(maps[scopes.STEP].values())
    assert all(found[s] for s in ep_scopes.EP_SCOPES + ("grad",)), found
    assert scopes.SCOPES == ("bus_unpack", "grad", "bus_pack",
                             "edm_update_bus", "step_metrics")


def test_a_loop_event_does_not_count_its_body_twice():
    """The layer scan's ``while`` carries ``grad`` and spans its body's ops,
    which carry ``mla`` and ``moe_experts``: without the loop's event each
    op counts once and the scopes add up to the step."""
    from bench import trace as btrace
    d, host = "/device:TPU:0", "/host:CPU"
    ev = lambda plane, name, start, dur: btrace.Event(
        plane, "XLA Ops" if plane.startswith("/device") else "python", name,
        float(start), float(dur))
    red = btrace.Reduction([ev(host, btrace.WINDOW_SPAN, 0, 100),
                            ev(d, "while.1", 0, 60), ev(d, "fusion.1", 0, 30),
                            ev(d, "gmm.1", 30, 20), ev(d, "fusion.2", 60, 40)])
    step_ops = {"while.1": "grad", "fusion.1": "mla", "gmm.1": "moe_experts",
                "fusion.2": "edm_update_bus"}
    with ep_scopes._with_ep_scopes():
        ms = scopes.attribute(ep_scopes.leaf_ops(red, {"while.1"}), step_ops,
                              [], 1)
    parts = sum(ms[k] for k in scopes.SCOPES + ep_scopes.EP_SCOPES
                + ("unscoped",))
    assert parts == pytest.approx(ms["step"]) == pytest.approx(90e-6)
    assert ms["mla"] == pytest.approx(30e-6) and ms["grad"] == 0.0


def test_the_mix_is_found_from_the_counts():
    m = cpu_run.spec_for(CELL).config["model"]
    counts = {"agents": 1, "agents_per_device": 1, "per_agent_batch": 1,
              "seq_len": 4096}
    assert ep_scopes.traffic_for(m, counts)["kind"] == "train_ep"
    assert ep_scopes.traffic_for(m, dict(counts, seq_len=1024)) is None
    assert scopes.traffic_for(m, counts) is None


def reading(**counts):
    class R:
        pass
    r = R()
    r.model = cpu_run.spec_for(CELL).config["model"]
    r.counts = counts
    r.chips = 1
    r.peaks = harness.lookup_peaks("TPU v5 lite")
    return r


def test_counts_at_published_widths():
    mfu = harness.load_module(harness.BENCH / "metrics/moe_train_mfu.py")
    gmm = harness.load_module(harness.BENCH / "metrics/moe_gmm_roofline.py")
    m = reading().model
    # one MLA sub-block: q 2048·16·192, latent + rope key 2048·576,
    # keys and values 512·16·256, output 16·128·2048
    assert mfu.mla_params(m) == 6291456 + 1179648 + 2097152 + 4194304
    tokens, rows = 4096, 15360
    per_token = (6 * (2 * 13762560 + 2 * 16 * 320 * 2048.5)
                 + 2 * 3 * 2048 * 10944 + 5 * 2 * 2048 * (64 + 6 * 1408)
                 + 2 * 2048 * 12800)
    assert mfu.fwd_flops(m, tokens, 4096, rows) == pytest.approx(
        per_token * tokens + 6 * 2048 * 1408 * rows)
    # 12 calls a MoE layer a step, each over that layer's rows
    r = reading(moe_rows_held=rows, steps_traced=1)
    nbytes, nflops = gmm.cost(r, 60)
    assert nflops == pytest.approx(2 * 2048 * 1408 * rows * 12)
    assert nbytes == pytest.approx(2 * (60 * 8 * 2048 * 1408
                                        + 12 * rows * (2048 + 1408)))
