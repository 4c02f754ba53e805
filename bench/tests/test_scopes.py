"""Tests of the train step's scope attribution (``bench/scopes.py``), on
the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

Hand-made HLO text and events pin the rules; the trace recorded before the
step had scopes (``trace_train_seq256.json.gz``) pins that the reduction
and every earlier reader give the numbers they gave then; the scoped trace
recorded on the chip (``trace_train_seq256_scoped.json.gz`` with its
``.ops.json.gz``) pins that the scopes and the unscoped ops make up the
step's busy time.
"""
from __future__ import annotations

import jax
import pytest

import cpu_run
from bench import harness, scopes, trace as btrace

ROOT = cpu_run.ROOT
TESTDATA = ROOT / "bench" / "testdata"
PARENT_TRACE = TESTDATA / "trace_train_seq256.json.gz"
SCOPED_TRACE = TESTDATA / "trace_train_seq256_scoped.json.gz"
SEQ256 = "smollm_360m.train.seq256"
# the counts ``train_steps`` reports for the seq256 mix, 2 steps traced
SEQ256_COUNTS = {"agents": 2, "agents_per_device": 2, "per_agent_batch": 1,
                 "seq_len": 256, "tokens_per_step": 512, "gossip_terms": 2,
                 "steps_traced": 2}

HLO = """HloModule jit_step, is_scheduled=true

FileNames
1 "trainer.py"

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %convert.1 = f32[4]{0} convert(%p), metadata={op_name="jit(step)/bus_unpack/convert_element_type"}
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %x = f32[4]{0} get-tuple-element(%t), index=1
  %dot.3 = f32[4]{0} dot(%x, %x), lhs_contracting_dims={0}, rhs_contracting_dims={0}
  ROOT %tuple.2 = (s32[], f32[4]{0}) tuple(%i, %dot.3)
}

%cond (t.1: (s32[], f32[4])) -> pred[] {
  %t.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (a: f32[4]) -> (f32[4], f32[4]) {
  %a = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation
  %while.1 = (s32[], f32[4]{0}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(step)/grad/vmap(transpose(jvp()))/while"}
  %copy.1 = f32[4]{0} copy(%fusion.1)
  %broadcast.2 = f32[4]{0} broadcast(%c), dimensions={}
  %sqrt.1 = f32[4]{0} sqrt(%broadcast.2), metadata={op_name="jit(step)/step_metrics/sqrt" stack_frame_id=3}
  ROOT %tuple.9 = (f32[4]{0}, f32[4]{0}) tuple(%copy.1, %sqrt.1)
}
"""


def test_scope_components_bare_or_under_transforms():
    assert scopes.scopes_in(
        "jit(train_step)/grad/vmap(transpose(jvp()))/dot_general") == ["grad"]
    assert scopes.scopes_in("jit(f)/vmap(bus_pack)/add") == ["bus_pack"]
    assert scopes.scopes_in("jit(f)/transpose(jvp(grad))/mul") == ["grad"]
    # a function named like a scope is not the scope
    assert scopes.scopes_in(
        "jit(train_step)/jit(edm_update_bus)/edm_update") == []
    assert scopes.scopes_in("jit(f)/grad/step_metrics/x") == [
        "grad", "step_metrics"]
    assert scopes.scopes_in(None) == []


def test_hlo_ops_take_scopes_from_metadata_fusions_loops_and_data():
    ops = scopes.hlo_ops(HLO)
    assert ops["fusion.1"] == "bus_unpack"      # its fused ops' scope
    assert ops["while.1"] == "grad"
    assert ops["dot.3"] == "grad"               # no metadata: the loop's
    assert ops["lt"] == "grad"
    assert ops["sqrt.1"] == "step_metrics"
    # ops XLA made: the scope of what they read, else of what reads them
    assert ops["copy.1"] == "bus_unpack"
    assert ops["broadcast.2"] == "step_metrics"
    assert ops["tuple.9"] is None               # reads two scopes
    assert "convert.1" not in ops               # fused, never an op
    assert scopes.hlo_scopes(HLO) == ["bus_unpack", "grad", "step_metrics"]


def ev(plane, name, start, dur):
    return btrace.Event(plane, "XLA Ops" if plane.startswith("/device")
                        else "python", name, float(start), float(dur))


def test_attribution_on_hand_made_events():
    d0, d1, host = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
    step = {"fusion.1": "bus_unpack", "while.1": "grad", "dot.3": "grad",
            "copy.1": None, "sqrt.1": "step_metrics", "fusion.7": "grad"}
    feed = ["xor.1", "fusion.7"]            # fusion.7 is in both programs
    events = [
        ev(host, btrace.WINDOW_SPAN, 0, 1000),
        ev(d0, "fusion.1", -50, 100),       # crosses the window's start
        ev(d0, "while.1", 100, 300),        # encloses its body's ops
        ev(d0, "dot.3", 150, 100),
        ev(d0, "fusion.7", 250, 100),       # the step's fusion.7
        ev(d0, "copy.1", 400, 50),
        ev(d0, "xor.1", 600, 20),           # the feed runs
        ev(d0, "fusion.7", 620, 20),        # the feed's fusion.7
        ev(d0, "nop.9", 700, 10),           # in neither program
        ev(d0, "sqrt.1", 950, 100),         # crosses the window's end
        ev(d1, "while.1", 0, 200),
        ev(d1, "dot.3", 50, 100),
        ev(d1, "sqrt.1", 300, 50),
    ]
    red = btrace.Reduction(events)
    d0_ops = red.device_ops[d0]
    assert scopes.label_programs(d0_ops, step, feed) == [
        "step", "step", "step", "step", "step", "feed", "feed", None, "step"]
    ms = scopes.attribute(red, step, feed, steps=2)
    per = 1e-6 / 2 / 2                       # ns → ms, 2 chips, 2 steps
    assert ms["bus_unpack"] == pytest.approx(50 * per)
    assert ms["grad"] == pytest.approx((300 + 200) * per)   # not summed
    assert ms["step_metrics"] == pytest.approx((50 + 50) * per)
    assert ms["bus_pack"] == ms["edm_update_bus"] == 0.0
    assert ms["unscoped"] == pytest.approx(50 * per)
    assert ms["step"] == pytest.approx((50 + 300 + 50 + 50 + 200 + 50) * per)
    assert ms["feed"] == pytest.approx(40 * per)
    assert ms["other"] == pytest.approx(10 * per)
    assert ms["unscoped_top"] == [["copy", pytest.approx(50 * per)]]


def test_the_mix_is_found_from_the_counts():
    model = harness.Spec(SEQ256).config["model"]
    traffic = harness.Spec(SEQ256).traffic
    assert scopes.traffic_for(model, SEQ256_COUNTS) == traffic
    assert scopes.traffic_for(model, dict(SEQ256_COUNTS, seq_len=32)) is None
    assert scopes.traffic_for(cpu_run.TINY_LM, SEQ256_COUNTS) is None


def test_programs_compiled_from_shapes_are_the_run_programs():
    """The readers compile the step and the feed from shapes alone; their
    ops are those of the programs the run compiled from its arrays."""
    spec = cpu_run.spec_for(SEQ256, cpu_run.TINY_LM, cpu_run.TINY_TRAIN)
    devices = jax.devices()[:1]
    maps = scopes.program_maps(scopes.compiled_texts(
        spec.config["model"], spec.traffic, devices))
    assert maps["scopes"] == list(scopes.SCOPES)
    cell = harness.load_module(spec.driver_path).Cell(
        spec.config["model"], spec.traffic, devices)
    run = cell.start(2**31 + 5)
    batch = cell.feed(run["tables"], run["dkey"], 0)
    assert scopes.hlo_ops(cell.step.lower(run["state"], batch).compile()
                          .as_text()) == maps["step"]
    assert sorted(scopes.hlo_ops(cell.feed.lower(
        run["tables"], run["dkey"], 0).compile().as_text())) == maps["feed"]


def test_readers_report_nothing_without_a_device_trace():
    reading = type("R", (), {"trace": None, "counts": {}})
    for s in scopes.SCOPES:
        reader = harness.load_module(harness.BENCH / "metrics" / f"{s}_ms.py")
        assert reader.read(reading) is None


# ---------------------------------------------------------------------------
# the trace recorded before the scopes: every earlier number stands
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def parent_trace():
    return btrace.Reduction(btrace.read_events(PARENT_TRACE))


def test_reduction_reads_what_it_read_before_the_scopes(parent_trace):
    red = parent_trace
    assert red.window_s() == pytest.approx(0.34385129400000003, rel=1e-12)
    assert red.busy_s() == pytest.approx(0.307275923, rel=1e-12)
    assert red.kernel("edm_update") == (2, pytest.approx(
        0.06891573000000001, rel=1e-12))
    assert red.kernel("gossip_axpy") == (2, pytest.approx(
        0.029701414000000002, rel=1e-12))
    assert red.exposed_collective_s() is None
    gaps = red.idle_gaps()
    assert len(gaps) == 336
    assert gaps[:2] == [("bench.step_dispatch", pytest.approx(
        0.034571673000000004, rel=1e-12)), ("bench.drain", pytest.approx(
            0.001990355, rel=1e-12))]
    top = [("edm_update", 0.06891573000000001),
           ("gossip_axpy", 0.029701414000000002),
           ("fusion", 0.027995891000000002),
           ("reshape", 0.027453366000000003),
           ("multiply_reduce_fusion", 0.025942394),
           ("while", 0.025418184), ("copy", 0.021704774000000003),
           ("constant_dynamic-update-slice_fusion", 0.019701719000000003),
           ("broadcast", 0.017084615), ("reduce_sum", 0.014775808000000001)]
    assert red.breakdown()["device_ops"] == [
        [k, pytest.approx(v, rel=1e-12)] for k, v in top]


@pytest.mark.parametrize("metric,value", [
    ("train_mfu", 3.3528138812061834),
    ("edm_update_roofline", 81.16084189584389),
    ("gossip_axpy_roofline", 80.7069608585927),
    ("device_idle_pct.train", 10.636973493547487),
])
def test_earlier_readers_read_what_they_read_before(parent_trace, metric,
                                                    value):
    spec = harness.Spec(SEQ256)
    reading = type("R", (), {"model": spec.config["model"], "chips": 1,
                             "peaks": harness.lookup_peaks("TPU v5 lite"),
                             "counts": SEQ256_COUNTS,
                             "trace": parent_trace})
    reader = harness.load_module(harness.BENCH / "metrics" / f"{metric}.py")
    assert reader.read(reading) == pytest.approx(value, rel=1e-12)


# ---------------------------------------------------------------------------
# the scoped trace recorded on the chip
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def scoped_trace():
    from bench.record_scopes import ops_path
    maps = scopes.read_maps(ops_path(SCOPED_TRACE))
    red = btrace.Reduction(btrace.read_events(SCOPED_TRACE))
    return red, maps, scopes.attribute(red, maps["step"], maps["feed"],
                                       maps["steps"])


def test_scopes_and_unscoped_make_up_the_step(scoped_trace):
    red, maps, ms = scoped_trace
    assert maps["scopes"] == list(scopes.SCOPES)
    assert all(ms[s] > 0 for s in scopes.SCOPES)
    parts = sum(ms[s] for s in scopes.SCOPES) + ms["unscoped"]
    assert parts == pytest.approx(ms["step"], rel=0.02)
    # the programs' ops account for the chip's whole busy time
    busy_ms = red.busy_s() * 1e3 / maps["steps"]
    assert ms["other"] < 0.01 * busy_ms
    assert ms["step"] + ms["feed"] + ms["other"] == pytest.approx(
        busy_ms, rel=0.02)
