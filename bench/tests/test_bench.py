"""Tests of the benchmark harness itself, on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

They load no TPU library: the trace reduction runs on a trace recorded on
the chip and checked in (``bench/testdata/``), the counts are checked
against hand counts, and the driver runs at a tiny size past the harness's
look for a chip (``cpu_run``).
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

import cpu_run
from bench import checks, harness, model_math, trace as btrace

ROOT = cpu_run.ROOT
TESTDATA = ROOT / "bench" / "testdata"
TRAIN_TRACE = TESTDATA / "trace_train_seq256.json.gz"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TRAIN_CELL = "smollm_360m.train.seq1024"


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())["model"]


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def ev(plane, name, start, dur):
    return btrace.Event(plane, "XLA Ops" if plane.startswith("/device")
                        else "python", name, float(start), float(dur))


def test_reduction_on_hand_made_events():
    d0, d1, host = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
    events = [
        ev(host, btrace.WINDOW_SPAN, 0, 100),
        ev(host, "bench.wait", 40, 30),
        ev(d0, "fusion.1", -10, 20),            # clipped to [0, 10)
        ev(d0, "edm_update", 5, 15),            # overlaps: union [0, 20)
        ev(d0, "collective-permute-done.3", 30, 20),
        ev(d0, "fusion.2", 45, 15),             # exposed coll: [30,45)
        ev(d1, "gossip_axpy", 0, 50),
        ev(d1, "edm_update", 60, 20),
    ]
    red = btrace.Reduction(events)
    assert red.window_s() == pytest.approx(100e-9)
    # d0 busy: [0,20) ∪ [30,60) = 50; d1: 50 + 20 = 70; mean 60
    assert red.busy_s() == pytest.approx(60e-9)
    assert red.kernel("edm_update") == (2, pytest.approx(35e-9))
    assert red.kernel("gossip_axpy") == (1, pytest.approx(50e-9))
    assert red.kernel("edm") == (0, 0.0)
    # d0: collective [30,50) minus compute [45,60) → 15; d1 none; mean
    assert red.exposed_collective_s() == pytest.approx(7.5e-9)
    gaps = red.idle_gaps()
    # d0 idle [20,30) and [60,100); d1 [50,60) and [80,100)
    assert sorted(g[1] for g in gaps) == pytest.approx(
        sorted([10e-9, 40e-9, 10e-9, 20e-9]))
    assert ("bench.wait", pytest.approx(10e-9)) in gaps   # d1 [50,60)


def brute_union_ns(intervals, lo, hi):
    """Busy nanoseconds by sorting every edge: independent of union()."""
    edges = sorted({lo, hi, *[max(lo, min(hi, x)) for s, e in intervals
                               for x in (s, e)]})
    total = 0.0
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for s, e in intervals):
            total += b - a
    return total


@pytest.fixture(scope="module")
def recorded():
    if not TRAIN_TRACE.is_file():
        pytest.fail(f"missing recorded trace {TRAIN_TRACE}")
    return btrace.read_events(TRAIN_TRACE)


def test_recorded_trace_busy_is_the_union(recorded):
    red = btrace.Reduction(recorded)
    assert red.n_devices == 1
    (ops,) = red.device_ops.values()
    want = brute_union_ns([(e.start_ns, e.end_ns) for e in ops],
                          red.lo, red.hi)
    assert red.busy_s() == pytest.approx(want * 1e-9, rel=1e-12)
    assert 0 < red.busy_s() <= red.window_s()


def test_recorded_trace_kernel_time_by_name(recorded):
    red = btrace.Reduction(recorded)
    for kernel in ("edm_update", "gossip_axpy"):
        calls, secs = red.kernel(kernel)
        hits = [e for e in recorded if btrace.DEVICE_PLANE.match(e.plane)
                and red.lo < e.end_ns and e.start_ns < red.hi
                and e.name.split(".")[0] == kernel]
        assert calls == 2 == len(hits), kernel     # one call per step
        assert secs == pytest.approx(sum(e.dur_ns for e in hits) * 1e-9)


def test_recorded_one_chip_trace_has_no_collective(recorded):
    red = btrace.Reduction(recorded)
    assert red.exposed_collective_s() is None


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def test_counts_at_smollm_widths():
    m = config("smollm_360m")
    # per layer: q 960·960, k/v 2·960·320, o 960·960, SwiGLU 3·960·2560
    layer = 921600 + 614400 + 921600 + 7372800
    assert model_math.layer_matmul_params(m) == layer == 9830400
    assert model_math.matmul_params(m) == 32 * layer + 960 * 49152
    # + 2 norms a layer, embedding, untied head, final norm
    assert model_math.param_count(m) == 32 * (layer + 1920) \
        + 2 * 49152 * 960 + 960 == 409_007_040
    # 6 · 361.76M + causal attention 12 · 32 · 960 · 512.5 at seq 1024
    assert model_math.train_flops_per_token(m, 1024) == pytest.approx(
        6 * 361_758_720 + 12 * 32 * 960 * 512.5)


def test_counts_at_starcoder2_widths():
    m = config("starcoder2_7b")
    assert m["n_layers"] == 16 and m["d_model"] == 4608
    # q 4608·4608, k/v 2·4608·512, o 4608·4608, GELU MLP 2·4608·18432
    layer = 21233664 + 4718592 + 21233664 + 169869312
    assert model_math.layer_matmul_params(m) == layer == 217_055_232
    assert model_math.matmul_params(m) == 16 * layer + 4608 * 49152
    # forward of 10 tokens whose queries see 100 keys in all:
    # 2 · params · 10 + 4 · 16 layers · 36 heads · 128 · 100
    assert model_math.fwd_flops(m, 10, 100) == pytest.approx(
        2 * model_math.matmul_params(m) * 10 + 4 * 16 * 36 * 128 * 100)


def reading(model, **counts):
    class R:
        pass
    r = R()
    r.model, r.counts = model, counts
    return r


def test_kernel_byte_counts():
    m = config("smollm_360m")
    edm = harness.load_module(ROOT / "bench/metrics/edm_update_roofline.py")
    axpy = harness.load_module(ROOT / "bench/metrics/gossip_axpy_roofline.py")
    P = 409_007_040
    # two agents on a chip: x, g, m, ψ read and m', ψ', φ written, f32
    assert edm.cost(reading(m, agents_per_device=2)) == (7 * 4 * 2 * P,
                                                         7 * 2 * P)
    # ring of two: two operands read, one written
    assert axpy.cost(reading(m, agents_per_device=2, gossip_terms=2)) == (
        3 * 4 * 2 * P, 4 * 2 * P)
    assert axpy.cost(reading(m, agents_per_device=1, gossip_terms=3)) == (
        4 * 4 * P, 6 * P)


def test_paged_kernel_counts_at_starcoder2_widths():
    m = config("starcoder2_7b")
    metric = lambda n: harness.load_module(ROOT / f"bench/metrics/{n}.py")
    # one dispatch: two decoding slots at kv 100 and 50, and a 256-token
    # chunk whose slot already holds 256 tokens; 16 layers, K=4, H=36, hd 128
    r = reading(m, n_layers=16, dispatches=[{"kv": [100, 50],
                                             "chunk": (256, 256)}])
    # decode: bf16 keys and values of 150 positions, q in and out of 2 rows
    assert metric("paged_attention_roofline").cost(r) == (
        16 * (2 * 150 * 2 * 4 * 128 + 2 * 2 * 2 * 36 * 128),
        16 * 4 * 36 * 128 * 150)
    # prefill: 256 cached positions' k and v; the chunk's q, k, v and output
    pairs = 256 * 256 + 256 * 257 // 2
    assert metric("paged_prefill_roofline").cost(r) == (
        16 * (2 * 256 * 2 * 4 * 128 + 256 * (2 * 36 + 2 * 4) * 128 * 2),
        16 * 4 * 36 * 128 * pairs)
    assert metric("serve_mfu").flops(r) == pytest.approx(
        model_math.fwd_flops(m, 2 + 256, 150 + pairs))


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

def test_peaks_reject_an_unknown_device():
    assert harness.lookup_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.lookup_peaks("TPU v99")


def test_norm_gap_floors_at_the_median_leaf():
    ref = np.array([[1.0, 2.0, 3.0, 1e-9]])
    prog = np.array([[1.1, 2.0, 3.0, 2e-9]])
    # leaf 0: 0.1 / max(1, median 1.5); the near-zero leaf: 1e-9 / 1.5
    assert checks.norm_gap(prog, ref) == pytest.approx(0.1 / 1.5)


@pytest.fixture(scope="module")
def tiny_runs():
    plain = cpu_run.run_cell(TRAIN_CELL, 2**31 + 11, model=cpu_run.TINY_LM,
                             traffic=cpu_run.TINY_TRAIN)
    traced = cpu_run.run_cell(TRAIN_CELL, 2**31 + 11, model=cpu_run.TINY_LM,
                              traffic=cpu_run.TINY_TRAIN, trace=True)
    return plain, traced


def test_last_line_has_the_result_keys(tiny_runs):
    (plain, _), (traced, _) = tiny_runs
    assert set(plain) == RESULT_KEYS | {"checks"}
    assert list(plain)[-1] == "checks"
    assert set(traced) == RESULT_KEYS | {"checks", "breakdown"}
    assert set(plain["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for c in plain["checks"].values():
        assert set(c) == {"value", "limit"}
    json.loads(json.dumps(plain))
    assert plain["correct"] and traced["correct"]


def test_same_seed_same_first_steps(tiny_runs):
    (_, a), (_, b) = tiny_runs
    assert a["info"]["first_losses"] == b["info"]["first_losses"]


SERVE_CELL = "starcoder2_7b.serve.code"


def open_loop():
    return harness.load_module(ROOT / "bench/traffic/open_loop.py")


def shape(reqs):
    return [(r.arrival, len(r.tokens), r.max_new) for r in reqs]


@pytest.mark.parametrize("seed", [2**31 + 3, 2**33 + 5, 7])
def test_every_seed_serves_the_same_fixed_schedule(seed):
    p = json.loads((ROOT / "bench/traffic/serve_code.json").read_text())
    drv = open_loop()
    want = drv.make_requests(2**31 + 3, p, 49152, p["rate"])
    got = drv.make_requests(seed, p, 49152, p["rate"])
    assert shape(got) == shape(want)
    if seed != 2**31 + 3:
        assert any((r.tokens != s.tokens).any() for r, s in zip(got, want))
    assert all(p["prompt"][2] <= len(r.tokens) <= p["prompt"][3]
               for r in got)
    assert all(p["output"][2] <= r.max_new <= p["output"][3] for r in got)
    # the committed mix: 40 arrivals over 37.5 s at 0.95 req/s, 63,797
    # prompt tokens and 1,950 output tokens, the first 6 due in the traced
    # 8 s
    assert len(want) == p["requests"] == 40
    assert want[-1].arrival == pytest.approx(44.5328 * 0.8 / 0.95, abs=1e-4)
    assert sum(len(r.tokens) for r in want) == 63_797
    assert sum(r.max_new for r in want) == 1_950
    assert sum(r.arrival < p["trace_seconds"] for r in want) == 6
    # another rate brings the same requests closer together
    fast = drv.make_requests(seed, p, 49152, 2 * p["rate"])
    assert [r.arrival * 2 for r in fast] == pytest.approx(
        [r.arrival for r in want])
    assert [(len(r.tokens), r.max_new) for r in fast] == \
        [(len(r.tokens), r.max_new) for r in want]


def test_ttft_p75_counts_every_request_and_the_unserved():
    drv = open_loop()
    Req = type("Req", (), {})
    reqs = []
    for rid, arrival in enumerate([0.0, 1.0, 2.0, 3.0]):
        r = Req()
        r.rid, r.arrival = rid, arrival
        reqs.append(r)
    # request 3 got no token by the end of the run, at 10 s: it counts
    # with its wait until then, 7 s
    res = {"wall": 10.0, "completed": {0: [5, 6, 7], 1: [8, 9], 2: [1, 2]},
           "stamps": {0: [0.5, 0.6, 0.8], 1: [1.2, 1.5], 2: [4.0, 4.1]}}
    s = drv.summarize(reqs, res)
    assert s["ttft"] == pytest.approx([0.5, 0.2, 2.0, 7.0])
    assert s["gaps"] == pytest.approx([0.1, 0.2, 0.3, 0.1])
    e2e = drv.end_to_end(s, res["wall"])
    assert set(e2e) == {"serve_ttft_p75_ms", "serve_itl_p95_ms",
                        "serve_tokens_per_s"}
    # linear interpolation between the 3rd and 4th of 0.2, 0.5, 2.0, 7.0
    assert e2e["serve_ttft_p75_ms"] == pytest.approx(1e3 * (2.0 + 0.25 * 5))
    assert e2e["serve_itl_p95_ms"] == pytest.approx(1e3 * np.percentile(
        [0.1, 0.2, 0.3, 0.1], 95))
    assert e2e["serve_tokens_per_s"] == pytest.approx(7 / 10.0)


def test_the_serving_cell_reports_its_own_metrics():
    spec = harness.Spec(SERVE_CELL)
    assert [m["name"] for m in spec.e2e] == [
        "setup_s", "serve_ttft_p75_ms", "serve_itl_p95_ms",
        "serve_tokens_per_s"]
    assert {m["name"] for m in spec.per_layer} == {
        "serve_mfu", "paged_prefill_roofline", "paged_attention_roofline",
        "device_idle_pct.serve"}
    assert set(spec.limits["limits"]) == {"logit_gap", "unserved"}
    train = harness.Spec(TRAIN_CELL)
    assert not {m["name"] for m in train.e2e + train.per_layer} & {
        m["name"] for m in spec.e2e + spec.per_layer} - {"setup_s"}


@pytest.fixture(scope="module")
def tiny_serve():
    return cpu_run.run_cell(SERVE_CELL, 2**31 + 13, model=cpu_run.TINY_CODER,
                            traffic=cpu_run.TINY_CODE, seconds=0.2)


def test_a_tiny_serving_run_serves_every_request(tiny_serve):
    line, out = tiny_serve
    assert set(line) == RESULT_KEYS | {"checks"}
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"setup_s", "serve_ttft_p75_ms",
                                    "serve_itl_p95_ms", "serve_tokens_per_s"}
    assert line["attempted"] == cpu_run.TINY_CODE["requests"]
    assert line["failed"] == 0 and line["correct"], line["checks"]
    info = out["info"]
    assert info["compiles_in_window"] == 0
    assert info["gaps"] == info["output_tokens"] - info["requests"]
    assert {"admit", "engine_step"} <= set(info["host_pauses_ms"])


def copy_bench(tmp: pathlib.Path) -> pathlib.Path:
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    return tmp


def test_new_files_are_found_without_an_edit(tmp_path):
    root = copy_bench(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/smollm_360m.json").read_text())
    cfg["model"]["n_layers"] = 4
    (root / "bench/configs/tiny_lm.json").write_text(json.dumps(cfg))
    traffic = json.loads((root / "bench/traffic/train_seq256.json")
                         .read_text())
    (root / "bench/traffic/train_seq64.json").write_text(
        json.dumps(dict(traffic, seq_len=64)))
    shutil.copy(root / "bench/limits/smollm_360m.train.seq256.json",
                root / "bench/limits/tiny_lm.train.seq64.json")
    (root / "bench/metrics/steps_traced.py").write_text(
        "def read(reading):\n    return reading.counts['steps_traced']\n")
    bench["configs"].append({"name": "tiny_lm", "source": "x",
                             "file": "bench/configs/tiny_lm.json",
                             "reduced": ["n_layers"], "why": "x"})
    bench["workloads"].append({"name": "tiny_lm.train.seq64",
                               "config": "tiny_lm", "traffic": "train_seq64",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "steps_traced", "unit": "steps",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves":
                               "train_tokens_per_s",
                               "workloads": ["tiny_lm.train.seq64"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = harness.Spec("tiny_lm.train.seq64", root=root)
    assert spec.config["model"]["n_layers"] == 4
    assert spec.traffic["seq_len"] == 64 and spec.kind == "train_steps"
    assert spec.driver_path == root / "bench/traffic/train_steps.py"
    assert [m["name"] for m in spec.e2e] == ["setup_s"] or \
        "setup_s" in [m["name"] for m in spec.e2e]
    assert "steps_traced" in [m["name"] for m in spec.per_layer]
    reader = harness.load_module(spec.bench_path / "metrics/steps_traced.py")
    assert reader.read(type("R", (), {"counts": {"steps_traced": 3}})) == 3


def run_cli(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", TRAIN_CELL, "--seed",
         "3", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_run_exits_nonzero_without_a_tpu():
    r = run_cli(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert not any(l.startswith("{") for l in r.stdout.splitlines())
    assert "needs a TPU" in r.stderr


def test_run_exits_nonzero_with_the_benchmark_alone(tmp_path):
    root = copy_bench(tmp_path)
    r = run_cli(root, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert not any(l.startswith("{") for l in r.stdout.splitlines())
