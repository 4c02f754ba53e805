"""The consensus kernel's roofline reader (``bench/metrics/
bus_consensus_roofline.py``) on hand-made trace events, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
from __future__ import annotations

import json

import pytest

import cpu_run
from bench import harness, trace as btrace

ROOT = cpu_run.ROOT
P = 409_007_040                      # smollm_360m parameters
DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, name, start, dur):
    return btrace.Event(plane, "XLA Ops" if plane.startswith("/device")
                        else "python", name, float(start), float(dur))


class Reading:
    def __init__(self, events):
        self.model = json.loads((ROOT / "bench" / "configs"
                                 / "smollm_360m.json").read_text())["model"]
        self.counts = {"agents_per_device": 2}
        self.peaks = harness.lookup_peaks("TPU v5 lite")
        self.trace = None if events is None else btrace.Reduction(events)


@pytest.fixture(scope="module")
def reader():
    return harness.load_module(ROOT / "bench/metrics/bus_consensus_roofline.py")


def test_bytes_are_one_read_of_the_chips_bus(reader):
    # two agents on a chip: the f32 bus read once, four FLOPs a parameter
    assert reader.cost(Reading(None)) == (4 * 2 * P, 4 * 2 * P)


def test_share_is_least_time_over_kernel_time(reader):
    window = ev(HOST, btrace.WINDOW_SPAN, 0, 20e6)
    r = Reading([window,
                 ev(DEV, "edm_update.1", 0, 4e6),
                 ev(DEV, "bus_consensus.1", 5e6, 4e6),
                 ev(DEV, "bus_consensus.1", 12e6, 6e6)])
    # two calls, 10 ms in all; each needs 8·P bytes at 819 GB/s
    least = 4 * 2 * P / 819e9
    assert reader.read(r) == pytest.approx(100 * 2 * least / 10e-3)


def test_nothing_is_read_where_the_kernel_did_not_run(reader):
    # the XLA expression's ops, as the step without the kernel runs them
    r = Reading([ev(HOST, btrace.WINDOW_SPAN, 0, 20e6),
                 ev(DEV, "edm_update.1", 0, 4e6),
                 ev(DEV, "reduce_sum.212", 5e6, 7e6),
                 ev(DEV, "multiply_reduce_fusion", 12e6, 8e6)])
    assert reader.read(r) is None
    assert reader.read(Reading(None)) is None
