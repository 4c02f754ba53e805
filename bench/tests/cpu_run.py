"""Drive a cell end to end on the CPU at a tiny size, past the harness's
look for a chip: the tests' way to run everything but the device check.

Kernels run in interpret mode there, so nothing here is a speed."""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

TINY_LM = {"n_layers": 2, "d_model": 128, "n_heads": 2, "n_kv_heads": 1,
           "d_ff": 256, "vocab_size": 512}
TINY_TRAIN = {"seq_len": 32, "trace_steps": 2}
TINY_CODER = {"n_layers": 2, "d_model": 128, "n_heads": 2, "n_kv_heads": 1,
              "head_dim": 64, "d_ff": 256, "vocab_size": 512}
TINY_CODE = {"rate": 40.0, "requests": 12, "prompt": [40, 0.5, 8, 96],
             "output": [6, 0.5, 2, 12], "prefill_chunk": 16,
             "page_size": 16, "max_slots": 8, "max_context": 128,
             "num_pages": 80, "drain_seconds": 30, "trace_seconds": 0.1}


def spec_for(workload: str, model=None, traffic=None,
             root: pathlib.Path = ROOT) -> harness.Spec:
    spec = harness.Spec(workload, root=root)
    if model:
        spec.config = dict(spec.config, model=dict(spec.config["model"],
                                                   **model))
    if traffic:
        spec.traffic = dict(spec.traffic, **traffic)
    return spec


def run_cell(workload: str, seed: int, *, model=None, traffic=None,
             trace: bool = False, seconds: float = 0.5,
             root: pathlib.Path = ROOT):
    """The result line of one run of ``workload`` on the CPU."""
    import jax
    spec = spec_for(workload, model, traffic, root)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=int(trace))
    devices = jax.devices()
    ctx = harness.Context(spec, args, time.perf_counter(), devices,
                          harness.lookup_peaks("TPU v5 lite"))
    out = harness.load_module(spec.driver_path).run(ctx)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(out["memory_peak_bytes"])}
    return harness.result_line(spec, ctx, out, device), out
