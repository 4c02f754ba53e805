#!/usr/bin/env python3
"""Record a short device trace of one cell on the chip, for the trace
reduction's tests (``bench/testdata/``) and for reading by hand.

    python3 bench/record_trace.py --workload smollm_360m.train.seq256 \
        --seed 5 --steps 2 --out trace_train_seq256.json.gz

Runs the cell's set-up and a traced window of ``--steps`` steps, writes the
normalised events (``bench.trace.save_events``) to ``--out``, and prints
the planes and lines of the trace and a few events of each with all their
statistics.
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "src"))

from bench import harness, trace as btrace  # noqa: E402


def describe(path) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        print(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:4]:
                print(f"    {ev.name!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={dict(ev.stats)}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    spec = harness.Spec(args.workload)
    harness.enable_compile_cache()
    ns = argparse.Namespace(seed=args.seed, seconds=1.0, trace=1)
    ctx = harness.Context(spec, ns, time.perf_counter(), jax.devices(),
                          harness.lookup_peaks(jax.devices()[0].device_kind))
    drv = harness.load_module(spec.driver_path)
    cell = drv.Cell(spec.config["model"], spec.traffic, ctx.devices)
    run = cell.start(args.seed)
    cell.first_steps(run)
    import shutil
    import tempfile
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(tmp)
    cell.window(run, steps=args.steps)
    jax.profiler.stop_trace()
    path = sorted(pathlib.Path(tmp).rglob("*.xplane.pb"))[-1]
    describe(path)
    events = btrace.load_xplane(path)
    shutil.rmtree(tmp, ignore_errors=True)
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    btrace.save_events(events, out)
    red = btrace.Reduction(events)
    print(f"events {len(events)} devices {red.n_devices} window_s "
          f"{red.window_s()} busy_s {red.busy_s()}")
    for k in ("edm_update", "gossip_axpy", "paged_attention",
              "paged_prefill"):
        print(f"kernel {k}: {red.kernel(k)}")
    print(f"exposed collective s: {red.exposed_collective_s()}")
    print(f"breakdown: {red.breakdown()}")


if __name__ == "__main__":
    main()
