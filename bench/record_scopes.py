#!/usr/bin/env python3
"""Record a device trace of one training cell on the chip together with
what the scope attribution (``bench/scopes.py``) needs, and print the
attribution, for the attribution's tests (``bench/testdata/``) and for
reading a scoped trace by hand.

    python3 bench/record_scopes.py --workload smollm_360m.train.seq256 \
        --seed 5 --steps 2 --out trace_train_seq256_scoped.json.gz

Runs ``bench/record_trace.py`` with the same arguments (it writes the
normalised events to ``--out`` and prints the trace's planes and lines,
and a few events of each with all their statistics), then compiles the
cell's step and feed as the per-layer readers do and writes their ops and
scopes beside the events (``*.ops.json.gz``), then prints each scope's
milliseconds a step, the unscoped ops and the feed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "src"))

from bench import harness, record_trace, scopes, trace as btrace  # noqa: E402


def ops_path(events_path) -> pathlib.Path:
    p = pathlib.Path(events_path)
    return p.with_name(p.name.replace(".json.gz", "") + ".ops.json.gz")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    record_trace.main()
    import jax
    spec = harness.Spec(args.workload)
    t0 = time.perf_counter()
    maps = scopes.program_maps(scopes.compiled_texts(
        spec.config["model"], spec.traffic, jax.devices()[:spec.chips]))
    maps["steps"] = args.steps
    scopes.save_maps(maps, ops_path(args.out))
    red = btrace.Reduction(btrace.read_events(args.out))
    ms = scopes.attribute(red, maps[scopes.STEP], maps[scopes.FEED],
                          args.steps)
    print(f"scopes found: {maps['scopes']} (compiled in "
          f"{time.perf_counter() - t0:.1f} s)")
    print("ms a step: " + json.dumps(ms))
    scoped = sum(ms[s] for s in scopes.SCOPES) + ms["unscoped"]
    print(f"scopes + unscoped {scoped:.3f} ms of the step's busy "
          f"{ms[scopes.STEP]:.3f} ms")


if __name__ == "__main__":
    main()
