#!/usr/bin/env python3
"""Sweep the offered rate of a serving cell on the chip, to find the knee:
the highest rate the engine sustains without a growing backlog.

    python3 bench/sweep.py --workload starcoder2_7b.serve.code \
        --rates 0.8,1.0,1.2,1.4

One process, one warmed engine, the cell's own mix at each rate (the
same requests at every rate, closer together).  For each rate it prints the requests, the tails of
time to first token and of the gaps between tokens, the output tokens per
second, and for time to first token and for how late admission ran, the
median of the last quarter of the requests over the first quarter's.
Where these climb from one rate to the next, the queue grew all through
the window; at the lowest rate the schedule alone sets them.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402
from bench.weights import seed_key  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=4_000_000_001)
    ap.add_argument("--set", action="append", default=[],
                    help="key=value: an engine setting of the mix to try "
                         "instead of the file's (JSON value)")
    args = ap.parse_args()
    import jax
    spec = harness.Spec(args.workload)
    for kv in args.set:
        key, _, value = kv.partition("=")
        spec.traffic[key] = json.loads(value)
    harness.enable_compile_cache()
    drv = harness.load_module(spec.driver_path)
    cell = drv.Cell(spec.config["model"], spec.traffic,
                    jax.devices()[:spec.chips])
    eng = cell.engine(cell.weights(seed_key(args.seed, 1)))
    cell.warm(eng)
    for rate in [float(r) for r in args.rates.split(",")]:
        reqs = drv.make_requests(args.seed, spec.traffic, cell.cfg.vocab_size,
                                 rate)
        res = cell.serve(eng, reqs, deadline=max(r.arrival for r in reqs)
                         + spec.traffic["drain_seconds"])
        s = drv.summarize(reqs, res)
        ttft, late = np.asarray(s["ttft"]), np.asarray(res["late"])
        q = max(len(ttft) // 4, 1)
        row = {"rate": rate, "settings": args.set, "requests": len(reqs),
               "served": len(res["completed"]),
               "ttft_p50_ms": 1e3 * drv.pct(ttft, 50),
               "ttft_p95_ms": 1e3 * drv.pct(ttft, 95),
               "itl_p50_ms": 1e3 * drv.pct(s["gaps"], 50),
               "itl_p95_ms": 1e3 * drv.pct(s["gaps"], 95),
               "tokens_per_s": s["out_tokens"] / res["wall"],
               "wall_s": res["wall"], "dispatches": res["steps"],
               "backlog": float(np.median(ttft[-q:]) / np.median(ttft[:q])),
               "late_first_ms": 1e3 * float(np.median(late[:q])),
               "late_last_ms": 1e3 * float(np.median(late[-q:])),
               "host_pauses_ms": res["host_pauses_ms"]}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
