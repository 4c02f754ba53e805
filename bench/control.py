#!/usr/bin/env python3
"""Read the two ends of each correctness limit of a cell, on the chip at
the cell's own size, in one process.

    python3 bench/control.py --workload smollm_360m.train.seq1024 \
        --seeds 12 --control-seeds 3 --out control.json

Training cells:

- lower readings: the program's first steps against the plain reference,
  on ``--seeds`` seeds;
- upper readings: the reference put in the program's place, computed with
  every matmul in float8 (e4m3), the precision below the configuration's
  bfloat16 (the control), and two faults of the timed path, planted in
  that reference: half of each row's positions left out of the loss, and
  no exchange between agents; on ``--control-seeds`` seeds.  A state left
  unchanged reads 1 on ``update_norm_gap`` by construction and needs no
  run.

Serving cells: the program serves the cell's own requests at its own
load, and every finished request is checked, as in a run (lower
reading); at the same served positions, the token that a float8 forward
pass over the same tokens puts first is checked the same way (the
control, in the program's place).

Prints each seed's readings and writes them all to ``--out``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import checks, harness  # noqa: E402

VARIANTS = {
    "control_fp8": {"matmul_dtype": "float8_e4m3fn"},
    "fault_half_batch": {"half_batch": True},
    "fault_no_gossip": {"gossip": False},
}


def readings_table(cell, seeds, control_seeds, log=print):
    import gc
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        run = cell.start(seed)
        prog = cell.first_steps(run)
        del run
        gc.collect()
        ref = cell.reference(seed)
        r = {"seed": seed, "kind": "program",
             **checks.train_readings(prog, ref)}
        log(json.dumps(r) + f"  ({time.perf_counter() - t0:.1f} s)")
        rows.append(r)
        if seed in control_seeds:
            for name, variant in VARIANTS.items():
                t0 = time.perf_counter()
                other = cell.reference(seed, **variant)
                r = {"seed": seed, "kind": name,
                     **checks.train_readings(other, ref)}
                log(json.dumps(r) + f"  ({time.perf_counter() - t0:.1f} s)")
                rows.append(r)
    return rows


def top_logit(params, m, reqs, completed) -> float:
    """The largest reference logit at the served positions of the longest
    of ``reqs``: served logits are bfloat16, so near-ties within its step
    there can put a token that is not the reference's best first."""
    import numpy as np
    from bench.reference import serve as serve_ref
    req = max(reqs, key=lambda r: len(r.tokens) + len(completed[r.rid]))
    seq, rows = serve_ref._positions(req, np.asarray(completed[req.rid]))
    logits = serve_ref.forward_logits(params, m, seq, rows)[:len(rows)]
    return float(logits.max())


def serve_readings(cell, seeds, control_seeds, log=print):
    import gc
    import numpy as np
    from bench.reference import serve as serve_ref
    from bench.weights import seed_key

    def reading(gaps):
        """The widest gap, and every gap above 0 (the positions whose
        token is not the reference's best), widest first."""
        return {"logit_gap": float(np.max(gaps, initial=0.0)),
                "positions": int(gaps.size),
                "off_best": sorted((round(float(g), 5)
                                    for g in gaps[gaps > 0]), reverse=True)}

    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        params = cell.weights(seed_key(seed, 1))
        eng = cell.engine(params)
        cell.warm(eng)
        reqs = cell.requests(seed)
        res = cell.serve(eng, reqs, deadline=max(r.arrival for r in reqs)
                         + cell.p["drain_seconds"])
        del eng
        gc.collect()
        done = [r for r in reqs if r.rid in res["completed"]]
        t1 = time.perf_counter()
        r = {"seed": seed, "kind": "program",
             "unserved": len(reqs) - len(done),
             **reading(serve_ref.served_gaps(params, cell.m, done,
                                             res["completed"])),
             "reference_s": time.perf_counter() - t1,
             "top_logit": top_logit(params, cell.m, done, res["completed"])}
        log(json.dumps(r) + f"  ({time.perf_counter() - t0:.1f} s)")
        rows.append(r)
        if seed in control_seeds:
            t1 = time.perf_counter()
            r = {"seed": seed, "kind": "control_fp8", "unserved": 0,
                 **reading(serve_ref.served_gaps(
                     params, cell.m, done, res["completed"],
                     "float8_e4m3fn"))}
            log(json.dumps(r) + f"  ({time.perf_counter() - t1:.1f} s)")
            rows.append(r)
        del params
        gc.collect()
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    spec = harness.Spec(args.workload)
    harness.enable_compile_cache()
    drv = harness.load_module(spec.driver_path)
    cell = drv.Cell(spec.config["model"], spec.traffic,
                    jax.devices()[:spec.chips])
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    controls = set(seeds[:args.control_seeds])
    if spec.kind == "open_loop":
        rows = serve_readings(cell, seeds, controls)
    else:
        rows = readings_table(cell, seeds, controls)
    summary = {}
    for kind in sorted({r["kind"] for r in rows}):
        sel = [r for r in rows if r["kind"] == kind]
        for key in spec.limits["limits"]:
            vals = [r[key] for r in sel]
            summary.setdefault(kind, {})[key] = (
                max(vals) if kind == "program" else min(vals))
    print("summary (program: largest; control and faults: smallest):")
    print(json.dumps(summary, indent=1))
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"workload": args.workload, "rows": rows,
                               "summary": summary}, indent=1))


if __name__ == "__main__":
    main()
