#!/usr/bin/env python3
"""Chip smoke test: the main path end to end on TPU at smollm_360m's
published widths (32 layers, d_model 960, 15/5 heads, vocab 49152), random
weights from a fixed seed.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # the multi-chip gossip paths, four chips

One chip, through the same entry points as the CLIs:

1. device — the first device must be a TPU; there is no CPU fallback;
2. train — ``repro.launch.train`` with two agents blocked on the chip, the
   packed bus, the fused Pallas EDM update and gossip combine on a ring,
   seq 1024, 5 steps: finite losses, those kernels and the one-pass
   consensus metric (``bus_consensus``) compiled for Mosaic
   (``tpu_custom_call``), and losses within 1e-3 (relative) of the same
   run on the unfused jnp chain; the checkpoint is exported to consensus;
3. serve — ``repro.launch.serve`` loads that consensus into the
   continuous-batching engine, Pallas paged kernels and chunked prefill,
   8 requests: every request finishes, both paged kernels are compiled for
   Mosaic, and every first token equals the ``attn_impl="ref"`` run's.

Four chips: 4 agents one per chip (``ppermute`` + fused kernels, ring),
and 2 pod agents × 2 FSDP shards, 3 steps each, each against its unfused
run on the same chips, with every agent's bus shard on a distinct chip.

Earlier lines report compile seconds, step seconds after warm-up and peak
device bytes — bring-up observations, not benchmark results.  The last
line is ``{"ok": true, "device": {...}}``; any failed phase exits non-zero
before it.  Checkpoints go to ``.smoke/`` (git-ignored); compiled programs
to the persistent cache of :mod:`repro.launch.compile_cache`.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import re
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / ".smoke"
ARCH = ["--arch", "smollm_360m"]


def fail(msg: str):
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str):
    if not cond:
        fail(msg)


def kernels(hlo: str) -> set:
    """Names of the Mosaic kernels (``tpu_custom_call``) in compiled HLO;
    every Pallas kernel of this repo carries a stable ``name``."""
    return {m.group(1) for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(r'op_name="[^"]*?/(\w+)/pallas_call', line)]
            if m}


def peak_gb(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.2f} GB"


def train(argv, label):
    from repro.launch import train as train_cli
    print(f"== train [{label}]: {' '.join(argv)}", flush=True)
    t0 = time.perf_counter()
    res = train_cli.main(ARCH + argv)
    vals = res["losses"] + res["consensus"]
    check(all(math.isfinite(v) for v in vals),
          f"{label}: non-finite loss/consensus {vals}")
    warm = res["step_s"][1:] or res["step_s"]
    print(f"{label}: compile {res['compile_s']:.1f}s, step "
          f"{statistics.median(warm):.3f}s median after warm-up, phase "
          f"{time.perf_counter() - t0:.1f}s, losses {res['losses']}",
          flush=True)
    return res


def agree(fused, plain, label, rtol=1e-3):
    for t, (a, b) in enumerate(zip(fused["losses"], plain["losses"])):
        check(abs(a - b) <= rtol * abs(b),
              f"{label}: step {t} fused loss {a} vs unfused {b} "
              f"(rtol {rtol})")
    print(f"{label}: fused == unfused losses within {rtol} relative",
          flush=True)


def phase_train_one_chip(dev):
    OUT.mkdir(exist_ok=True)
    ckpt, cons = OUT / "train.npz", OUT / "consensus.npz"
    common = ["--agents", "2", "--agents-per-device", "2",
              "--gossip-engine", "ppermute", "--packed-bus",
              "--topology", "ring", "--seq", "1024",
              "--per-agent-batch", "1", "--steps", "5"]
    fused = train(common + ["--fused-kernel", "--ckpt", str(ckpt)], "fused")
    found = kernels(fused.pop("compiled").as_text())
    check({"edm_update", "gossip_axpy", "bus_consensus"} <= found,
          f"fused step lacks Mosaic kernels: found {sorted(found)}")
    print(f"fused step Mosaic kernels: {sorted(found)}", flush=True)
    del fused["state"]
    print(f"peak device memory after fused train: {peak_gb(dev)}",
          flush=True)
    plain = train(common, "unfused")
    del plain["state"]
    agree(fused, plain, "train 1 chip")

    from repro.train import checkpoint
    t0 = time.perf_counter()
    checkpoint.export_consensus(str(ckpt), str(cons))
    print(f"consensus -> {cons} ({time.perf_counter() - t0:.1f}s)",
          flush=True)
    return cons


def serve(cons, impl):
    from repro.launch import serve as serve_cli
    argv = ARCH + ["--ckpt", str(cons), "--continuous-batching",
                   "--attn-impl", impl, "--prefill-chunk", "16",
                   "--requests", "8", "--max-slots", "8", "--page-size", "16",
                   "--prompt-dist", "exact"]
    print(f"== serve [{impl}]: {' '.join(argv)}", flush=True)
    t0 = time.perf_counter()
    res = serve_cli.main(argv)
    eng = res["engine"]
    check(res["metrics"]["requests"] == 8 and len(eng.completed) == 8,
          f"serve {impl}: {len(eng.completed)} of 8 requests finished")
    print(f"serve {impl}: phase {time.perf_counter() - t0:.1f}s (compiles "
          f"included)", flush=True)
    return eng


def phase_serve(dev, cons):
    eng = serve(cons, "pallas")
    found = kernels(eng.compiled_step_text())
    check({"paged_attention", "paged_prefill"} <= found,
          f"serving step lacks Mosaic paged kernels: found {sorted(found)}")
    print(f"serving step Mosaic kernels: {sorted(found)}", flush=True)
    ref = serve(cons, "ref")
    for rid, toks in sorted(eng.completed.items()):
        check(int(toks[0]) == int(ref.completed[rid][0]),
              f"request {rid}: first token {int(toks[0])} (pallas) vs "
              f"{int(ref.completed[rid][0])} (ref)")
    print(f"serve: first tokens of all 8 requests match ref "
          f"{[int(ref.completed[r][0]) for r in sorted(ref.completed)]}",
          flush=True)
    print(f"peak device memory after serve: {peak_gb(dev)}", flush=True)


def distinct_shards(state, label):
    shards = state["params"].addressable_shards
    devices = {s.device for s in shards}
    check(len(shards) == 4 and len(devices) == 4,
          f"{label}: bus shards on {sorted(d.id for d in devices)}, "
          f"expected 4 distinct chips")
    print(f"{label}: bus shards {[tuple(s.data.shape) for s in shards]} on "
          f"devices {sorted(d.id for d in devices)}", flush=True)


def phase_four_chips():
    runs = {
        "data x4": ["--agents", "4", "--gossip-engine", "ppermute",
                    "--packed-bus", "--topology", "ring"],
        "pod 2x2": ["--agents", "pod", "--pods", "2", "--shards", "2",
                    "--gossip-engine", "ppermute", "--packed-bus",
                    "--topology", "ring"],
    }
    for label, argv in runs.items():
        argv = argv + ["--seq", "1024", "--per-agent-batch", "1",
                       "--steps", "3"]
        fused = train(argv + ["--fused-kernel"], f"{label} fused")
        found = kernels(fused.pop("compiled").as_text())
        check({"edm_update", "gossip_axpy"} <= found,
              f"{label}: fused step lacks Mosaic kernels: {sorted(found)}")
        print(f"{label}: Mosaic kernels {sorted(found)}", flush=True)
        distinct_shards(fused.pop("state"), f"{label} fused")
        plain = train(argv, f"{label} unfused")
        distinct_shards(plain.pop("state"), f"{label} unfused")
        agree(fused, plain, label)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + serve on one chip; 4: the multi-chip "
                         "gossip paths only")
    args = ap.parse_args()
    src = ROOT / "src"
    check((src / "repro").is_dir(),
          f"no repro package at {src}: run from a checkout of the repo")
    sys.path.insert(0, str(src))

    import jax
    devs = jax.devices()
    dev = devs[0]
    check(dev.platform == "tpu",
          f"needs a TPU; JAX found platform {dev.platform!r} "
          f"({dev.device_kind}, {len(devs)} device(s))")
    check(len(devs) >= args.chips,
          f"--chips {args.chips} needs {args.chips} chips, found {len(devs)}")
    print(f"device: {dev.device_kind} x{len(devs)}", flush=True)

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    if args.chips == 4:
        phase_four_chips()
    else:
        cons = phase_train_one_chip(dev)
        phase_serve(dev, cons)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
