"""The benchmark harness: one cell, one run.

It reads ``BENCHMARK.json``, finds the cell's files by name, refuses to run
without the chips the cell asks for, points JAX's persistent compilation
cache at one fixed directory of the checkout, hands the cell to its
driver (``bench/traffic/<kind>.py``), reads the per-layer metrics with
their readers (``bench/metrics/<name>.py``) and prints the result line.

A driver is a module with ``run(ctx) -> dict``.  It builds the system
under test, calls ``ctx.setup_done()`` when the first timed step or
request is due, and returns::

    {"e2e": {metric: value},            # --trace 0
     "counts": {...},                   # readings the metric readers use
     "trace": bench.trace.Reduction or None,   # --trace 1
     "checks": {name: {"value": v, "limit": l}},
     "attempted": n, "failed": n, "memory_peak_bytes": n,
     "info": {...}}                     # earlier lines, for people
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import pathlib
import sys
import time
from typing import Any, Dict, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


class BenchError(Exception):
    """A run that cannot produce a result: it exits non-zero."""


def load_json(path: pathlib.Path) -> Any:
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    return json.loads(path.read_text())


def load_module(path: pathlib.Path):
    """Import a harness file by path (metric names contain dots)."""
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_dyn_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def lookup_peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


class Spec:
    """Everything ``BENCHMARK.json`` says about one cell, with its files."""

    def __init__(self, workload: str, root: pathlib.Path = ROOT):
        bench = load_json(root / "BENCHMARK.json")
        cells = {c["name"]: c for c in bench["workloads"]}
        if workload not in cells:
            raise BenchError(f"unknown workload {workload!r} (known: "
                             f"{sorted(cells)})")
        self.bench = bench
        self.bench_path = bench_dir(root)
        self.cell = cells[workload]
        self.name = workload
        self.chips = int(self.cell["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.cell["config"]]
        self.config = load_json(root / self.config_entry["file"])
        self.traffic = load_json(bench_dir(root) / "traffic"
                                 / f"{self.cell['traffic']}.json")
        self.kind = self.traffic["kind"]
        self.driver_path = bench_dir(root) / "traffic" / f"{self.kind}.py"
        self.limits = load_json(bench_dir(root) / "limits"
                                / f"{workload}.json")
        self.e2e = [m for m in bench["end_to_end"] if self._has(m)]
        names = {m["name"] for m in self.e2e}
        self.per_layer = [m for m in bench["per_layer"]
                          if workload in m.get("workloads", ())
                          or ("workloads" not in m and m["moves"] in names)]

    def _has(self, metric) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]


def bench_dir(root: pathlib.Path) -> pathlib.Path:
    return root / "bench"


class Context:
    """What a driver gets: the cell, the run's arguments, the device."""

    def __init__(self, spec: Spec, args, t_start: float, devices, peaks):
        self.spec = spec
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.t_start = t_start
        self.devices = devices[:spec.chips]
        self.peaks = peaks
        self.setup_s: Optional[float] = None

    def setup_done(self) -> float:
        self.setup_s = time.perf_counter() - self.t_start
        return self.setup_s

    def info(self, **kv) -> None:
        """An earlier line, for people; the result line stays last."""
        print("bench: " + " ".join(f"{k}={_fmt(v)}" for k, v in kv.items()),
              flush=True)


def _fmt(v):
    return json.dumps(v) if isinstance(v, (dict, list, tuple)) else str(v)


class Reading:
    """What a per-layer metric reader gets."""

    def __init__(self, spec: Spec, ctx: Context, out: dict):
        self.model = spec.config["model"]
        self.chips = spec.chips
        self.peaks = ctx.peaks
        self.counts = out.get("counts", {})
        self.trace = out.get("trace")


class CompileCounter:
    """Counts backend compiles and persistent-cache loads while active."""

    _active: Optional["CompileCounter"] = None
    _registered = False

    def __init__(self):
        self.n = 0

    @classmethod
    def _listen(cls, event, *args, **kw):
        if cls._active is not None and (
                "backend_compile" in event or "cache_retrieval" in event):
            cls._active.n += 1

    def __enter__(self):
        import jax
        if not CompileCounter._registered:
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._listen)
            CompileCounter._registered = True
        CompileCounter._active = self
        return self

    def __exit__(self, *exc):
        CompileCounter._active = None


def memory_peak(devices) -> int:
    """``peak_bytes_in_use`` of the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def enable_compile_cache() -> str:
    """Persistent compilation cache at one fixed path inside the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one (JAX reads it itself).
    Every program is cached, however quick its compile, so a second run
    of a cell compiles nothing."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(spec: Spec, ctx: Context, out: dict, device: dict) -> dict:
    """The run's last line: correct, attempted, failed, metrics,
    device, [breakdown], and the compared numbers last."""
    checks = out["checks"]
    correct = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    metrics = {}
    if ctx.trace:
        reading = Reading(spec, ctx, out)
        for m in spec.per_layer:
            reader = load_module(spec.bench_path / "metrics"
                                 / f"{m['name']}.py")
            value = reader.read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = dict(out["e2e"], setup_s=ctx.setup_s)
        for m in spec.e2e:
            if m["name"] not in e2e:
                raise BenchError(f"driver {spec.kind} gave no "
                                 f"{m['name']} for {spec.name}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    line = {"correct": correct, "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "metrics": metrics,
            "device": device}
    if ctx.trace and out.get("trace") is not None:
        line["breakdown"] = out["trace"].breakdown()
    line["checks"] = checks
    return line


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    try:
        spec = Spec(args.workload)
        src = ROOT / "src"
        if not (src / "repro").is_dir():
            raise BenchError(f"no system under test at {src}")
        sys.path.insert(0, str(src))
        import jax
        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise BenchError(f"needs a TPU; JAX found platform "
                             f"{devices[0].platform!r}")
        if len(devices) < spec.chips:
            raise BenchError(f"{spec.name} needs {spec.chips} chips, JAX "
                             f"found {len(devices)}")
        peaks = lookup_peaks(devices[0].device_kind)
        ctx = Context(spec, args, t_start, devices, peaks)
        ctx.info(workload=spec.name, seed=ctx.seed, seconds=ctx.seconds,
                 trace=int(ctx.trace), device=devices[0].device_kind,
                 chips=spec.chips, compile_cache=enable_compile_cache())
        driver = load_module(spec.driver_path)
        out = driver.run(ctx)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": int(out["memory_peak_bytes"])}
        if ctx.trace:
            red = out["trace"]
            device["busy_s"] = red.busy_s()
            device["window_s"] = red.window_s()
        line = result_line(spec, ctx, out, device)
    except BenchError as e:
        print(f"bench: error: {e}", file=sys.stderr, flush=True)
        return 2
    for k, v in out.get("info", {}).items():
        ctx.info(**{k: v})
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
