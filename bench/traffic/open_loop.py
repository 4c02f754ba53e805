"""Driver of the ``open_loop`` traffic kind: serving requests that arrive on
a schedule, whether or not earlier ones have finished.

The mix's file gives the arrival rate, the prompt and output length
distributions (lognormal, clipped) and the engine's settings; the
configuration's file gives the model.  The system under test is the
program's continuous-batching engine (``ContinuousBatchingEngine``: paged
KV cache, chunked prefill fused into the decode dispatch, Pallas paged
kernels), driven by the benchmark's own open loop through
``try_admit`` and ``step``.

Every seed serves the same work: the mix gives the number of
``requests``, and their arrival times and (prompt, output) lengths are
drawn from its ``shape_seed``; the run's seed draws the token ids (and the
weights), which change no length and no time.  ``--seconds`` changes
nothing, so a tail has the same number of samples in every run.  A traced
run serves only the arrivals due in its first ``trace_seconds``.  The run
ends when the last request has finished, and no request is ever dropped.
Time to first token is measured from each request's due time, so a stall
delays every later request.  Decoding is greedy, and nothing stops early
(no EOS).

Set-up makes the weights on the device from the seed and warms the
engine's two dispatch shapes (decode-only, and decode plus one prefill
chunk) on a throwaway request.  Once the window has closed and memory has
been read, the engine is freed and every finished request is run through
the plain reference: the check is the widest gap by which a served
token's logit lies below the reference's best at its position.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import numpy as np

from bench import checks, trace as btrace
from bench.harness import CompileCounter, memory_peak
from bench.reference import serve as serve_ref
from bench.weights import make_weights, seed_key

WEIGHTS = 1


def lengths(rng, n: int, median: float, sigma: float, lo: int, hi: int):
    return np.clip(np.round(rng.lognormal(np.log(median), sigma, n)),
                   lo, hi).astype(np.int64)


def make_requests(seed: int, p: Dict, vocab: int, rate: float):
    """(arrival s, prompt ids, output budget) of the first ``p["requests"]``
    arrivals of the schedule, sorted by arrival.  The schedule scales with
    ``1 / rate`` and its lengths do not depend on it."""
    from repro.serve import Request
    shape = np.random.default_rng(int(p["shape_seed"]))
    n = int(p["requests"])
    # the stream holds one gap past the last arrival before the lengths,
    # so the schedule stays the one the cell's readings were taken on
    arrivals = np.cumsum(shape.exponential(1.0 / rate, n + 1))[:n]
    prompts = lengths(shape, n, *p["prompt"])
    outputs = lengths(shape, n, *p["output"])
    rng = np.random.default_rng(int(seed))
    return [Request(rid=i, tokens=rng.integers(
        0, vocab, int(prompts[i])).astype(np.int32),
        max_new=int(outputs[i]), arrival=float(arrivals[i]))
        for i in range(n)]


def pct(vals, q) -> float:
    return float(np.percentile(np.asarray(vals, np.float64), q))


class HostPauses:
    """The longest host pause of each kind while active, in seconds, with
    the window time it began at: an admission pass, an engine step (one
    dispatch with its token sync and page tables), a garbage collection.
    A stall in an untraced run is then named by the span it fell in."""

    def __init__(self, t0: float):
        self.t0, self.longest, self._gc = t0, {}, None

    def span(self, name: str, begin: float, end: float) -> None:
        if end - begin > self.longest.get(name, (0.0,))[0]:
            self.longest[name] = (end - begin, begin - self.t0)

    def _collect(self, phase, info) -> None:
        if phase == "start":
            self._gc = time.perf_counter()
        elif self._gc is not None:
            self.span(f"gc{info['generation']}", self._gc,
                      time.perf_counter())

    def __enter__(self):
        gc.callbacks.append(self._collect)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._collect)

    def ms(self) -> Dict:
        """{kind: [longest ms, began at window s]}."""
        return {k: [round(1e3 * d, 3), round(at, 3)]
                for k, (d, at) in sorted(self.longest.items())}


class Cell:
    """The program's serving engine for one configuration and mix."""

    def __init__(self, model_cfg: Dict, p: Dict, devices):
        from repro.configs.base import ModelConfig
        from repro.models import build_model
        from repro.serve import PagedCacheConfig
        self.m, self.p, self.devices = model_cfg, p, devices
        self.cfg = ModelConfig(**model_cfg)
        self.model = build_model(self.cfg)
        shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        self.weights = jax.jit(lambda k: make_weights(shapes, k))
        self.pcfg = PagedCacheConfig(
            page_size=int(p["page_size"]), num_pages=int(p["num_pages"]),
            max_slots=int(p["max_slots"]),
            max_context=int(p["max_context"]))

    def requests(self, seed: int):
        return make_requests(seed, self.p, self.cfg.vocab_size,
                             float(self.p["rate"]))

    def engine(self, params):
        from repro.serve import ContinuousBatchingEngine
        return ContinuousBatchingEngine(
            self.model, params, self.pcfg, attn_impl=self.p["attn_impl"],
            prefill_chunk=int(self.p["prefill_chunk"]),
            max_step_tokens=self.p.get("max_step_tokens"))

    def warm(self, eng) -> None:
        """Compile both dispatch shapes on a throwaway request, then reset
        the engine's serving state (its compiles and pools stay)."""
        from repro.serve import Request
        C = int(self.p["prefill_chunk"])
        eng.reset()
        eng._t0 = time.perf_counter()
        req = Request(rid=-1, tokens=np.zeros(C + 1, np.int32), max_new=3,
                      arrival=0.0)
        if not eng.try_admit(req):
            raise RuntimeError("the engine cannot admit a one-chunk request")
        while eng.live or eng._filling:
            eng.step()
        jax.block_until_ready(eng.pools)
        eng.reset()

    def serve(self, eng, reqs: List, *, deadline: float,
              log: bool = False) -> Dict:
        """The open loop: admit what is due, dispatch, until every request
        has finished.  Returns the per-request timings, the most KV pages
        held at once (admission reserves a request's pages for its whole
        context), and with ``log``
        each dispatch's decode rows and prefill chunk (for the kernels'
        byte counts)."""
        pending = list(reqs)
        eng.reset()
        finished, stamps, dispatches = set(), {}, []
        i = 0
        with jax.profiler.TraceAnnotation(btrace.WINDOW_SPAN):
            t0 = time.perf_counter()
            eng._t0 = t0
            late, pages = [], 0
            pauses = HostPauses(t0)
            with pauses:
                while i < len(pending) or eng.live or eng._filling:
                    now = time.perf_counter() - t0
                    if now > deadline:
                        break
                    with jax.profiler.TraceAnnotation("bench.admit"):
                        while i < len(pending) and pending[i].arrival <= now:
                            if not eng.try_admit(pending[i]):
                                break
                            late.append(now - pending[i].arrival)
                            i += 1
                    pages = max(pages, eng.alloc.pages_in_use)
                    pauses.span("admit", t0 + now, time.perf_counter())
                    if eng.live or eng._filling:
                        self._dispatch(eng, log, dispatches, stamps,
                                       finished, pauses)
                    elif i < len(pending):
                        with jax.profiler.TraceAnnotation(
                                "bench.wait_arrival"):
                            time.sleep(max(0.0, min(
                                1e-3, pending[i].arrival - now)))
            wall = time.perf_counter() - t0
        return {"wall": wall, "stamps": stamps, "late": late,
                "completed": dict(eng.completed),
                "queue_waits": list(eng.queue_waits), "steps": eng.steps,
                "dispatches": dispatches, "host_pauses_ms": pauses.ms(),
                "kv_pages_peak": pages}

    @staticmethod
    def _dispatch(eng, log, dispatches, stamps, finished, pauses) -> None:
        """One engine step, with each token it emitted stamped."""
        if log:
            dispatches.append(dispatch_work(eng))
        begin = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            before = {s.req.rid: len(s.emitted) for s in eng.live.values()}
            eng.step()
        end = time.perf_counter()
        pauses.span("engine_step", begin, end)
        t = end - pauses.t0
        for s in eng.live.values():
            if len(s.emitted) != before.get(s.req.rid, 0):
                stamps.setdefault(s.req.rid, []).append(t)
        for rid, toks in eng.completed.items():
            if rid not in finished:
                finished.add(rid)
                got = stamps.setdefault(rid, [])
                if len(got) < len(toks):
                    got.append(t)


def dispatch_work(eng) -> Dict:
    """What the next dispatch computes: the kv length of every decoding
    slot, and the prefill chunk's start and length (or none)."""
    a = eng.alloc
    decoding = a.active & ~a.prefilling
    kv = (a.lengths[decoding] + 1).astype(int).tolist()
    work = eng._next_chunk()
    chunk = (0, 0) if work is None else (int(work[1]), int(work[2]))
    return {"kv": kv, "chunk": chunk}


def summarize(reqs, res) -> Dict:
    """End-to-end numbers of one served window.  A request that got no
    token by the end of the run counts with its wait until then, a bound
    its time to first token exceeds."""
    ttft = []
    gaps = []
    out_tokens = 0
    for r in reqs:
        st = res["stamps"].get(r.rid)
        if r.rid not in res["completed"] or not st:
            ttft.append(res["wall"] - r.arrival)
            continue
        ttft.append(st[0] - r.arrival)
        gaps.extend(np.diff(st).tolist())
        out_tokens += len(res["completed"][r.rid])
    return {"ttft": ttft, "gaps": gaps, "out_tokens": out_tokens}


def end_to_end(s: Dict, wall: float) -> Dict:
    """The cell's end-to-end metrics from :func:`summarize`'s lists: time
    to first token at the 75th percentile of every request (of 40, the
    highest percentile with ten beyond it), the gaps between tokens at the
    95th, and output tokens over the window."""
    return {
        "serve_ttft_p75_ms": 1e3 * pct(s["ttft"], 75),
        "serve_itl_p95_ms": 1e3 * pct(s["gaps"], 95) if s["gaps"] else 1e300,
        "serve_tokens_per_s": s["out_tokens"] / wall,
    }


def run(ctx) -> Dict:
    spec = ctx.spec
    p = spec.traffic
    phases = {"imports": time.perf_counter() - ctx.t_start}
    cell = Cell(spec.config["model"], p, ctx.devices)
    params = jax.block_until_ready(cell.weights(seed_key(ctx.seed, WEIGHTS)))
    phases["weights"] = time.perf_counter() - ctx.t_start
    eng = cell.engine(params)
    cell.warm(eng)
    phases["engine_and_warm_up"] = time.perf_counter() - ctx.t_start
    reqs = cell.requests(ctx.seed)
    if ctx.trace:
        # a traced run serves the arrivals of the first trace_seconds: the
        # profiler's buffer and the reduction stay small
        reqs = [r for r in reqs if r.arrival < float(p["trace_seconds"])]
    ctx.setup_done()
    out: Dict = {"trace": None, "e2e": {}}
    deadline = (max((r.arrival for r in reqs), default=0.0)
                + float(p["drain_seconds"]))
    with CompileCounter() as compiles:
        if ctx.trace:
            box = {}
            events = btrace.capture(lambda: box.update(
                r=cell.serve(eng, reqs, deadline=deadline, log=True)))
            res = box["r"]
            out["trace"] = btrace.Reduction(events)
        else:
            res = cell.serve(eng, reqs, deadline=deadline)
    s = summarize(reqs, res)
    served = [r for r in reqs if r.rid in res["completed"]]
    out["e2e"] = end_to_end(s, res["wall"])
    out["counts"] = {"queue_waits_s": res["queue_waits"],
                     "dispatches": res["dispatches"],
                     "n_layers": cell.cfg.n_layers,
                     "prompt_tokens": sum(len(r.tokens) for r in reqs),
                     "output_tokens": s["out_tokens"],
                     "requests": len(reqs), "steps": res["steps"],
                     "wall_s": res["wall"]}
    out["memory_peak_bytes"] = memory_peak(ctx.devices)
    out["attempted"] = len(reqs)
    out["failed"] = len(reqs) - len(served)
    out["info"] = {
        "setup_s": ctx.setup_s, "requests": len(reqs),
        "served": len(served), "dispatches": res["steps"],
        "prompt_tokens": out["counts"]["prompt_tokens"],
        "output_tokens": s["out_tokens"], "gaps": len(s["gaps"]),
        "compiles_in_window": compiles.n,
        "wall_s": res["wall"],
        "ttft_p50_ms": 1e3 * pct(s["ttft"], 50),
        "itl_p50_ms": 1e3 * pct(s["gaps"], 50) if s["gaps"] else None,
        "admitted_late_p99_ms": 1e3 * pct(res["late"], 99)
        if res["late"] else None,
        "host_pauses_ms": res["host_pauses_ms"],
        "kv_pages_peak": res["kv_pages_peak"],
        "kv_pages": cell.pcfg.num_pages,
        "peak_bytes_in_use": out["memory_peak_bytes"],
        "setup_phases_s": phases}
    del eng
    gc.collect()
    t0 = time.perf_counter()
    gap = serve_ref.widest_gap(params, spec.config["model"], served,
                               res["completed"])
    out["checks"] = checks.with_limits(
        {"logit_gap": gap, "unserved": len(reqs) - len(served)}, spec.limits)
    out["info"]["reference_s"] = time.perf_counter() - t0
    return out
