"""Continuous-batching decode scheduler over the paged KV cache (DESIGN §10).

The seed serving path is batch-synchronous: every request in a batch decodes
until the LAST one finishes (head-of-line blocking) and pays KV for the
longest context (padding).  Here the decode batch is a set of **slots**:
each jitted ``serve_step`` decodes every live slot in ONE dispatch, finished
requests release their pages immediately, and arrivals are admitted the
moment a slot + pages are free — so throughput tracks the *mean* request
length, not the max.

Division of labor:

* device — ``build_paged_serve_step``: embed → paged-attention block scan →
  greedy head, for the whole slot batch, jitted once (shapes are static:
  ``max_slots`` slots, fixed page-table width);
* host — :class:`ContinuousBatchingEngine`: page allocator bookkeeping,
  per-request prefill + page scatter on admit, EOS/max-token eviction, and
  the arrival loop.  Per step it ships two small int32 tables (page table,
  kv lengths) and syncs one (B, 1) token array — no cache movement.

Two prefill paths (DESIGN §11):

* **legacy per-request** (``prefill_chunk=None``): prefill runs per request
  at its EXACT prompt length — a compile per distinct length, bounded by a
  size-capped LRU of per-length jit instances (``prefill_cache_cap``) and
  by the load generator's bucketed prompt draws.  Right-padding prompts
  instead would corrupt the ring-cache layout (row = position mod window)
  and the last-position prefill logits.  Every live decode slot stalls
  while a prefill runs — the head-of-line cost the bench measures.
* **chunked** (``prefill_chunk=C``): prompts are split into fixed-size
  C-token chunks (last chunk padded, ``chunk_len`` masked) and ONE mixed
  jitted step advances every live decode slot AND at most one chunk per
  dispatch, under a per-step token budget (``max_step_tokens``).  All
  shapes are static, so the whole serving trace needs exactly TWO compiles
  (mixed + decode-only) independent of the prompt-length distribution —
  ``compile_count`` makes that assertable.

``poisson_load`` generates open-loop Poisson arrivals with heterogeneous
prompt/output lengths; ``run_fixed_batch`` is the seed-style baseline the
benchmark gates the engine against (same step math, batch-synchronous
scheduling), instrumented per token so p50/p99 latencies are comparable.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import Model
from .paged_cache import PageAllocator, PagedCacheConfig, init_paged_pools

__all__ = ["Request", "poisson_load", "build_paged_serve_step",
           "ContinuousBatchingEngine", "run_fixed_batch", "summarize"]


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray          # (S,) int32 prompt ids
    max_new: int                # generation budget incl. the prefill token
    arrival: float              # seconds after load start (open loop)
    eos_id: int = -1            # -1: disabled (random-weight smokes)


def poisson_load(n_requests: int, rate: float, *, vocab: int,
                 prompt_buckets=(16, 32), new_token_buckets=(8, 16, 32, 96),
                 prompt_dist: str = "bucket", seed: int = 0,
                 eos_id: int = -1) -> List[Request]:
    """Open-loop Poisson arrivals (exponential gaps at ``rate`` req/s) with
    prompt lengths and generation budgets drawn from small bucket sets —
    heterogeneous enough to expose head-of-line blocking.

    ``prompt_dist`` selects the prompt-length draw:

    * ``"bucket"`` (default): uniform over ``prompt_buckets``.  This is a
      **legacy-path accommodation**, not a realism choice: the per-request
      prefill engine pays one XLA compile per DISTINCT prompt length, so
      an un-bucketed draw turns a load test into a compile storm.  Keeping
      the bucketed draw as the default keeps older callers honest about
      what they can afford.
    * ``"exact"``: uniform integer over ``[min(prompt_buckets),
      max(prompt_buckets)]`` — a length continuum no compile cache can
      pre-warm.  This is what real traffic looks like, and the chunked
      engine serves it with a CONSTANT compile count (static chunk
      shapes); on the legacy path it measures the compile storm itself.
    """
    assert prompt_dist in ("bucket", "exact"), prompt_dist
    rng = np.random.default_rng(seed)
    t = 0.0
    out = []
    lo, hi = min(prompt_buckets), max(prompt_buckets)
    for rid in range(n_requests):
        t += float(rng.exponential(1.0 / rate))
        if prompt_dist == "bucket":
            S = int(rng.choice(prompt_buckets))
        else:
            S = int(rng.integers(lo, hi + 1))
        out.append(Request(
            rid=rid,
            tokens=rng.integers(0, vocab, (S,)).astype(np.int32),
            max_new=int(rng.choice(new_token_buckets)),
            arrival=t, eos_id=eos_id))
    return out


def build_paged_serve_step(model: Model, *, attn_impl: str = "ref",
                           page_size: Optional[int] = None,
                           mixed: bool = False) -> Callable:
    """jitted ``step(params, pools, token, positions, page_table, kv_len)``
    → ``(next_token (B, 1), new_pools)``: one dispatch decodes the whole
    slot batch through the paged cache (greedy head).

    ``mixed=True`` builds the chunked-prefill fused step (DESIGN §11):
    ``step(params, pools, token, positions, page_table, kv_len,
    chunk_tokens, pt_row, chunk_start, chunk_len)`` →
    ``(next_token (B, 1), chunk_next (C,), new_pools)`` — the decode batch
    plus ONE prompt chunk of one slot in a single weight scan.
    ``chunk_next[i]`` is the greedy token after chunk position i; the
    engine reads row ``chunk_len - 1`` when the chunk completes a prompt
    (rows past ``chunk_len`` are padding garbage).  ``chunk_start`` /
    ``chunk_len`` are traced 0-d int32 — NOT shapes — so every chunk of
    every prompt length reuses this one compile.

    ``attn_impl``: "ref" is the pure-jnp gather + ``sdpa_ref`` path — the
    bit-exactness anchor the divergence gate relies on; "pallas" reads the
    page pool directly through :func:`repro.kernels.ops.paged_attention`
    (decode) and :func:`repro.kernels.ops.paged_prefill_attention`
    (chunk) — page-table gather in the BlockSpec index map, no dense
    gather."""
    assert model.decode_step_paged is not None, \
        f"{model.cfg.family}: no paged decode path (attention families only)"
    window = model.decode_window
    if attn_impl == "ref":
        attn_fn = prefill_attn_fn = None
    else:
        assert attn_impl == "pallas" and page_size is not None
        from repro.kernels.ops import paged_attention, paged_prefill_attention

        def attn_fn(q, k_pool, v_pool, page_table, kv_len):
            return paged_attention(q, k_pool, v_pool, page_table, kv_len,
                                   page_size=page_size)

        def prefill_attn_fn(q, k_chunk, v_chunk, k_pool, v_pool, pt_row,
                            chunk_start, chunk_len):
            return paged_prefill_attention(
                q, k_chunk, v_chunk, k_pool, v_pool, pt_row, chunk_start,
                chunk_len, page_size=page_size, window=window)

    if not mixed:
        def step(params, pools, token, positions, page_table, kv_len):
            logits, pools = model.decode_step_paged(
                params, pools, token, positions, page_table, kv_len,
                attn_fn=attn_fn)
            nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
            return nxt.astype(jnp.int32)[:, None], pools

        return jax.jit(step)

    assert model.decode_step_mixed is not None, \
        f"{model.cfg.family}: no mixed serving step (attention families only)"

    def mixed_step(params, pools, token, positions, page_table, kv_len,
                   chunk_tokens, pt_row, chunk_start, chunk_len):
        d_logits, c_logits, pools = model.decode_step_mixed(
            params, pools, token, positions, page_table, kv_len,
            chunk_tokens, pt_row, chunk_start, chunk_len,
            attn_fn=attn_fn, prefill_attn_fn=prefill_attn_fn)
        nxt = jnp.argmax(d_logits[:, -1].astype(jnp.float32), axis=-1)
        cn = jnp.argmax(c_logits[0].astype(jnp.float32), axis=-1)
        return nxt.astype(jnp.int32)[:, None], cn.astype(jnp.int32), pools

    return jax.jit(mixed_step)


@dataclasses.dataclass
class _Live:
    req: Request
    slot: int
    emitted: List[int]
    t_last: float               # emission time of the latest token


@dataclasses.dataclass
class _Fill:
    """A slot mid-chunked-prefill: admitted (pages reserved), prompt being
    written one chunk per mixed dispatch, no token emitted yet."""
    req: Request
    slot: int


class ContinuousBatchingEngine:
    """Slot-based continuous batching: admit on free pages, decode every
    live slot per dispatch, evict on EOS/max-tokens.

    Greedy decoding on the ``attn_impl="ref"`` backend is **token-exact**
    vs the dense reference :func:`repro.serve.engine.greedy_generate`:
    identical q/k/v values flow through the same ``sdpa_ref`` ops, and
    page-padding columns contribute exactly 0.0 under softmax
    (``exp(-1e30 − m)`` underflows to 0.0, and adding 0.0 to a float sum
    is the identity).  Logits agree to float32 rounding — the padded
    attention width changes XLA's reduction splitting, so the last ulp
    can wiggle without ever moving the argmax — see ``tests/test_serve.py``.

    ``prefill_chunk=C`` switches prompt processing to chunked prefill
    (DESIGN §11): admission only reserves a slot + pages, then each
    dispatch runs the fused mixed step — every live decode slot plus at
    most one C-token chunk of the OLDEST mid-prefill slot (FIFO), capped
    by ``max_step_tokens`` (chunk tokens + decode tokens per dispatch).
    The same argument chain gives token-exactness: chunk rows flow
    through the identical rope/sdpa ops at identical absolute positions,
    and the padded tail of the last chunk is masked out of the attention
    and scattered to the null page.

    ``compile_count`` counts engine-level jitted callables as they are
    built: per-prompt-length prefill and per-page-count scatter instances
    on the legacy path (kept in an LRU bounded by ``prefill_cache_cap`` —
    an evicted length recompiles on return), plus one each for the
    decode-only / mixed steps on first use.  It survives ``reset()`` so a
    warm→reset→measure bench can assert the measured phase compiled
    nothing new.
    """

    def __init__(self, model: Model, params, pcfg: PagedCacheConfig, *,
                 attn_impl: str = "ref", prefill_chunk: Optional[int] = None,
                 max_step_tokens: Optional[int] = None,
                 prefill_cache_cap: int = 8):
        if model.cfg.attn_kind != "gqa" or model.cfg.first_k_dense:
            raise NotImplementedError(
                f"{model.cfg.name}: the paged engine serves GQA stacks "
                f"without leading dense layers; attn_kind="
                f"{model.cfg.attn_kind!r} with first_k_dense="
                f"{model.cfg.first_k_dense} needs a latent paged cache, "
                f"which it does not have")
        assert model.decode_window == pcfg.window, \
            (model.decode_window, pcfg.window)
        self.model, self.params, self.pcfg = model, params, pcfg
        self.alloc = PageAllocator(pcfg)
        self.pools = init_paged_pools(model.cfg, pcfg)
        self.prefill_chunk = prefill_chunk
        if prefill_chunk is not None:
            assert prefill_chunk >= 1, prefill_chunk
            # ring scatter writes chunk rows at position % window: a chunk
            # wider than the ring would collide with itself
            assert not pcfg.window or prefill_chunk <= pcfg.window, \
                (prefill_chunk, pcfg.window)
        assert max_step_tokens is None or max_step_tokens >= 1
        self.max_step_tokens = max_step_tokens
        assert prefill_cache_cap >= 1, prefill_cache_cap
        self.prefill_cache_cap = prefill_cache_cap
        self.compile_count = 0
        from collections import OrderedDict
        self._jit_cache: "OrderedDict[Any, Callable]" = OrderedDict()
        self._step = None           # decode-only step, built on first use
        self._mixed = None          # mixed step, built on first use
        self._attn_impl = attn_impl
        self.reset()

    def reset(self) -> None:
        """Fresh serving state (allocator, slots, metrics) with the jitted
        step/prefill/scatter callables AND ``compile_count`` retained —
        benchmarks warm up the compiles on a throwaway trace, reset, then
        measure.  Pools keep stale pages: every page is re-written
        (prefill scatter / chunk scatter / decode write) before ``kv_len``
        ever exposes it, so stale rows are unreachable by construction
        (the masked-tail contract)."""
        pcfg = self.pcfg
        self.alloc = PageAllocator(pcfg)
        if not hasattr(self, "pools"):
            self.pools = init_paged_pools(self.model.cfg, pcfg)
        self.tok = np.zeros((pcfg.max_slots, 1), np.int32)
        self.live: Dict[int, _Live] = {}          # slot -> decoding state
        self._filling: List[_Fill] = []           # FIFO of mid-prefill slots
        self.completed: Dict[int, np.ndarray] = {}  # rid -> generated ids
        self.latencies: List[float] = []          # per emitted token (s)
        self.ttfts: List[float] = []              # arrival -> first token (s)
        self.queue_waits: List[float] = []        # arrival -> admission (s)
        self.steps = 0
        self._t0 = time.perf_counter()            # run() resets; absolute

    # -- compile accounting -------------------------------------------------

    def _cached_jit(self, key, factory) -> Callable:
        """Size-capped LRU of jitted callables, keyed by what pins their
        compiled shape (prompt length, page count).  A miss builds a FRESH
        ``jax.jit`` instance — so evicting an entry really frees its
        executable, and re-encountering the length really recompiles —
        and bumps ``compile_count``."""
        cache = self._jit_cache
        if key in cache:
            cache.move_to_end(key)
            return cache[key]
        fn = factory()
        self.compile_count += 1
        cache[key] = fn
        while len(cache) > self.prefill_cache_cap:
            cache.popitem(last=False)
        return fn

    def _decode_step(self) -> Callable:
        if self._step is None:
            self._step = build_paged_serve_step(
                self.model, attn_impl=self._attn_impl,
                page_size=self.pcfg.page_size)
            self.compile_count += 1
        return self._step

    def _mixed_step(self) -> Callable:
        if self._mixed is None:
            self._mixed = build_paged_serve_step(
                self.model, attn_impl=self._attn_impl,
                page_size=self.pcfg.page_size, mixed=True)
            self.compile_count += 1
        return self._mixed

    # -- device helpers -----------------------------------------------------

    @staticmethod
    def _scatter_impl(pools, caches, pages):
        """Scatter one request's dense prefill cache into its pages.
        caches leaf: (n_blocks, 1, L, K, hd); pages: (n_used,) physical
        ids.  Logical row r lands at row ``r % page_size`` of page
        ``pages[r // page_size]`` — for ring caches L == window and the
        rolled prefill layout maps through unchanged."""

        def one(pool, c):
            n_blocks, _, L, K, hd = c.shape
            ps = pool.shape[3]
            n_used = pages.shape[0]
            rows = jnp.pad(c[:, 0], ((0, 0), (0, n_used * ps - L),
                                     (0, 0), (0, 0)))
            rows = rows.reshape(n_blocks, n_used, ps, K, hd)
            return pool.at[:, :, pages].set(rows.transpose(0, 3, 1, 2, 4))

        return jax.tree.map(one, pools, caches)

    # -- admission / eviction -----------------------------------------------

    def try_admit(self, req: Request) -> bool:
        """Admit if a slot and enough pages are free.

        Legacy path: per-request prefill + page scatter, emitting the
        request's first token (prefill argmax) before returning.  Chunked
        path: reservation only — the prompt is processed one chunk per
        mixed dispatch and the first token is emitted by the dispatch that
        completes the last chunk."""
        S = int(req.tokens.shape[0])
        # rows the slot will hold: prompt + every fed-back token (the
        # final emitted token is never fed, hence max_new − 1)
        ctx = S + req.max_new - 1
        if not self.alloc.can_admit(ctx):
            return False
        now = time.perf_counter()
        self.queue_waits.append(now - (self._t0 + req.arrival))
        if self.prefill_chunk is not None:
            slot = self.alloc.admit(ctx, S, chunked=True)
            self._filling.append(_Fill(req=req, slot=slot))
            return True
        slot = self.alloc.admit(ctx, S)
        prefill = self._cached_jit(("prefill", S),
                                   lambda: jax.jit(self.model.prefill))
        logits, caches = prefill(self.params,
                                 {"tokens": jnp.asarray(req.tokens)[None]})
        n_used = self.alloc.pages_needed(ctx)
        scatter = self._cached_jit(("scatter", n_used),
                                   lambda: jax.jit(self._scatter_impl))
        pages = jnp.asarray(self.alloc.page_table[slot, :n_used])
        self.pools = scatter(self.pools, caches, pages)
        tok0 = int(jnp.argmax(logits[0, -1].astype(jnp.float32)))
        now = time.perf_counter()
        st = _Live(req=req, slot=slot, emitted=[tok0], t_last=now)
        # TTFT of token #1 (queue wait + prefill), on the absolute clock
        ttft = now - (self._t0 + req.arrival)
        self.latencies.append(ttft)
        self.ttfts.append(ttft)
        if req.max_new == 1 or tok0 == req.eos_id:
            self._finish(st)
        else:
            self.tok[slot, 0] = tok0
            self.live[slot] = st
        return True

    def _finish(self, st: _Live) -> None:
        self.completed[st.req.rid] = np.asarray(st.emitted, np.int32)
        self.alloc.release(st.slot)
        self.tok[st.slot, 0] = 0
        self.live.pop(st.slot, None)

    # -- decode -------------------------------------------------------------

    def _decode_inputs(self):
        """(positions, page_table, kv_len) for the decode half of a
        dispatch.  Mid-prefill slots are masked OUT: kv_len 0 and a
        null page-table row — in ring mode their decode-side write row
        ``length % window`` aliases a LIVE ring row, so the mask is
        correctness, not hygiene (see ``PageAllocator.decode_tables``)."""
        lens = self.alloc.lengths
        decoding = self.alloc.active & ~self.alloc.prefilling
        kv = np.where(decoding, lens + 1, 0).astype(np.int32)
        if self.pcfg.window:
            kv = np.minimum(kv, self.pcfg.window).astype(np.int32)
        pt, _ = self.alloc.decode_tables()
        return jnp.asarray(lens), pt, jnp.asarray(kv)

    def _next_chunk(self):
        """Pick the chunk for this dispatch: up to ``prefill_chunk`` tokens
        of the OLDEST mid-prefill slot, shrunk to the per-step token
        budget (``max_step_tokens`` − live decode slots).  Returns None
        (decode-only step) when there is no prefill work or no budget —
        budget starvation is transient, since live slots drain."""
        if not self._filling:
            return None
        C = self.prefill_chunk
        n_tok = C
        if self.max_step_tokens is not None:
            n_tok = min(n_tok, self.max_step_tokens - len(self.live))
        fill = self._filling[0]
        cur = int(self.alloc.prefill_cursor[fill.slot])
        n_tok = min(n_tok, int(fill.req.tokens.shape[0]) - cur)
        if n_tok <= 0:
            return None
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :n_tok] = fill.req.tokens[cur:cur + n_tok]
        return fill, cur, n_tok, chunk

    def step(self) -> None:
        """One batched dispatch: every live decode slot advances one token;
        in chunked mode one prefill chunk rides along (mixed step)."""
        positions, pt, kv = self._decode_inputs()
        work = self._next_chunk() if self.prefill_chunk is not None else None
        if work is None:
            nxt, self.pools = self._decode_step()(
                self.params, self.pools, jnp.asarray(self.tok),
                positions, pt, kv)
        else:
            fill, cur, n_tok, chunk = work
            pt_row = jnp.asarray(self.alloc.page_table[fill.slot])
            nxt, chunk_next, self.pools = self._mixed_step()(
                self.params, self.pools, jnp.asarray(self.tok),
                positions, pt, kv, jnp.asarray(chunk), pt_row,
                jnp.asarray(cur, jnp.int32), jnp.asarray(n_tok, jnp.int32))
        nxt = np.asarray(nxt)
        now = time.perf_counter()
        self.steps += 1
        joined = -1                       # slot that turned live THIS step
        if work is not None:
            self.alloc.advance_prefill(fill.slot, n_tok)
            if not self.alloc.prefilling[fill.slot]:
                # final chunk: emit the first token (argmax after the last
                # REAL prompt position — rows ≥ n_tok are padding)
                self._filling.pop(0)
                tok0 = int(np.asarray(chunk_next)[n_tok - 1])
                st = _Live(req=fill.req, slot=fill.slot, emitted=[tok0],
                           t_last=now)
                ttft = now - (self._t0 + fill.req.arrival)
                self.latencies.append(ttft)
                self.ttfts.append(ttft)
                if fill.req.max_new == 1 or tok0 == fill.req.eos_id:
                    self._finish(st)
                else:
                    self.tok[fill.slot, 0] = tok0
                    self.live[fill.slot] = st
                    joined = fill.slot
        for slot in list(self.live):
            if slot == joined:
                continue          # first decode of this slot is next step
            st = self.live[slot]
            self.alloc.advance(slot)
            tok = int(nxt[slot, 0])
            st.emitted.append(tok)
            self.latencies.append(now - st.t_last)
            st.t_last = now
            if len(st.emitted) >= st.req.max_new or tok == st.req.eos_id:
                self._finish(st)
            else:
                self.tok[slot, 0] = tok

    def compiled_step_text(self) -> str:
        """Compiled HLO text of the serving dispatch — the mixed step under
        chunked prefill, else the decode-only step — lowered at this
        engine's shapes (after :meth:`run`, the executable already built).
        A chip check reads which Pallas kernels it holds."""
        positions, pt, kv = self._decode_inputs()
        args = (self.params, self.pools, jnp.asarray(self.tok), positions,
                pt, kv)
        if self.prefill_chunk is None:
            return self._decode_step().lower(*args).compile().as_text()
        chunk = (jnp.zeros((1, self.prefill_chunk), jnp.int32),
                 jnp.asarray(self.alloc.page_table[0]),
                 jnp.asarray(0, jnp.int32), jnp.asarray(1, jnp.int32))
        return self._mixed_step().lower(*args, *chunk).compile().as_text()

    # -- arrival loop -------------------------------------------------------

    def run(self, requests: List[Request]) -> Dict[str, Any]:
        """Drive the open-loop arrival trace to completion; returns
        :func:`summarize`-style metrics."""
        pending = sorted(requests, key=lambda r: r.arrival)
        self._t0 = time.perf_counter()
        i = 0
        while i < len(pending) or self.live or self._filling:
            now = time.perf_counter() - self._t0
            while i < len(pending) and pending[i].arrival <= now:
                if not self.try_admit(pending[i]):
                    break                      # no slot/pages — decode first
                i += 1
            if self.live or self._filling:
                self.step()
            elif i < len(pending):
                time.sleep(min(1e-3, max(0.0, pending[i].arrival - now)))
        wall = time.perf_counter() - self._t0
        return summarize(self.completed, self.latencies, wall,
                         steps=self.steps, ttfts=self.ttfts,
                         queue_waits=self.queue_waits,
                         compile_count=self.compile_count)


def _pctls(vals, prefix: str) -> Dict[str, Any]:
    v = np.asarray(vals, np.float64) * 1e3
    return {
        f"{prefix}_p50_ms": round(float(np.percentile(v, 50)), 3)
        if len(v) else None,
        f"{prefix}_p99_ms": round(float(np.percentile(v, 99)), 3)
        if len(v) else None,
    }


def summarize(completed: Dict[int, np.ndarray], latencies: List[float],
              wall: float, *, steps: int,
              ttfts: Optional[List[float]] = None,
              queue_waits: Optional[List[float]] = None,
              compile_count: Optional[int] = None) -> Dict[str, Any]:
    """Serving metrics.  ``latencies`` are per emitted token (TTFT for a
    request's first token, inter-token gap after); ``ttfts`` /
    ``queue_waits`` are per request — TTFT (arrival → first token) is
    where chunked prefill shows up, queue wait (arrival → admission)
    isolates capacity from prefill scheduling."""
    total = int(sum(len(v) for v in completed.values()))
    lat = np.asarray(latencies) * 1e3
    out = {
        "requests": len(completed),
        "tokens": total,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(total / wall, 2) if wall else float("inf"),
        "steps": steps,
        "p50_ms": round(float(np.percentile(lat, 50)), 3) if len(lat) else None,
        "p99_ms": round(float(np.percentile(lat, 99)), 3) if len(lat) else None,
    }
    if ttfts is not None:
        out.update(_pctls(ttfts, "ttft"))
    if queue_waits is not None:
        out.update(_pctls(queue_waits, "queue"))
    if compile_count is not None:
        out["compile_count"] = compile_count
    return out


def run_fixed_batch(model: Model, params, requests: List[Request], *,
                    batch_size: int, prompt_pad: Optional[int] = None
                    ) -> Dict[str, Any]:
    """Seed-style batch-synchronous baseline, instrumented per token.

    Requests are chunked in arrival order into fixed batches: every chunk
    waits for its LAST arrival, prompts pad to one fixed length
    (``prompt_pad``, default the max prompt in the trace — the one-shape
    compile a static serving path would pin), and the whole chunk decodes
    ``max(max_new)`` steps.  Tokens past a request's own budget are
    decoded-and-discarded — that waste, plus the arrival barrier, is
    exactly the head-of-line cost continuous batching removes.  Only the
    requested tokens count toward throughput; latencies are stamped per
    decode step, so p50/p99 compare like-for-like with the engine."""
    from .engine import _jitted_serve_step, grow_caches

    if prompt_pad is None:
        prompt_pad = max(int(r.tokens.shape[0]) for r in requests)
    step = _jitted_serve_step(model)   # lru-cached: warmup calls carry over
    reqs = sorted(requests, key=lambda r: r.arrival)
    completed: Dict[int, np.ndarray] = {}
    latencies: List[float] = []
    ttfts: List[float] = []
    queue_waits: List[float] = []
    steps = 0
    t0 = time.perf_counter()
    for c0 in range(0, len(reqs), batch_size):
        chunk = reqs[c0:c0 + batch_size]
        barrier = max(r.arrival for r in chunk)
        while time.perf_counter() - t0 < barrier:
            time.sleep(1e-3)
        now = time.perf_counter()
        for r in chunk:
            queue_waits.append(now - (t0 + r.arrival))
        toks = np.zeros((len(chunk), prompt_pad), np.int32)
        for j, r in enumerate(chunk):
            toks[j, :r.tokens.shape[0]] = r.tokens
        n_steps = max(r.max_new for r in chunk)
        logits, caches = model.prefill(params, {"tokens": jnp.asarray(toks)})
        caches = grow_caches(model, caches, len(chunk),
                             model.decode_window or prompt_pad + n_steps)
        tok = jnp.argmax(logits[:, -1].astype(jnp.float32),
                         -1)[:, None].astype(jnp.int32)
        emitted = [np.asarray(tok)[:, 0]]
        now = time.perf_counter()
        t_last = [now] * len(chunk)
        for j, r in enumerate(chunk):
            ttft = now - (t0 + r.arrival)
            latencies.append(ttft)
            ttfts.append(ttft)
        steps += 1
        for s in range(n_steps - 1):
            tok, caches = step(params, caches, tok,
                               jnp.asarray(prompt_pad + s, jnp.int32))
            tok.block_until_ready()
            now = time.perf_counter()
            steps += 1
            emitted.append(np.asarray(tok)[:, 0])
            for j, r in enumerate(chunk):
                if s + 2 <= r.max_new:      # token s+2 is within budget
                    latencies.append(now - t_last[j])
                    t_last[j] = now
        gen = np.stack(emitted, axis=1)      # (chunk, n_steps)
        for j, r in enumerate(chunk):
            completed[r.rid] = gen[j, :r.max_new]
    wall = time.perf_counter() - t0
    return summarize(completed, latencies, wall, steps=steps, ttfts=ttfts,
                     queue_waits=queue_waits)
