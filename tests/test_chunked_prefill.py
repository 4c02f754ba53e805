"""Chunked prefill fused into the decode dispatch (DESIGN §11).

* token-exactness — the chunked engine's greedy output is EXACTLY the
  dense reference's (``greedy_generate``) and the legacy per-request
  engine's, across dense / GQA / sliding-window(ring) variants and both
  attention backends, including under a tight per-step token budget and
  a chunk width that is not a page multiple;
* chunk-by-chunk prefill logits match the one-shot dense prefill to
  float32 rounding at every prompt position;
* the Pallas paged prefill-attention kernel vs the gather+sdpa oracle vs
  a brute-force dense truth, on ragged chunk boundaries, ring wrap
  points and a NaN-poisoned pool (unallocated pages are never read);
* allocator invariants for interleaved chunked prefill + decode — a
  deterministic trajectory plus a hypothesis sweep (``slow``), linear
  and ring modes;
* compile accounting — the legacy per-length LRU really bounds the jit
  cache (evicted lengths recompile on return) and the chunked engine's
  ``compile_count`` is CONSTANT across prompt-length distributions.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serve.engine import greedy_generate
from repro.serve.paged_cache import (NULL_PAGE, PageAllocator,
                                     PagedCacheConfig, init_paged_pools)
from repro.serve.scheduler import (ContinuousBatchingEngine, Request,
                                   poisson_load)

PROMPTS = (5, 12, 20)          # ragged: straddles page and window boundaries


def _variant(name):
    cfg = get_smoke_config("smollm_360m")
    window = 0
    if name == "gqa":
        cfg = dataclasses.replace(cfg, n_kv_heads=2)
    elif name == "window":
        window = 16            # < max prompt: exercises the ring wrap
    return cfg, window


def _pcfg(window=0, max_slots=4):
    ctx = window or 64
    return PagedCacheConfig(
        page_size=8, num_pages=1 + max_slots * (-(-ctx // 8)),
        max_slots=max_slots, max_context=ctx, window=window)


def _requests(cfg, max_new=6, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    tokens=rng.integers(0, cfg.vocab_size, (S,))
                    .astype(np.int32),
                    max_new=max_new, arrival=0.0)
            for i, S in enumerate(PROMPTS)]


# ---------------------------------------------------------------------------
# end-to-end token-exactness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("attn_impl", ["ref", "pallas"])
@pytest.mark.parametrize("variant", ["dense", "gqa", "window"])
def test_chunked_engine_tokens_match_dense_reference(variant, attn_impl):
    """Chunked engine == per-request greedy_generate, token-for-token, on
    an exact-length Poisson trace (the distribution the legacy path can't
    afford), with exactly TWO compiles (mixed + decode-only)."""
    cfg, window = _variant(variant)
    model = build_model(cfg, decode_window=window)
    params = model.init(jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(model, params, _pcfg(window),
                                   attn_impl=attn_impl, prefill_chunk=8)
    reqs = poisson_load(6, rate=500.0, vocab=cfg.vocab_size,
                        prompt_buckets=(12, 20), new_token_buckets=(4, 9),
                        prompt_dist="exact", seed=3)
    metrics = eng.run(reqs)
    for r in reqs:
        ref = np.asarray(greedy_generate(
            model, params, {"tokens": jnp.asarray(r.tokens)[None]},
            n_steps=r.max_new))[0]
        np.testing.assert_array_equal(ref, eng.completed[r.rid])
    assert metrics["compile_count"] == 2
    assert metrics["ttft_p99_ms"] is not None
    assert metrics["queue_p99_ms"] is not None


@pytest.mark.parametrize("variant", ["dense", "window"])
def test_chunked_engine_matches_legacy_engine(variant):
    """Chunked and legacy per-request engines emit IDENTICAL tokens for
    the same trace — chunking is a scheduling change, not a math change.
    Also pins the budgeted path (max_step_tokens) and a chunk width that
    is not a page multiple."""
    cfg, window = _variant(variant)
    model = build_model(cfg, decode_window=window)
    params = model.init(jax.random.PRNGKey(0))
    reqs = poisson_load(6, rate=500.0, vocab=cfg.vocab_size,
                        prompt_buckets=(12, 20), new_token_buckets=(4, 9),
                        seed=5)
    legacy = ContinuousBatchingEngine(model, params, _pcfg(window))
    legacy.run(reqs)
    for chunk, mst in ((8, None), (5, 7)):
        eng = ContinuousBatchingEngine(model, params, _pcfg(window),
                                       prefill_chunk=chunk,
                                       max_step_tokens=mst)
        eng.run(reqs)
        for r in reqs:
            np.testing.assert_array_equal(
                legacy.completed[r.rid], eng.completed[r.rid],
                err_msg=f"{variant}: chunk={chunk} mst={mst} rid={r.rid}")


@pytest.mark.parametrize("variant", ["dense", "gqa", "window"])
def test_chunk_by_chunk_matches_full_prefill(variant):
    """Driving ``prefill_chunk_paged`` chunk by chunk over a prompt
    reproduces the one-shot dense prefill's logits at EVERY position to
    float32 rounding (and the argmax exactly) — the padded tail of the
    last chunk contributes nothing."""
    cfg, window = _variant(variant)
    model = build_model(cfg, decode_window=window)
    params = model.init(jax.random.PRNGKey(0))
    S, C = 20, 8
    rng = np.random.default_rng(7)
    tokens = rng.integers(0, cfg.vocab_size, (S,)).astype(np.int32)
    # dense truth: model.prefill returns only the LAST position's logits,
    # so build the per-position row from prefix prefills
    dense = []
    for p in range(S):
        lg, _ = model.prefill(
            params, {"tokens": jnp.asarray(tokens[:p + 1])[None]})
        dense.append(np.asarray(lg[0, -1], np.float32))
    dense = np.stack(dense)

    pcfg = _pcfg(window)
    alloc = PageAllocator(pcfg)
    pools = init_paged_pools(cfg, pcfg)
    slot = alloc.admit(S, S, chunked=True)
    pt_row = jnp.asarray(alloc.page_table[slot])
    got = []
    for cur in range(0, S, C):
        n = min(C, S - cur)
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :n] = tokens[cur:cur + n]
        logits, pools = model.prefill_chunk_paged(
            params, pools, jnp.asarray(chunk), pt_row,
            jnp.asarray(cur, jnp.int32), jnp.asarray(n, jnp.int32))
        got.append(np.asarray(logits[0, :n], np.float32))
        alloc.advance_prefill(slot, n)
    assert not alloc.prefilling[slot]
    got = np.concatenate(got)
    np.testing.assert_allclose(got, dense, atol=1e-4, rtol=1e-3)
    np.testing.assert_array_equal(got.argmax(-1), dense.argmax(-1))


# ---------------------------------------------------------------------------
# kernel vs oracle vs dense truth
# ---------------------------------------------------------------------------

# (window, chunk_start, C, chunk_len): linear first/mid/ragged-last chunks,
# ring before/at/long-after the wrap, ragged ring tails, C == window
KERNEL_CASES = [
    (0, 0, 4, 4), (0, 4, 4, 4), (0, 9, 4, 3), (0, 20, 4, 1),
    (8, 0, 4, 4), (8, 4, 4, 4), (8, 7, 4, 4), (8, 8, 4, 4),
    (8, 13, 4, 3), (8, 37, 4, 2), (8, 37, 8, 8),
]


@pytest.mark.parametrize("window,start,C,clen", KERNEL_CASES)
def test_prefill_kernel_matches_oracle_and_truth(window, start, C, clen):
    """Pallas prefill kernel == gather+sdpa oracle == brute-force dense
    ``sdpa_ref`` on NaN-poisoned pools (every pool row the slot does not
    own is NaN — finite output proves neither path read one), with GQA
    head sharing and ragged chunk tails."""
    from repro.kernels.ops import paged_prefill_attention
    from repro.kernels.ref import paged_prefill_attention_ref
    from repro.models.attention import sdpa_ref

    rng = np.random.default_rng(0)
    page_size, n_pages, num_pages = 4, 6, 16
    K, G, hd = 2, 2, 8
    H = K * G
    k_hist = rng.standard_normal((start, K, hd)).astype(np.float32)
    v_hist = rng.standard_normal((start, K, hd)).astype(np.float32)
    k_pool = np.full((K, num_pages, page_size, hd), np.nan, np.float32)
    v_pool = np.full((K, num_pages, page_size, hd), np.nan, np.float32)
    n_slot_pages = (window // page_size) if window else n_pages
    phys = rng.choice(np.arange(1, num_pages), size=n_slot_pages,
                      replace=False)
    pt_row = np.zeros((n_pages,), np.int32)
    pt_row[:n_slot_pages] = phys
    # null page is a live write sink (clamped reads see weight-0 rows)
    k_pool[:, NULL_PAGE] = 0.0
    v_pool[:, NULL_PAGE] = 0.0
    for p in range(start):
        row = p % window if window else p
        pg, r = row // page_size, row % page_size
        k_pool[:, pt_row[pg], r] = k_hist[p]
        v_pool[:, pt_row[pg], r] = v_hist[p]

    q = rng.standard_normal((1, C, H, hd)).astype(np.float32)
    k_c = rng.standard_normal((1, C, K, hd)).astype(np.float32)
    v_c = rng.standard_normal((1, C, K, hd)).astype(np.float32)

    ref = paged_prefill_attention_ref(
        q, k_c, v_c, jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pt_row), start, clen, window=window)
    ker = paged_prefill_attention(
        q, k_c, v_c, jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pt_row), jnp.asarray(start, jnp.int32),
        jnp.asarray(clen, jnp.int32), page_size=page_size, window=window)
    ref = np.asarray(ref)[:, :clen]
    ker = np.asarray(ker)[:, :clen]
    assert np.isfinite(ref).all(), "oracle read a poisoned page"
    assert np.isfinite(ker).all(), "kernel read a poisoned page"
    np.testing.assert_allclose(ker, ref, atol=2e-5)
    # brute-force dense truth over history + the real chunk rows
    k_all = np.concatenate([k_hist, k_c[0, :clen]])[None]
    v_all = np.concatenate([v_hist, v_c[0, :clen]])[None]
    truth = sdpa_ref(jnp.asarray(q[:, :clen]), jnp.asarray(k_all),
                     jnp.asarray(v_all), causal=True, window=window,
                     q_offset=start)
    np.testing.assert_allclose(np.asarray(truth), ref, atol=2e-5)


# ---------------------------------------------------------------------------
# allocator: interleaved chunked prefill + decode
# ---------------------------------------------------------------------------

def test_allocator_chunked_trajectory():
    pcfg = PagedCacheConfig(page_size=8, num_pages=8, max_slots=3,
                            max_context=24)
    al = PageAllocator(pcfg)
    d = al.admit(10, 6)                       # legacy: rows live immediately
    s = al.admit(20, 17, chunked=True)        # 3 pages reserved up front
    assert al.pages_in_use == 2 + 3
    assert al.prefilling[s] and not al.prefilling[d]
    assert al.lengths[s] == 0 and al.prefill_cursor[s] == 0
    # mid-prefill slots are masked out of the decode dispatch
    pt, _ = al.decode_tables()
    assert (np.asarray(pt)[s] == NULL_PAGE).all()
    assert (np.asarray(pt)[d] != NULL_PAGE).any()
    # but their real pages stay visible to the chunk path
    assert (al.page_table[s] != NULL_PAGE).sum() == 3
    with pytest.raises(AssertionError):
        al.advance(s)                         # no decode while prefilling
    al.advance_prefill(s, 8)
    al.advance(d)                             # decode interleaves freely
    assert al.lengths[s] == 8 == al.prefill_cursor[s]
    with pytest.raises(AssertionError):
        al.advance_prefill(s, 10)             # cursor past prompt_len
    al.advance_prefill(s, 9)                  # ragged last chunk
    assert not al.prefilling[s] and al.lengths[s] == 17
    pt, _ = al.decode_tables()
    assert (np.asarray(pt)[s] != NULL_PAGE).any()
    al.advance(s)                             # now a decode slot
    with pytest.raises(AssertionError):
        al.advance_prefill(s, 1)              # prefill is over
    al.release(s)
    assert not al.prefilling[s] and al.prefill_cursor[s] == 0
    assert al.pages_in_use == 2


@pytest.mark.slow
@pytest.mark.parametrize("window", [0, 16])
def test_allocator_chunked_interleaved_property(window):
    """Random interleavings of chunked admits, legacy admits, prefill
    advances, decode advances and releases preserve the allocator
    invariants (hypothesis sweep; linear and ring modes)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    pcfg = PagedCacheConfig(page_size=8, num_pages=13, max_slots=4,
                            max_context=32, window=window)

    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 4), st.integers(0, 2 ** 30)),
                    max_size=60),
           st.integers(0, 2 ** 30))
    def run(ops, seed):
        rng = np.random.default_rng(seed)
        al = PageAllocator(pcfg)
        filling, decoding = [], []
        for op, r in ops:
            if op == 0 or op == 1:                        # admit
                S = 1 + r % 24
                ctx = min(S + rng.integers(0, 8), pcfg.max_context)
                ctx = max(ctx, S) if not window else S + int(rng.integers(0, 8))
                if not al.can_admit(ctx):
                    continue
                chunked = op == 0
                slot = al.admit(ctx, S, chunked=chunked)
                (filling if chunked else decoding).append(slot)
            elif op == 2 and filling:                     # prefill chunk
                slot = filling[r % len(filling)]
                left = int(al.prompt_len[slot] - al.prefill_cursor[slot])
                al.advance_prefill(slot, 1 + r % left)
                if not al.prefilling[slot]:
                    filling.remove(slot)
                    decoding.append(slot)
            elif op == 3 and decoding:                    # decode token
                slot = decoding[r % len(decoding)]
                if window or al.lengths[slot] < pcfg.max_context:
                    al.advance(slot)
            elif op == 4 and (filling or decoding):       # release
                pool = filling if (r % 2 == 0 and filling) else decoding
                if not pool:
                    pool = filling or decoding
                slot = pool[r % len(pool)]
                al.release(slot)
                pool.remove(slot)
            # -- invariants ----------------------------------------------
            assert al.prefilling[al.prefilling].size == len(filling)
            assert not (al.prefilling & ~al.active).any()
            assert (al.prefill_cursor <= al.prompt_len).all()
            assert (al.lengths[al.prefilling]
                    == al.prefill_cursor[al.prefilling]).all()
            owned = al.page_table[al.active]
            owned = owned[owned != NULL_PAGE]
            assert len(set(owned.tolist())) == len(owned)   # disjoint
            assert al.pages_in_use == len(owned)
            pt, _ = al.decode_tables()
            assert (np.asarray(pt)[al.prefilling] == NULL_PAGE).all()
        for slot in filling + decoding:
            al.release(slot)
        assert al.pages_in_use == 0 and al.n_active == 0

    run()


def test_prefill_chunk_validation():
    cfg, window = _variant("window")
    model = build_model(cfg, decode_window=window)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    with pytest.raises(AssertionError):   # chunk would self-collide in ring
        ContinuousBatchingEngine(model, params, _pcfg(window),
                                 prefill_chunk=window + 1)
    with pytest.raises(AssertionError):
        ContinuousBatchingEngine(model, params, _pcfg(window),
                                 prefill_chunk=0)
    with pytest.raises(AssertionError):
        ContinuousBatchingEngine(model, params, _pcfg(window),
                                 prefill_chunk=8, max_step_tokens=0)


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------

def test_legacy_prefill_cache_lru_is_size_capped():
    """The legacy path's per-length jit cache really evicts: with cap 4,
    a third distinct prompt length evicts the first (prefill + scatter
    entries), so re-admitting it recompiles; a still-cached length does
    not."""
    cfg, _ = _variant("dense")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(model, params, _pcfg(),
                                   prefill_cache_cap=4)

    def admit(S, rid):
        # max_new=1: the prefill token completes the request immediately,
        # so the slot frees and only compile accounting accumulates
        r = Request(rid=rid, tokens=np.arange(S, dtype=np.int32) % 17,
                    max_new=1, arrival=0.0)
        assert eng.try_admit(r)

    admit(5, 0)                    # prefill(5)+scatter(1p)      -> 2
    admit(12, 1)                   # prefill(12)+scatter(2p)     -> 4
    assert eng.compile_count == 4
    admit(12, 2)                   # both cached                 -> 4
    assert eng.compile_count == 4
    admit(20, 3)                   # prefill(20)+scatter(3p) evicts length-5
    assert eng.compile_count == 6
    admit(12, 4)                   # still cached (LRU-refreshed)
    assert eng.compile_count == 6
    admit(5, 5)                    # evicted: BOTH entries rebuilt
    assert eng.compile_count == 8


def test_chunked_compile_count_constant_across_distributions():
    """The chunked engine compiles exactly twice (mixed + decode-only) no
    matter the prompt-length distribution — bucketed or an exact-length
    continuum — and ``reset()`` keeps the compiles warm."""
    cfg, _ = _variant("dense")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(model, params, _pcfg(), prefill_chunk=8)
    for dist, seed in (("bucket", 0), ("exact", 1), ("exact", 2)):
        eng.reset()
        reqs = poisson_load(5, rate=500.0, vocab=cfg.vocab_size,
                            prompt_buckets=(9, 21),
                            new_token_buckets=(4, 7),
                            prompt_dist=dist, seed=seed)
        metrics = eng.run(reqs)
        assert metrics["compile_count"] == 2, (dist, seed)


def test_poisson_exact_prompt_dist():
    """``prompt_dist="exact"`` draws a length continuum over the bucket
    span — lengths outside the bucket set appear, none outside the span;
    arrivals and budgets are unaffected."""
    reqs = poisson_load(64, rate=100.0, vocab=64,
                        prompt_buckets=(8, 24), new_token_buckets=(4,),
                        prompt_dist="exact", seed=0)
    lens = {int(r.tokens.shape[0]) for r in reqs}
    assert all(8 <= n <= 24 for n in lens)
    assert lens - {8, 24}, "exact draw never left the bucket set"
    with pytest.raises(AssertionError):
        poisson_load(1, rate=1.0, vocab=64, prompt_dist="nope")
