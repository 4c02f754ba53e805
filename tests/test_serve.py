"""Serving path (DESIGN §10): paged KV cache + continuous batching.

* paged-vs-dense decode equivalence — the paged engine's logits match the
  dense reference to float32 rounding and greedy tokens are EXACTLY equal,
  across ragged slot batches, for the dense-attention, GQA and
  sliding-window(ring) variants;
* Pallas paged decode-attention vs the dense oracle on ragged batches,
  including the masked-tail contract (NaN-poisoned unallocated pages
  never reach the output);
* page-allocator admit/advance/release trajectory invariants;
* layout-driven cache growth for the fixed-batch reference path;
* consensus export: per-leaf agent mean, loaded under ``serve_param_specs``
  and generating identically (subprocess ``--agents pod`` training run).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels.paged_attention import GRID_MAX_PAGES, pages_per_block
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.serve.engine import greedy_generate, grow_caches, serve_param_specs
from repro.serve.paged_cache import (NULL_PAGE, PageAllocator,
                                     PagedCacheConfig, init_paged_pools)
from repro.serve.scheduler import (ContinuousBatchingEngine, Request,
                                   poisson_load, run_fixed_batch)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}

PROMPTS = (5, 12, 20)          # ragged: straddles page and window boundaries


def _variant(name):
    import dataclasses
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
# paged vs dense: logits bit-exact on ragged slot batches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["dense", "gqa", "window"])
def test_paged_logits_match_dense(variant):
    """For every slot of a ragged batch, every decode step's logits from
    the paged path match the dense reference's to float32 rounding, and
    the greedy argmax is EXACTLY equal.  Page-padding columns contribute
    exactly 0.0 under softmax (−inf mask → exp underflow), but the padded
    attention width changes XLA's reduction splitting, so the last ulp of
    the float sums can differ — token-level exactness is the serving
    contract (asserted here per step and end-to-end below)."""
    cfg, window = _variant(variant)
    model = build_model(cfg, decode_window=window)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _requests(cfg)
    eng = ContinuousBatchingEngine(model, params, _pcfg(window))
    for r in reqs:
        assert eng.try_admit(r)

    # dense side: per-request caches at each request's own exact length
    dense = []
    for r in reqs:
        logits, caches = model.prefill(
            params, {"tokens": jnp.asarray(r.tokens)[None]})
        L = int(r.tokens.shape[0])
        caches = grow_caches(model, caches, 1, window or L + r.max_new)
        dense.append({"caches": caches, "pos": L,
                      "tok": jnp.argmax(logits[:, -1].astype(jnp.float32),
                                        -1)[:, None].astype(jnp.int32)})

    pt, _ = eng.alloc.device_tables()
    for step in range(4):
        lens = eng.alloc.lengths
        kv = np.where(eng.alloc.active, lens + 1, 0).astype(np.int32)
        if window:
            kv = np.minimum(kv, window).astype(np.int32)
        paged_logits, eng.pools = model.decode_step_paged(
            params, eng.pools, jnp.asarray(eng.tok), jnp.asarray(lens),
            pt, jnp.asarray(kv))
        for i, d in enumerate(dense):
            ref_logits, d["caches"] = model.decode_step(
                params, d["caches"], d["tok"],
                jnp.asarray(d["pos"], jnp.int32))
            got = np.asarray(paged_logits[i], np.float32)
            want = np.asarray(ref_logits[0], np.float32)
            np.testing.assert_allclose(
                got, want, atol=1e-4, rtol=1e-3,
                err_msg=f"{variant}: slot {i} step {step} logits diverged")
            assert got.argmax() == want.argmax(), \
                f"{variant}: slot {i} step {step} greedy token diverged"
            d["tok"] = jnp.argmax(ref_logits[:, -1].astype(jnp.float32),
                                  -1)[:, None].astype(jnp.int32)
            d["pos"] += 1
            eng.tok[i, 0] = int(d["tok"][0, 0])
            eng.alloc.advance(i)


@pytest.mark.parametrize("attn_impl", ["ref", "pallas"])
def test_engine_tokens_match_dense_reference(attn_impl):
    """End-to-end continuous engine == per-request greedy_generate,
    token-for-token, on a Poisson trace (both attention backends)."""
    cfg, window = _variant("dense")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(model, params, _pcfg(), attn_impl=attn_impl)
    reqs = poisson_load(6, rate=500.0, vocab=cfg.vocab_size,
                        prompt_buckets=(12, 20),
                        new_token_buckets=(4, 9), seed=3)
    eng.run(reqs)
    for r in reqs:
        ref = np.asarray(greedy_generate(
            model, params, {"tokens": jnp.asarray(r.tokens)[None]},
            n_steps=r.max_new))[0]
        np.testing.assert_array_equal(ref, eng.completed[r.rid])


def test_paged_never_reads_unallocated_pages():
    """Masked-tail contract: NaN-poison every page no live slot owns — live
    slots' logits are unchanged and finite, so neither the gather path nor
    the Pallas index map can have touched an unallocated page's data.  (The
    null page stays clean: page-table tail entries point at it and its
    rows carry exactly-zero softmax weight — 0.0 × finite is the identity,
    0.0 × NaN is not, so "never read" for it means weight-0, not
    untouched-by-gather.)"""
    cfg, _ = _variant("dense")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(model, params, _pcfg())
    for r in _requests(cfg):
        assert eng.try_admit(r)
    lens = eng.alloc.lengths
    kv = np.where(eng.alloc.active, lens + 1, 0).astype(np.int32)
    pt, _ = eng.alloc.device_tables()
    clean, _ = model.decode_step_paged(
        params, eng.pools, jnp.asarray(eng.tok), jnp.asarray(lens), pt,
        jnp.asarray(kv))

    owned = set(np.asarray(pt)[np.asarray(eng.alloc.active)].reshape(-1)
                .tolist()) | {NULL_PAGE}
    unallocated = [p for p in range(eng.pcfg.num_pages) if p not in owned]
    poisoned = jax.tree.map(
        lambda pool: pool.at[:, :, jnp.asarray(unallocated)].set(jnp.nan),
        eng.pools)
    dirty, _ = model.decode_step_paged(
        params, poisoned, jnp.asarray(eng.tok), jnp.asarray(lens), pt,
        jnp.asarray(kv))
    live = np.asarray(eng.alloc.active)
    assert np.isfinite(np.asarray(dirty, np.float32)[live]).all()
    np.testing.assert_array_equal(np.asarray(clean, np.float32)[live],
                                  np.asarray(dirty, np.float32)[live])


def _edges(page_size, ppb, full):
    """kv lengths at the page and block edges, and a full slot."""
    block = ppb * page_size
    return [0, 1, page_size - 1, page_size, block - 1, block, block + 1, full]


# the copying walk: hd 128, K 4, G 9 (starcoder2_7b's 36 / 4), page 64
_COPY = dict(K=4, G=9, hd=128, page=64, n_pages=10)
_COPY_PPB = pages_per_block(page_size=64, hd=128, n_kv_heads=4, n_pages=10,
                            itemsize=4)
# the grid walk: smollm_360m's K 5 × hd 64, page 16
_GRID = dict(K=5, G=3, hd=64, page=16, n_pages=20)
_GRID_PPB = min(GRID_MAX_PAGES, pages_per_block(
    page_size=16, hd=64, n_kv_heads=5, n_pages=20, itemsize=4))

PAGED_ORACLE_CASES = {
    # idle slot 1; full slot 2
    "ragged": dict(K=2, G=3, hd=16, page=8, n_pages=3, kv=[5, 0, 24, 17]),
    "copy_walk_edges": dict(**_COPY, kv=_edges(64, _COPY_PPB, 640)),
    "grid_walk_edges": dict(**_GRID, kv=_edges(16, _GRID_PPB, 320)),
    # entries past the used prefix point at a poisoned page, not NULL, and
    # rows past kv_len in the last used page are poisoned too
    "copy_walk_poisoned_tail": dict(**_COPY, kv=_edges(64, _COPY_PPB, 640),
                                    tail="poison"),
    "grid_walk_poisoned_tail": dict(**_GRID, kv=_edges(16, _GRID_PPB, 320),
                                    tail="poison"),
    # entries past the used prefix (an idle slot's first among them) name a
    # page past the pool: the interpreter raises on any fetch of one
    "copy_walk_out_of_range_tail": dict(**_COPY, kv=_edges(64, _COPY_PPB,
                                                           640),
                                        tail="out_of_range"),
    "grid_walk_out_of_range_tail": dict(**_GRID, kv=_edges(16, _GRID_PPB,
                                                           320),
                                        tail="out_of_range"),
    # ring mode: kv_len = min(length, window), the slot's ring pages
    "ring": dict(**{**_COPY, "n_pages": 8},
                 kv=np.minimum([700, 300, 0, 2000], 8 * 64).tolist()),
}


@pytest.mark.parametrize("case", PAGED_ORACLE_CASES)
def test_paged_kernel_matches_oracle_ragged(case):
    """Pallas kernel vs the gather+sdpa oracle on a ragged batch with idle
    slots, GQA head-sharing and NaN-poisoned unallocated pages, with kv
    lengths at the page and block edges of both walks (copying, hd 128;
    grid, hd 64).  The TPU interpreter raises on an out-of-bounds fetch and
    fills fresh VMEM with NaN, so a buffer row the walk did not fill must
    be masked too."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.ops import paged_attention
    from repro.kernels.ref import paged_attention_ref

    c = PAGED_ORACLE_CASES[case]
    K, G, hd, page_size, n_pages = (c[k] for k in
                                    ("K", "G", "hd", "page", "n_pages"))
    tail = c.get("tail", "null")
    kv_len = np.array(c["kv"], np.int32)
    B = len(kv_len)
    n_used = -(-kv_len // page_size)
    num_pages = 1 + int(n_used.sum()) + 4            # 4 spare, unallocated
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, K, G, hd)).astype(np.float32))
    kp = rng.normal(size=(K, num_pages, page_size, hd)).astype(np.float32)
    vp = rng.normal(size=(K, num_pages, page_size, hd)).astype(np.float32)
    pt = np.full((B, n_pages), {"null": 0, "poison": num_pages - 1,
                                "out_of_range": num_pages + 1000}[tail],
                 np.int32)
    first = 1
    for b, n in enumerate(n_used):
        pt[b, :n] = np.arange(first, first + n)
        first += n
        if tail == "poison" and kv_len[b] % page_size:
            last = pt[b, n - 1]
            kp[:, last, kv_len[b] % page_size:] = np.nan
            vp[:, last, kv_len[b] % page_size:] = np.nan
    kp[:, first:] = np.nan                           # unallocated pages
    vp[:, first:] = np.nan
    kp, vp = jnp.asarray(kp), jnp.asarray(vp)
    len_j = jnp.asarray(kv_len)
    try:
        out = paged_attention(q, kp, vp, jnp.asarray(pt), len_j,
                              page_size=page_size,
                              interpret=pltpu.InterpretParams())
        out.block_until_ready()
    finally:   # a fault inside the interpreter leaves its state behind
        pltpu.reset_tpu_interpret_mode_state()
    ref = paged_attention_ref(q, jnp.nan_to_num(kp), jnp.nan_to_num(vp),
                              jnp.asarray(np.minimum(pt, num_pages - 1)),
                              len_j, page_size=page_size)
    live = kv_len > 0
    assert jnp.isfinite(out).all()
    assert (np.asarray(out)[~live] == 0).all()       # idle: zero tile
    assert np.allclose(np.asarray(out)[live], np.asarray(ref)[live],
                       atol=1e-5)


# ---------------------------------------------------------------------------
# allocator units
# ---------------------------------------------------------------------------

def test_allocator_admit_evict_trajectory():
    pcfg = PagedCacheConfig(page_size=8, num_pages=6, max_slots=3,
                            max_context=24)          # 5 usable pages
    al = PageAllocator(pcfg)
    assert al.pages_in_use == 0 and al.n_active == 0
    s0 = al.admit(context_len=9, prompt_len=5)       # 2 pages
    s1 = al.admit(context_len=24, prompt_len=20)     # 3 pages
    assert al.pages_in_use == 5 and al.n_active == 2
    # disjointness + no null page handed out
    used = np.concatenate([al.page_table[s0], al.page_table[s1]])
    used = used[used != NULL_PAGE]
    assert NULL_PAGE not in used.tolist()
    assert len(set(used.tolist())) == len(used)
    # a slot is still free but the page pool is exhausted
    assert al.free_slots and not al.can_admit(1)
    al.advance(s1)
    assert al.lengths[s1] == 21
    al.release(s1)                                   # pages come back
    assert al.n_active == 1 and al.pages_in_use == 2
    assert (al.page_table[s1] == NULL_PAGE).all() and al.lengths[s1] == 0
    assert al.can_admit(24)
    with pytest.raises(AssertionError):
        al.release(s1)                               # double release
    al.release(s0)
    assert al.pages_in_use == 0 and al.n_active == 0
    assert len(al.free_pages) == pcfg.num_pages - 1  # null page never freed


def test_allocator_ring_mode_owns_whole_ring():
    pcfg = PagedCacheConfig(page_size=8, num_pages=16, max_slots=2,
                            max_context=128, window=16)
    al = PageAllocator(pcfg)
    assert pcfg.pages_per_slot == 2
    assert al.pages_needed(context_len=5) == 2       # whole ring up front
    s = al.admit(context_len=100, prompt_len=30)     # > window: legal (ring)
    assert al.lengths[s] == 30                       # TRUE absolute length
    for _ in range(70):
        al.advance(s)
    assert al.lengths[s] == 100


def test_pagedcacheconfig_validation():
    with pytest.raises(AssertionError):
        PagedCacheConfig(page_size=6, num_pages=8, max_slots=1,
                         max_context=16)             # not 8-row aligned
    with pytest.raises(AssertionError):
        PagedCacheConfig(page_size=8, num_pages=16, max_slots=1,
                         max_context=64, window=20)  # window % page != 0
    with pytest.raises(AssertionError):
        PagedCacheConfig(page_size=8, num_pages=3, max_slots=1,
                         max_context=64)             # pool < 1 slot + null


# ---------------------------------------------------------------------------
# layout-driven cache growth (fixed-batch reference path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["falcon_mamba_7b", "jamba_1_5_large_398b"])
def test_grow_caches_leaves_length_free_leaves_alone(arch):
    """SSM/conv state has no sequence axis: growth must pass it through
    bit-identically (the name-matching growth this replaces could silently
    mis-grow any leaf whose dim happened to equal the prompt length)."""
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                              cfg.vocab_size)
    _, caches = model.prefill(params, {"tokens": toks})
    grown = grow_caches(model, caches, 2, 6 + 4)
    flat_c = jax.tree_util.tree_leaves_with_path(caches)
    flat_g = dict(jax.tree_util.tree_leaves_with_path(grown))
    n_grown = 0
    for path, c in flat_c:
        g = flat_g[path]
        if g.shape == c.shape:
            np.testing.assert_array_equal(np.asarray(c, np.float32),
                                          np.asarray(g, np.float32))
        else:
            n_grown += 1
    if cfg.family == "hybrid":
        assert n_grown > 0                           # attn positions grew
    else:
        assert n_grown == 0                          # pure SSM: nothing to


def test_fixed_batch_baseline_counts_only_requested_tokens():
    cfg, _ = _variant("dense")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _requests(cfg, max_new=4)
    reqs[0].max_new = 9                              # head-of-line chunk
    m = run_fixed_batch(model, params, reqs, batch_size=len(reqs))
    assert m["tokens"] == sum(r.max_new for r in reqs)
    assert m["steps"] == 9                           # max(max_new) for all


# ---------------------------------------------------------------------------
# consensus export (train -> serve handoff)
# ---------------------------------------------------------------------------

def test_consensus_export_is_agent_mean(tmp_path):
    from repro.train import checkpoint

    rng = np.random.default_rng(0)
    A = 4
    params = {"embed": rng.normal(size=(A, 7, 3)).astype(np.float32),
              "blocks": ({"w": rng.normal(size=(A, 2, 5)).astype(np.float32)},)}
    state = {"params": params, "opt": {"m": jax.tree.map(np.zeros_like,
                                                         params)},
             "step": np.int32(3)}
    src, dst = str(tmp_path / "train.npz"), str(tmp_path / "consensus.npz")
    checkpoint.save_state(src, state)
    checkpoint.export_consensus(src, dst)
    got = np.load(dst)
    assert set(got.files) == {"embed", "blocks|0|w"}  # params only, no axis
    np.testing.assert_array_equal(
        got["embed"],
        params["embed"].mean(axis=0, dtype=np.float64).astype(np.float32))
    like = {"embed": jax.ShapeDtypeStruct((7, 3), np.float32),
            "blocks": ({"w": jax.ShapeDtypeStruct((2, 5), np.float32)},)}
    back = checkpoint.load_consensus(dst, like)
    np.testing.assert_array_equal(back["embed"], got["embed"])


def test_consensus_export_keeps_bf16_leaves(tmp_path):
    """bf16 params (every published config's dtype) survive save → export
    → load: .npz alone would hand them back as raw ``|V2`` bytes."""
    from repro.train import checkpoint

    bf16 = jnp.dtype("bfloat16")
    rng = np.random.default_rng(0)
    params = {"embed": rng.normal(size=(2, 7, 3)).astype(bf16),
              "norm": rng.normal(size=(2, 3)).astype(np.float32)}
    src, dst = str(tmp_path / "train.npz"), str(tmp_path / "consensus.npz")
    checkpoint.save_state(src, {"params": params, "step": np.int32(1)})
    like = {"params": jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), params),
        "step": jax.ShapeDtypeStruct((), np.int32)}
    back = checkpoint.load_state(src, like)
    assert back["params"]["embed"].dtype == bf16
    np.testing.assert_array_equal(back["params"]["embed"], params["embed"])
    checkpoint.export_consensus(src, dst)
    cons = checkpoint.load_consensus(dst, {
        "embed": jax.ShapeDtypeStruct((7, 3), bf16),
        "norm": jax.ShapeDtypeStruct((3,), np.float32)})
    assert cons["embed"].dtype == bf16
    np.testing.assert_array_equal(
        cons["embed"],
        params["embed"].mean(axis=0, dtype=np.float64).astype(bf16))


def test_consensus_export_from_pod_run_serves(tmp_path):
    """Acceptance: a checkpoint from an ``--agents pod`` (FSDP-sharded)
    training run exports its consensus, loads under ``serve_param_specs``
    on the serving mesh, and generates identically to averaging the
    gathered-layout agent params directly — the checkpoint being logical/
    sharding-independent is what makes both routes the same bytes."""
    from repro.train import checkpoint

    ckpt = str(tmp_path / "pod.npz")
    env = {**ENV, "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "smollm_360m",
         "--smoke", "--steps", "2", "--agents", "pod", "--pods", "2",
         "--seq", "16", "--gossip-engine", "ppermute", "--ckpt", ckpt],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]

    cons = str(tmp_path / "consensus.npz")
    checkpoint.export_consensus(ckpt, cons)

    cfg = get_smoke_config("smollm_360m")
    model = build_model(cfg)
    like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_c = jax.tree.map(jnp.asarray,
                            checkpoint.load_consensus(cons, like))

    # gathered-layout route: mean the stacked agent params of the raw
    # checkpoint directly (float64 accumulate, one rounding — as export)
    data = np.load(ckpt)
    direct = {}
    for k in data.files:
        if k.startswith("params|"):
            leaf = data[k]
            direct[k[len("params|"):]] = (
                leaf.mean(axis=0, dtype=np.float64).astype(leaf.dtype))
    flat, _ = jax.tree_util.tree_flatten_with_path(params_c)
    for path, leaf in flat:
        key = "|".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        np.testing.assert_array_equal(np.asarray(leaf), direct[key])

    # load under the serving TP specs and generate
    from jax.sharding import NamedSharding
    mesh = make_mesh((1, 1), ("data", "model"))
    specs = serve_param_specs(model, fsdp=False, multi_pod=False)
    sharded = jax.tree.map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
        params_c, specs)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    out_sharded = greedy_generate(model, sharded, {"tokens": toks}, 5)
    out_plain = greedy_generate(model, params_c, {"tokens": toks}, 5)
    np.testing.assert_array_equal(np.asarray(out_sharded),
                                  np.asarray(out_plain))
