"""DeepSeek-V2's blocks on the training path: multi-head latent attention
with YaRN rope, and the dropless expert share, against plain jnp written
from the published equations, and the whole smoke model against the
benchmark's reference (``bench/reference/mla_moe.py``)."""
import dataclasses
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ModelConfig, RunConfig
from repro.models import build_model
from repro.models.attention import (apply_mla, init_mla, mla_softmax_scale,
                                    yarn_inv_freq)
from repro.models.moe import apply_moe_dropless, init_moe

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from bench.reference import mla_moe  # noqa: E402

HI = jax.lax.Precision.HIGHEST

MLA_CFG = dict(name="mla-tiny", family="moe", n_layers=1, d_model=64,
               n_heads=4, n_kv_heads=4, attn_kind="mla", kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
               rope_factor=40.0, rope_original_max_pos=64,
               yarn_mscale_all_dim=0.707, dtype="float32")
MOE_CFG = dict(name="moe-tiny", family="moe", n_layers=1, d_model=32,
               n_heads=2, n_kv_heads=2, d_ff=16, n_experts=8,
               experts_per_token=3, n_shared_experts=2, moe_dropless=True,
               norm_topk_prob=False, routed_scaling_factor=1.0,
               router_aux="seq", router_aux_coef=0.001, dtype="float32")


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def norm(x, w, eps=1e-6):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


# ---------------------------------------------------------------------------
# MLA
# ---------------------------------------------------------------------------

def plain_mla(p, c: ModelConfig, x):
    """MLA of arXiv:2405.04434 §2.1 for x (B, S, d), one position at a
    time in numpy-style loops over heads, YaRN frequencies from the
    formula."""
    B, S, _ = x.shape
    H, dn, dr, r, dv = (c.n_heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
                        c.kv_lora_rank, c.v_head_dim)
    f = c.rope_theta ** (-np.arange(0, dr, 2) / dr)
    corr = lambda n: dr * math.log(c.rope_original_max_pos
                                   / (n * 2 * math.pi)) / (2 * math.log(
                                       c.rope_theta))
    low = max(math.floor(corr(c.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(c.yarn_beta_slow)), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = f * (1 - ramp) + f / c.rope_factor * ramp
    ang = np.arange(S)[:, None] * inv[None]
    cos, sin = jnp.asarray(np.cos(ang)), jnp.asarray(np.sin(ang))

    def rot(t):                               # (B, S, dr)
        a, b = t[..., :dr // 2], t[..., dr // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    h = norm(x, p["ln"])
    kv_a = mm(h, p["wkv_a"])
    lat = norm(kv_a[..., :r], p["latent"]["ln"])
    k_pe = rot(kv_a[..., r:])
    m = 0.1 * c.yarn_mscale_all_dim * math.log(c.rope_factor) + 1
    s = (dn + dr) ** -0.5 * m * m
    mask = np.tril(np.ones((S, S), bool))
    outs = []
    for i in range(H):
        q = mm(h, p["wq"][:, i * (dn + dr):(i + 1) * (dn + dr)])
        kvb = mm(lat, p["wkv_b"][:, i * (dn + dv):(i + 1) * (dn + dv)])
        qi = jnp.concatenate([q[..., :dn], rot(q[..., dn:])], -1)
        ki = jnp.concatenate([kvb[..., :dn], k_pe], -1)
        sc = jnp.einsum("bqd,bkd->bqk", qi, ki, precision=HI) * s
        w = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), -1)
        outs.append(jnp.einsum("bqk,bkd->bqd", w, kvb[..., dn:],
                               precision=HI))
    return x + mm(jnp.concatenate(outs, -1), p["wo"])


def test_yarn_at_published_widths():
    cfg = get_config("deepseek_v2_lite")
    inv = yarn_inv_freq(cfg)
    f = 1e4 ** (-np.arange(0, 64, 2) / 64)
    # low = 10, high = 23: untouched below, divided by 40 from 23 on
    np.testing.assert_allclose(inv[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], f[23:] / 40, rtol=1e-6)
    r = (16 - 10) / 13
    np.testing.assert_allclose(inv[16], f[16] * (1 - r) + f[16] / 40 * r,
                               rtol=1e-6)
    assert mla_softmax_scale(cfg) == pytest.approx(0.11472, abs=5e-5)


def test_mla_matches_plain_jnp():
    c = ModelConfig(**MLA_CFG)
    p = init_mla(jax.random.PRNGKey(0), c)
    p = jax.tree.map(lambda l: l + 0.1 * jax.random.normal(
        jax.random.PRNGKey(l.size), l.shape), p)     # nonzero norm offsets
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, c.d_model))
    pos = jnp.broadcast_to(jnp.arange(10)[None], (2, 10))
    with jax.default_matmul_precision("highest"):
        got, _ = apply_mla(p, c, x, pos)
    np.testing.assert_allclose(got, plain_mla(p, c, x), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the dropless expert share
# ---------------------------------------------------------------------------

def plain_moe(p, c: ModelConfig, x, held=None):
    """The whole layer, or the part of the experts in ``held`` (global ids
    with their weights at positions 0..), token by token: softmax scores,
    top-k, gate = score, Σ gate · SwiGLU_e + shared."""
    B, S, d = x.shape
    held = range(c.n_experts) if held is None else held
    h = norm(x, p["ln"]).reshape(B * S, d)
    s = jax.nn.softmax(mm(h, p["router"]), -1)
    top = np.argsort(-np.asarray(s), axis=-1)[:, :c.experts_per_token]
    y = np.zeros((B * S, d), np.float32)
    for t in range(B * S):
        for j, e in enumerate(held):
            if e in top[t]:
                g = jax.nn.silu(mm(h[t], p["w_gate"][j])) * mm(h[t],
                                                               p["w_up"][j])
                y[t] += float(s[t, e]) * np.asarray(mm(g, p["w_down"][j]))
    sh = p["shared"]
    y = y + np.asarray(mm(jax.nn.silu(mm(h, sh["w_gate"])) * mm(h, sh["w_up"]),
                          sh["w_down"]))
    return x + y.reshape(B, S, d)


def whole_layer(seed=0):
    c = ModelConfig(**MOE_CFG)
    p = init_moe(jax.random.PRNGKey(seed), c)
    p["ln"] = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                      p["ln"].shape)
    x = jax.random.normal(jax.random.PRNGKey(seed + 2), (2, 24, c.d_model))
    return c, p, x


def share(c, p, i, n):
    """Share i of n: its config and weights."""
    held = c.n_experts // n
    ci = dataclasses.replace(c, n_experts_held=held, expert_first=i * held)
    pi = dict(p, **{k: p[k][i * held:(i + 1) * held]
                    for k in ("w_gate", "w_up", "w_down")})
    return ci, pi


def shared_part(p, x):
    sh, h = p["shared"], norm(x, p["ln"])
    return mm(jax.nn.silu(mm(h, sh["w_gate"])) * mm(h, sh["w_up"]),
              sh["w_down"])


def test_the_shares_add_up_to_the_whole_layer():
    """Four shares of two experts: each gives x + shared + its routed
    part; with x and the shared experts counted once they make the uncut
    layer's output."""
    c, p, x = whole_layer()
    n = 4
    with jax.default_matmul_precision("highest"):
        whole, aux_whole, rows_whole = apply_moe_dropless(p, c, x, 1e-6)
        outs = [apply_moe_dropless(pi, ci, x, 1e-6)
                for ci, pi in (share(c, p, i, n) for i in range(n))]
    ref = plain_moe(p, c, x)
    np.testing.assert_allclose(whole, ref, rtol=1e-4, atol=1e-4)
    common = x + shared_part(p, x)
    added = common + sum(y - common for y, _, _ in outs)
    np.testing.assert_allclose(added, ref, rtol=1e-4, atol=1e-4)
    assert sum(int(r) for _, _, r in outs) == int(rows_whole) == \
        x.shape[0] * x.shape[1] * c.experts_per_token
    # the router and its loss see every expert in every share
    np.testing.assert_allclose([float(a) for _, a, _ in outs],
                               float(aux_whole), rtol=1e-6)


def test_a_share_is_its_experts_part():
    c, p, x = whole_layer(seed=3)
    ci, pi = share(c, p, 2, 4)
    with jax.default_matmul_precision("highest"):
        y, _, rows = apply_moe_dropless(pi, ci, x, 1e-6)
    held = range(ci.expert_first, ci.expert_first + ci.n_experts_held)
    np.testing.assert_allclose(y, plain_moe(pi, c, x, held=list(held)),
                               rtol=1e-4, atol=1e-4)
    h = norm(x, p["ln"]).reshape(-1, c.d_model)
    top = jax.lax.top_k(mm(h, p["router"]), c.experts_per_token)[1]
    assert int(rows) == int(np.sum((np.asarray(top) >= ci.expert_first)
                                   & (np.asarray(top) < ci.expert_first
                                      + ci.n_experts_held)))


def test_dropless_under_forced_imbalance():
    """Every token's first choice is one held expert: all of them are
    computed, none dropped."""
    c, p, x = whole_layer(seed=5)
    v = jax.random.normal(jax.random.PRNGKey(9), (c.d_model,))
    x = 0.3 * x + v                     # every token near one direction
    router = p["router"].at[:, 3].set(4.0 * v / jnp.linalg.norm(v))
    ci, pi = share(c, dict(p, router=router), 1, 4)   # holds experts 2, 3
    with jax.default_matmul_precision("highest"):
        y, _, rows = apply_moe_dropless(pi, ci, x, 1e-6)
    h = norm(x, p["ln"]).reshape(-1, c.d_model)
    assert bool(jnp.all(jnp.argmax(mm(h, router), -1) == 3))
    assert int(rows) >= x.shape[0] * x.shape[1]
    np.testing.assert_allclose(y, plain_moe(pi, c, x, held=[2, 3]),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the whole model, the trainer's counter, the presets
# ---------------------------------------------------------------------------

def test_smoke_model_loss_and_grads_match_the_reference():
    cfg = get_smoke_config("deepseek_v2_lite")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    params = jax.tree.map(lambda l: l + 0.05 * jax.random.normal(
        jax.random.PRNGKey(l.size), l.shape, l.dtype), params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    m = dataclasses.asdict(cfg)
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(model.loss)(params,
                                                    {"tokens": tokens})
        ref, g_ref = jax.value_and_grad(
            lambda p: mla_moe.loss(p, m, tokens))(params)
    assert float(got) == pytest.approx(float(ref), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)


def test_train_step_counts_the_held_rows():
    from repro.train import build_train_step, init_state, make_gossip_schedule
    cfg = dataclasses.replace(get_smoke_config("deepseek_v2_lite"),
                              n_experts_held=0)     # every expert held here
    model = build_model(cfg)
    run = RunConfig(global_batch=2, seq_len=32, agents="data",
                    algorithm="edm", topology="ring")
    sched = make_gossip_schedule(run, 2)
    state = init_state(model, run, 2, jax.random.PRNGKey(0))
    step = jax.jit(build_train_step(model, run, sched))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 1, 32),
                                          0, cfg.vocab_size)}
    _, metrics = step(state, batch)
    moe_layers = cfg.n_layers - cfg.first_k_dense
    assert int(metrics["moe_rows_held"]) == \
        2 * 32 * cfg.experts_per_token * moe_layers


def test_param_count_is_the_parameter_tree():
    for cfg in (get_smoke_config("deepseek_v2_lite"),
                get_smoke_config("deepseek_moe_16b")):
        shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
        assert cfg.n_params() == sum(int(np.prod(s.shape))
                                     for s in jax.tree.leaves(shapes))


def test_preset_is_the_benchmark_configuration():
    bench = json.loads((ROOT / "bench/configs/deepseek_v2_lite.json")
                       .read_text())
    cfg = dataclasses.asdict(get_config("deepseek_v2_lite"))
    for key, value in bench["model"].items():
        assert cfg[key] == value, key
    defaults = dataclasses.asdict(ModelConfig(name="", family="moe",
                                              n_layers=1, d_model=1))
    differs = {k for k, v in cfg.items() if v != defaults[k]}
    assert differs - {"source"} <= set(bench["model"]), \
        differs - set(bench["model"])


def test_the_paged_engine_refuses_mla():
    from repro.serve.paged_cache import PagedCacheConfig
    from repro.serve.scheduler import ContinuousBatchingEngine
    model = build_model(get_smoke_config("deepseek_v2_lite"))
    pcfg = PagedCacheConfig(page_size=8, num_pages=16, max_slots=2,
                            max_context=32)
    with pytest.raises(NotImplementedError, match="latent paged cache"):
        ContinuousBatchingEngine(model, None, pcfg)
