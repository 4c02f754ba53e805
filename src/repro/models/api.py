"""Public model API: build any assigned architecture behind one interface.

``Model`` bundles init / loss / prefill / decode / specs.  ``input_specs``
returns ``jax.ShapeDtypeStruct`` stand-ins for every model input of a given
run shape — the dry-run lowers against these (no allocation).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig
from . import encdec as ed
from . import transformer as tf

__all__ = ["Model", "build_model", "input_specs", "batch_specs"]


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable                    # key -> params
    loss: Callable                    # (params, batch) -> scalar; decoder
                                      # LMs: with_stats=True -> (scalar, counters)
    prefill: Callable                 # (params, batch) -> (logits, caches)
    decode_step: Callable             # (params, caches, token, pos) -> (logits, caches)
    init_cache: Callable              # (batch, length) -> caches
    param_specs: Callable             # () -> pytree of PartitionSpec
    cache_specs: Callable             # () -> pytree of PartitionSpec
    decode_window: int = 0            # sliding-window size baked at build time
    # (params, pools, token, positions, page_table, kv_len, attn_fn=None)
    # -> (logits, pools); None for families without a paged decode path
    decode_step_paged: Optional[Callable] = None
    # (params, pools, tokens, pt_row, chunk_start, chunk_len, attn_fn=None)
    # -> (chunk logits, pools); one prompt chunk of one slot (DESIGN §11)
    prefill_chunk_paged: Optional[Callable] = None
    # (params, pools, token, positions, page_table, kv_len, chunk_tokens,
    #  pt_row, chunk_start, chunk_len, attn_fn=None, prefill_attn_fn=None)
    # -> (decode logits, chunk logits, pools); the fused mixed serving step
    decode_step_mixed: Optional[Callable] = None


def _frontend_tokens(cfg: ModelConfig) -> int:
    if cfg.family == "vlm":
        return cfg.n_frontend_tokens
    return 0


def build_model(cfg: ModelConfig, decode_window: int = 0,
                unroll: bool = False) -> Model:
    if cfg.family == "encdec":
        def loss(params, batch, remat=True):
            return ed.encdec_loss(cfg, params, batch, remat=remat,
                                  unroll=unroll)

        def prefill(params, batch):
            return ed.encdec_prefill(cfg, params, batch["tokens"],
                                     batch["frontend"], window=decode_window,
                                     unroll=unroll)

        def decode_step(params, caches, token, pos):
            return ed.encdec_decode_step(cfg, params, caches, token, pos,
                                         window=decode_window, unroll=unroll)

        def init_cache(batch, length):
            return ed.init_encdec_cache(cfg, batch, length,
                                        n_frames=cfg.n_frontend_tokens)

        return Model(cfg, lambda k: ed.init_encdec(cfg, k), loss, prefill,
                     decode_step, init_cache,
                     lambda: ed.encdec_param_specs(cfg),
                     lambda: ed.encdec_cache_specs(cfg),
                     decode_window=decode_window)

    nf = _frontend_tokens(cfg)

    def loss(params, batch, remat=True, remat_policy="full",
             with_stats=False):
        return tf.lm_loss(cfg, params, batch, remat=remat, unroll=unroll,
                          remat_policy=remat_policy, with_stats=with_stats)

    def prefill(params, batch):
        return tf.lm_prefill(cfg, params, batch["tokens"],
                             batch.get("frontend"), window=decode_window,
                             unroll=unroll)

    def decode_step(params, caches, token, pos):
        return tf.lm_decode_step(cfg, params, caches, token, pos,
                                 window=decode_window, unroll=unroll)

    def init_cache(batch, length):
        return tf.init_lm_cache(cfg, batch, length)

    def decode_step_paged(params, pools, token, positions, page_table,
                          kv_len, attn_fn=None):
        return tf.lm_decode_step_paged(cfg, params, pools, token, positions,
                                       page_table, kv_len,
                                       window=decode_window, unroll=unroll,
                                       attn_fn=attn_fn)

    def prefill_chunk_paged(params, pools, tokens, pt_row, chunk_start,
                            chunk_len, attn_fn=None):
        return tf.lm_prefill_chunk_paged(cfg, params, pools, tokens, pt_row,
                                         chunk_start, chunk_len,
                                         window=decode_window, unroll=unroll,
                                         attn_fn=attn_fn)

    def decode_step_mixed(params, pools, token, positions, page_table,
                          kv_len, chunk_tokens, pt_row, chunk_start,
                          chunk_len, attn_fn=None, prefill_attn_fn=None):
        return tf.lm_serve_step_mixed(cfg, params, pools, token, positions,
                                      page_table, kv_len, chunk_tokens,
                                      pt_row, chunk_start, chunk_len,
                                      window=decode_window, unroll=unroll,
                                      attn_fn=attn_fn,
                                      prefill_attn_fn=prefill_attn_fn)

    return Model(cfg, lambda k: tf.init_lm(cfg, k), loss, prefill,
                 decode_step, init_cache,
                 lambda: tf.lm_param_specs(cfg),
                 lambda: tf.lm_cache_specs(cfg),
                 decode_window=decode_window,
                 decode_step_paged=decode_step_paged,
                 prefill_chunk_paged=prefill_chunk_paged,
                 decode_step_mixed=decode_step_mixed)


# ---------------------------------------------------------------------------
# ShapeDtypeStruct input specs (dry-run stand-ins, no allocation)
# ---------------------------------------------------------------------------

def batch_specs(cfg: ModelConfig, run: RunConfig,
                agent_axis: Optional[int] = None) -> Dict[str, Any]:
    """Training/prefill batch ShapeDtypeStructs.

    agent_axis: if given, a leading per-agent axis A is prepended and the
    per-agent batch is global_batch // A (decentralized trainer layout).
    """
    B, S = run.global_batch, run.seq_len
    lead: tuple = ()
    if agent_axis:
        assert B % agent_axis == 0, (B, agent_axis)
        lead, B = (agent_axis,), B // agent_axis
    d = cfg.d_model
    fdt = jnp.dtype(cfg.dtype)
    specs: Dict[str, Any] = {
        "tokens": jax.ShapeDtypeStruct(lead + (B, S), jnp.int32),
    }
    if cfg.family == "vlm":
        specs["frontend"] = jax.ShapeDtypeStruct(
            lead + (B, cfg.n_frontend_tokens, d), fdt)
    elif cfg.family == "encdec":
        specs["frontend"] = jax.ShapeDtypeStruct(
            lead + (B, cfg.n_frontend_tokens, d), fdt)
    return specs


def input_specs(cfg: ModelConfig, run: RunConfig,
                agent_axis: Optional[int] = None) -> Dict[str, Any]:
    """Full input specs for the run mode (train/prefill: batch;
    decode: token + pos + caches)."""
    if run.mode in ("train", "prefill"):
        return batch_specs(cfg, run, agent_axis)
    # decode: one token with a seq_len-long context cache
    B = run.global_batch
    cache_len = run.decode_window or run.seq_len
    model = build_model(cfg, decode_window=run.decode_window)
    caches = jax.eval_shape(lambda: model.init_cache(B, cache_len))
    return {
        "token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
        "caches": caches,
    }
