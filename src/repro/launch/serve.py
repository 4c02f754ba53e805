"""Serving launcher: batched prefill + greedy decode, or the
continuous-batching engine (DESIGN §10).

  # dense reference path (seed behavior)
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_14b --smoke \
      --batch 4 --prompt-len 32 --new-tokens 16 [--window 16]

  # continuous batching over the paged KV cache
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_14b --smoke \
      --continuous-batching --max-slots 8 --page-size 8 --requests 16 \
      [--rate 50] [--window 16] [--ckpt consensus.npz]

  # chunked prefill fused into the decode dispatch (DESIGN §11)
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_14b --smoke \
      --continuous-batching --prefill-chunk 8 --max-step-tokens 16 \
      --prompt-dist exact --max-slots 8 --page-size 8 --requests 16

:func:`main` takes an argv list and returns the run's record: the
engine's metrics and the engine itself (its ``completed`` tokens per
request) for continuous batching, the generated ids otherwise.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serve import greedy_generate


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--window", type=int, default=0,
                    help="sliding-window KV cache size (0 = full)")
    ap.add_argument("--ckpt", default=None,
                    help="consensus-exported params .npz "
                         "(train.checkpoint.export_consensus)")
    # continuous-batching engine (DESIGN §10)
    ap.add_argument("--continuous-batching", action="store_true",
                    help="serve a Poisson request trace through the paged "
                         "continuous-batching engine instead of one fixed "
                         "batch")
    ap.add_argument("--page-size", type=int, default=8,
                    help="KV page rows (multiple of 8)")
    ap.add_argument("--max-slots", type=int, default=8,
                    help="concurrent decode slots")
    ap.add_argument("--requests", type=int, default=16,
                    help="requests in the Poisson trace")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--attn-impl", choices=("ref", "pallas"), default="ref")
    # chunked prefill (DESIGN §11)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: fixed chunk width in tokens "
                         "(None = legacy per-request exact-length prefill)")
    ap.add_argument("--max-step-tokens", type=int, default=None,
                    help="per-dispatch token budget (chunk + live decodes); "
                         "None = uncapped")
    ap.add_argument("--prompt-dist", choices=("bucket", "exact"),
                    default="bucket",
                    help="prompt-length draw: 'bucket' keeps compiles "
                         "bounded for the legacy path, 'exact' is a length "
                         "continuum (chunked path serves it compile-free)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg, decode_window=args.window)
    if args.ckpt:
        from repro.train import checkpoint
        like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        params = jax.tree.map(jnp.asarray,
                              checkpoint.load_consensus(args.ckpt, like))
        print(f"loaded consensus params from {args.ckpt}")
    else:
        params = model.init(jax.random.PRNGKey(0))

    if args.continuous_batching:
        from repro.serve import (ContinuousBatchingEngine, PagedCacheConfig,
                                 poisson_load)
        max_prompt, max_new = 32, 32
        ctx = args.window or max_prompt + max_new
        pcfg = PagedCacheConfig(
            page_size=args.page_size,
            num_pages=1 + args.max_slots * (-(-ctx // args.page_size)),
            max_slots=args.max_slots, max_context=ctx, window=args.window)
        eng = ContinuousBatchingEngine(model, params, pcfg,
                                       attn_impl=args.attn_impl,
                                       prefill_chunk=args.prefill_chunk,
                                       max_step_tokens=args.max_step_tokens)
        reqs = poisson_load(args.requests, args.rate, vocab=cfg.vocab_size,
                            prompt_buckets=(max_prompt // 2, max_prompt),
                            new_token_buckets=(4, 8, 16, max_new),
                            prompt_dist=args.prompt_dist, seed=1)
        metrics = eng.run(reqs)
        pf = (f"chunked(C={args.prefill_chunk})"
              if args.prefill_chunk else "per-request")
        print(f"arch={cfg.name} engine=continuous slots={args.max_slots} "
              f"page={args.page_size} window={args.window or 'full'} "
              f"attn={args.attn_impl} prefill={pf} "
              f"compiles={metrics['compile_count']}")
        print("serve metrics: " + json.dumps(metrics))
        print(f"generated {metrics['tokens']} tokens over "
              f"{metrics['requests']} requests "
              f"({metrics['tokens_per_s']} tok/s)")
        return {"metrics": metrics, "engine": eng}

    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size)}
    if cfg.family in ("vlm", "encdec"):
        batch["frontend"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, cfg.n_frontend_tokens, cfg.d_model),
            dtype=jnp.dtype(cfg.dtype))

    t0 = time.time()
    out = greedy_generate(model, params, batch, n_steps=args.new_tokens)
    dt = time.time() - t0
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"window={args.window or 'full'}")
    print(f"generated {args.new_tokens} tokens/request in {dt:.2f}s "
          f"({args.batch * args.new_tokens / dt:.1f} tok/s)")
    for i in range(min(args.batch, 4)):
        print(f"  req{i}: {out[i].tolist()}")
    return {"generated": out}


if __name__ == "__main__":
    main()
