"""Serving launcher: thin CLI over the compiled serving engine.

Runs a (reduced) architecture through the continuous-batching engine —
batched single-pass prefill + scan-based donated decode with on-device
sampling — and reports per-request latency, aggregate tokens/s, and the
executor-cache compile counts. ``--sequential`` runs the reconstructed
pre-PR token-by-token path instead (the benchmark baseline).

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --smoke \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.backend import enable_compile_cache
from repro.common.config import get_config
from repro.launch.engine import (CACHE_DTYPES, ServeEngine, parse_cache_dtype,
                                 sequential_decode, sequential_prefill,
                                 sequential_step_fn)
from repro.models import layers as L
from repro.models import transformer as T


def build_inputs(cfg, batch: int, prompt_len: int, seed: int = 0):
    """(params, prompts, extra_embeds) for a serve run — shared with
    benchmarks/bench_serve.py so the CLI and the benchmark can't diverge."""
    key = jax.random.PRNGKey(seed)
    params = L.init_params(T.model_specs(cfg), key, jnp.float32)
    rng = np.random.RandomState(seed)
    prompts = rng.randint(0, cfg.vocab_size, (batch, prompt_len)).astype(np.int32)
    extra = None
    if cfg.family == "audio":
        extra = rng.randn(batch, cfg.encoder_seq, cfg.d_model).astype(np.float32)
    return params, prompts, extra


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cache-dtype", default="bf16",
                    help=f"one of {sorted(CACHE_DTYPES)} (int8 = quantized caches)")
    ap.add_argument("--decode-block", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=0,
                    help="decode slots (0 = --batch)")
    ap.add_argument("--spec-gamma", type=int, default=0,
                    help="self-speculative draft length (0 = off; greedy only)")
    ap.add_argument("--spec-draft-layers", type=int, default=0,
                    help="truncated-depth draft layers (0 = num_layers // 2)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="seed caches from previously-seen pow2 prompt heads")
    ap.add_argument("--sequential", action="store_true",
                    help="run the reconstructed pre-PR token-by-token path")
    args = ap.parse_args(argv)

    # validate EARLY with the supported-name list, not a jnp.dtype traceback
    # from deep inside cache init
    try:
        cache_dtype = parse_cache_dtype(args.cache_dtype)
    except ValueError as e:
        ap.error(str(e))

    cfg = get_config(args.arch, smoke=args.smoke)
    params, prompts, extra = build_inputs(cfg, args.batch, args.prompt_len, args.seed)

    if args.sequential:
        step = sequential_step_fn(cfg)
        t0 = time.perf_counter()
        logits, caches = sequential_prefill(
            cfg, params, jnp.asarray(prompts), args.prompt_len + args.gen,
            extra, cache_dtype, step=step)
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0
        t0 = time.perf_counter()
        toks = sequential_decode(cfg, params, logits, caches, args.prompt_len,
                                 args.gen, args.temperature, args.seed, step=step)
        t_decode = max(time.perf_counter() - t0, 1e-9)
        report = {
            "arch": args.arch,
            "mode": "sequential",
            "batch": args.batch,
            "prefill_s": round(t_prefill, 3),
            "decode_tok_per_s": round(args.batch * args.gen / t_decode, 1),
            "ms_per_decode_step": round(1000 * t_decode / max(args.gen, 1), 2),
            "wall_s": round(t_prefill + t_decode, 3),
            "sample_output": np.asarray(toks[0, :8]).tolist(),
        }
        print(json.dumps(report, indent=1))
        return report

    engine = ServeEngine(
        cfg, params, max_batch=args.max_batch or args.batch,
        cache_dtype=cache_dtype,
        decode_block=args.decode_block, temperature=args.temperature,
        seed=args.seed, spec_gamma=args.spec_gamma,
        spec_draft_layers=args.spec_draft_layers or None,
        prefix_cache=args.prefix_cache,
    )
    toks, rep = engine.generate(list(prompts), args.gen, extra_embeds=extra)
    prefill_s = max((r["prefill_s"] for r in rep["requests"]), default=0.0)
    decode_s = max(rep["wall_s"] - prefill_s, 1e-9)
    report = {
        "arch": args.arch,
        "mode": "engine",
        "batch": args.batch,
        "prefill_s": round(prefill_s, 3),
        # decode-only rate (same basis as ms_per_decode_step and
        # bench_serve.py); end-to-end throughput is tokens_per_s_e2e
        "decode_tok_per_s": round(rep["generated_tokens"] / decode_s, 1),
        "tokens_per_s_e2e": rep["tokens_per_s"],
        "ms_per_decode_step": round(1000 * decode_s / max(args.gen, 1), 2),
        "wall_s": rep["wall_s"],
        "requests": rep["requests"],
        "compiled_executors": rep["compiled_executors"],
        "sample_output": toks[0][:8],
    }
    for k in ("speculative", "prefix_cache"):
        if k in rep:
            report[k] = rep[k]
    print(json.dumps(report, indent=1))
    return report


if __name__ == "__main__":
    main()
