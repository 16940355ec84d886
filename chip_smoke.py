#!/usr/bin/env python3
"""Smoke run of the system's main paths on one TPU chip.

    python chip_smoke.py               # phases 1-3 on one chip
    python chip_smoke.py --four-chips  # phase 4 only, on a host with 4 chips

Phases, each through the entry points a user calls:

1. e-health C-HSGD (the paper's path): ``repro.launch.train.main`` at the
   CLI's fleet defaults (10 groups x 64 devices), once plain and once private
   (DP clip + noise, secure aggregation). Checks a finite, falling loss, that
   the compiled round holds the compress kernel (``tpu_custom_call``), and
   the kernel's output for one exchange matrix against the jitted reference.
2. LLM-scale HSGD: ``llm_hybrid`` on stablelm-1.6b at its published widths,
   depth cut to fit the chip, two ``LLMRoundRunner.run_fixed`` rounds.
3. Serving: ``ServeEngine`` on stablelm-1.6b at full depth and width. Two
   3000-token prompts prefill through the compiled flash kernel and must
   decode the same greedy tokens as the token-by-token parity path; then
   four short requests.
4. (``--four-chips``) the group-sharded federation on a 4x1 mesh against the
   same run on one device.

The script fails unless JAX's first device is a TPU, and any failed check
exits non-zero. The last line of its output is one JSON object naming the
device. Each process holds the chip alone, so everything runs in this one.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

STABLELM = "stablelm-1.6b"
# Depth of the llm_hybrid combined model in phase 2. The round compiled for
# v5e needs 9.55 GB at 4 layers and 12.73 GB at 8 (memory_analysis: arguments
# + outputs + temporaries - aliased), about 0.8 GB a layer, so 8 leaves
# ~4 GB of the chip's 16 GiB free. Widths stay published.
LLM_LAYERS = 8


def log(phase: str, **numbers) -> None:
    print(f"[{phase}] " + json.dumps(numbers, default=float), flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


# ---------------------------------------------------------------------------
# Phase 1: e-health C-HSGD
# ---------------------------------------------------------------------------

EHEALTH_ARGS = ["--algorithm", "c-hsgd", "--model", "paper-cnn",
                "--dataset", "organamnist", "--rounds", "4"]
PRIVATE_ARGS = ["--dp-clip", "1.0", "--dp-sigma", "1.0", "--secure-agg"]


def _compress_parity(mat, k: int, levels: int, noise=None):
    """(mismatches, max |diff|, bound) of the compiled kernel against the
    jitted reference on one exchange matrix.

    ``core/compression.py`` promises bit-identity, so 0 mismatches is the
    expectation. The bound admits what a one-ulp difference in the quantizer's
    division can do: move an entry by one grid step of its row, at most
    2 * max|x_row| / (levels - 1). A wrong threshold or mask moves entries by
    up to |x| and breaks it.
    """
    from repro.core.compression import compress_rows_ref
    from repro.kernels.compress import fused_compress_pallas

    dp = {} if noise is None else {"dp_clip": jnp.float32(1.0),
                                   "dp_sigma": jnp.float32(1.0),
                                   "dp_noise": noise}
    got = fused_compress_pallas(mat, k, levels, **dp)
    want = jax.jit(compress_rows_ref, static_argnames="levels")(
        mat, k, levels=levels, **dp)
    got, want = np.asarray(got), np.asarray(want)
    diff = np.abs(got - want)
    span = np.abs(np.asarray(mat)).max(axis=1, keepdims=True)
    if noise is not None:  # clipping only shrinks a row; noise adds to it
        span = span + np.abs(np.asarray(noise)).max(axis=1, keepdims=True)
    bound = 2.0 * span / (levels - 1) + 1e-6
    return int((got != want).sum()), float(diff.max()), bool((diff <= bound).all())


def _exchange_matrix(runner, model, state, data, fed):
    """(matrix, k, levels): the widest row group of one uncompressed exchange
    message, i.e. the matrix ``compress_pytree`` hands the kernel."""
    from repro.core.hsgd import exchange

    msg = exchange(model, state, data, fed).stale
    leaves = jax.tree_util.tree_leaves(msg)
    n = max(leaf.shape[-1] for leaf in leaves)
    mat = jnp.concatenate([leaf.astype(jnp.float32).reshape(-1, n)
                           for leaf in leaves if leaf.shape[-1] == n])
    k = max(1, round(runner.train.compression_k * n))
    return mat, k, runner.train.quantization_bits


def phase_ehealth(extra_args=()):
    from repro.core import federation as F
    from repro.core.baselines import make_runner
    from repro.core.hsgd import init_state, make_group_weights
    from repro.launch import train as TR

    base = EHEALTH_ARGS + list(extra_args)
    out = {}
    for name, argv in (("plain", base), ("private", base + PRIVATE_ARGS)):
        m = TR.main(argv)
        out[name] = {"loss_first": m["train_loss_first"],
                     "loss_final": m["train_loss_final"], "steps": m["steps"],
                     "auc_roc": m.get("auc_roc"), "wall_s": m["wall_s"]}
        check(np.isfinite([m["train_loss_first"], m["train_loss_final"]]).all(),
              f"{name} e-health loss is finite")
        check(m["train_loss_final"] < m["train_loss_first"],
              f"{name} e-health loss falls ({m['train_loss_first']} -> "
              f"{m['train_loss_final']})")

    # The same round executors main ran, rebuilt from the same functions:
    # their compiled HLO must hold the compress kernel.
    args = TR.parse_args(base)
    _, fed, train, model, _, _, data = TR.ehealth_setup(args)
    runner, fed = make_runner(args.algorithm, model, fed, train)
    w = make_group_weights(data)
    state = init_state(jax.random.PRNGKey(args.seed), model, fed, data)
    P, Q = fed.local_interval * fed.lam, fed.local_interval
    lr = jnp.float32(args.lr)
    plain_fn = runner.round_fn(P, Q, collect_stats=False)
    private_fn = runner.round_fn(P, Q, collect_stats=False, dp=True,
                                 secure_agg=True)
    masks = F.secure_agg_masks(state.theta2, args.seed, 0)
    hlo = {
        "plain": plain_fn.lower(state, data, w, lr).compile().as_text(),
        "private": private_fn.lower(
            state, data, w, lr, dp_clip=jnp.float32(1.0),
            dp_sigma=jnp.float32(1.0), agg_masks=masks).compile().as_text(),
    }
    for name, text in hlo.items():
        out[name]["round_has_tpu_custom_call"] = "tpu_custom_call" in text

    mat, k, levels = _exchange_matrix(runner, model, state, data, fed)
    noise = jax.random.normal(jax.random.PRNGKey(7), mat.shape, jnp.float32)
    for name, nz in (("plain", None), ("private", noise)):
        mism, dmax, within = _compress_parity(mat, k, levels, nz)
        out[name].update({"parity_matrix": list(mat.shape),
                          "parity_mismatches": mism, "parity_max_diff": dmax,
                          "parity_within_one_step": within})
        check(within, f"{name} compress kernel agrees with the jitted reference")
        check(out[name]["round_has_tpu_custom_call"],
              f"{name} compiled round contains tpu_custom_call")
    for name in ("plain", "private"):
        log(f"phase1 e-health {name}", **out[name])
    return out


# ---------------------------------------------------------------------------
# Phase 2: LLM-scale HSGD on stablelm-1.6b widths
# ---------------------------------------------------------------------------


def phase_llm(cfg=None, num_layers: int = LLM_LAYERS, batch: int = 4,
              seq: int = 1024, P: int = 4, Q: int = 2, rounds: int = 2,
              lr: float = 0.01):
    from repro.common.config import get_config
    from repro.data.synthetic import llm_batch_fn
    from repro.launch.steps import LLMRoundRunner, init_llm_params
    from repro.models.split_model import llm_hybrid

    full = cfg or get_config(STABLELM)
    cfg = full.replace(num_layers=num_layers)
    log("phase2 llm config", arch=full.name, num_layers=cfg.num_layers,
        published_layers=full.num_layers, d_model=cfg.d_model,
        num_heads=cfg.num_heads, d_ff=cfg.d_ff, vocab_size=cfg.vocab_size,
        cut=f"depth {full.num_layers} -> {cfg.num_layers}", batch=batch,
        seq=seq, P=P, Q=Q, compression_k=0.25, quant_levels=128)
    # remat on (llm_hybrid's default; the CLI turns it off): without it the
    # round saves every layer's [4, 32, 1024, 1024] f32 attention scores and
    # needs 18.5 GB at 4 layers
    model = llm_hybrid(cfg, n_tower=1, remat=True)
    params = init_llm_params(jax.random.PRNGKey(0), model, n_pods=1)
    batch_fn = llm_batch_fn(cfg, batch, seq, n_pods=1, seed=0)
    runner = LLMRoundRunner(model, n_pods=1)

    fn = runner.round_fn(P, Q, 0.25, 128, collect_stats=False)
    # lowered with the same argument types run_fixed passes, so the persistent
    # compile cache serves run_fixed's compile of this round
    compiled = fn.lower(params, batch_fn(0, P // Q), lr).compile()
    mem = compiled.memory_analysis()
    mem_gb = {k: round(getattr(mem, k) / 1e9, 3) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes")} if mem else {}

    t0 = time.perf_counter()
    params, losses = runner.run_fixed(params, batch_fn, steps=rounds * P, P=P,
                                      Q=Q, lr=lr, compression_k=0.25,
                                      quant_levels=128)
    jax.block_until_ready(params)
    losses = np.asarray(losses)
    out = {"losses": losses.tolist(), "wall_s": time.perf_counter() - t0,
           "round_memory_gb": mem_gb,
           "round_has_tpu_custom_call": "tpu_custom_call" in compiled.as_text()}
    log("phase2 llm", **out)
    check(np.isfinite(losses).all(), "LLM-scale HSGD loss is finite")
    check(out["round_has_tpu_custom_call"],
          "LLM round contains tpu_custom_call")
    return out


# ---------------------------------------------------------------------------
# Phase 3: serving stablelm-1.6b
# ---------------------------------------------------------------------------

# Flash kernel vs the float32 online-softmax reference. The kernel runs at
# the serving path's default matmul precision, where the MXU may round f32
# operands to bf16 (8-bit mantissa, relative error 2^-9). With unit-normal
# q, k, v and scale D^-1/2 the scores are O(1) and carry an error of about
# 2e-3; the output of a row whose softmax rests on a few keys moves by up to
# 2^-9 * max|v| ~ 1e-2, and the check takes the maximum over ~6M outputs.
# 5e-2 leaves margin over that; a wrong causal or window mask moves the
# outputs of early rows, which attend a handful of keys, by O(1).
FLASH_ATOL = 5e-2


def _flash_parity(heads: int, head_dim: int):
    from repro.kernels.ops import flash_attention
    from repro.models.attention import _blockwise_sdpa

    out = {}
    for S, window in ((2048, 0), (3000, 0), (3000, 1024)):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(S + window), 3)
        q, k, v = (jax.random.normal(kx, (1, S, heads, head_dim), jnp.float32)
                   for kx in (kq, kk, kv))
        pos = jnp.arange(S, dtype=jnp.int32)[None]
        scale = head_dim ** -0.5
        got = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, scale=scale, window=window))(q, k, v)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda q, k, v: _blockwise_sdpa(
                q, k, v, pos, pos, scale, window))(q, k, v)
        err = float(jnp.max(jnp.abs(got - want)))
        out[f"S{S}_w{window}"] = err
        check(err <= FLASH_ATOL,
              f"flash kernel within {FLASH_ATOL} of _blockwise_sdpa "
              f"(S={S}, window={window}): {err}")
    return out


def phase_serve(cfg=None, prompt_len: int = 3000, gen: int = 32,
                short_len: int = 24, short_gen: int = 16):
    from repro.common.buckets import pow2_ceil
    from repro.common.config import get_config
    from repro.launch.engine import ServeEngine, sequential_generate
    from repro.launch.serve import build_inputs
    from repro.models import attention as A

    cfg = cfg or get_config(STABLELM)
    check(prompt_len > A.BLOCKWISE_THRESHOLD,
          "long prompts reach the flash prefill path")
    params, prompts, _ = build_inputs(cfg, 2, prompt_len, seed=0)
    out = {"num_layers": cfg.num_layers, "d_model": cfg.d_model,
           "vocab_size": cfg.vocab_size, "prompt_len": prompt_len, "gen": gen}
    out["flash_max_abs_err"] = _flash_parity(cfg.num_heads,
                                             cfg.resolved_head_dim)

    # Greedy parity at float32 matmul precision on both sides: at the
    # default bf16-pass precision the two paths' different association
    # orders move logits by ~1e-2, and random-weight logits over a 100k
    # vocabulary have near-ties that such noise flips.
    with jax.default_matmul_precision("highest"):
        # One decode slot, one token per decode block. Compiled for v5e, a
        # block of 2 or more steps needs 10.6 GB of temporaries for one
        # 4096-token f32 slot (about a copy of the layer weights and four of
        # the cache), a block of 1 needs 3.3 GB, and the f32 weights take
        # 5.8 GB.
        engine = ServeEngine(cfg, params, max_batch=1,
                             cache_dtype=jnp.float32, decode_block=1)
        t0 = time.perf_counter()
        toks, rep = engine.generate(list(prompts), gen)
        out["long_wall_s"] = time.perf_counter() - t0
        out["long_prefill_s"] = [r["prefill_s"] for r in rep["requests"]]
        out["compiled_executors"] = rep["compiled_executors"]
        del engine
        ref = np.asarray(sequential_generate(
            cfg, params, jnp.asarray(prompts), gen, cache_dtype=jnp.float32,
            cache_len=pow2_ceil(prompt_len + gen)))
    same = [list(map(int, t)) == ref[i].tolist() for i, t in enumerate(toks)]
    out["greedy_tokens_match"] = same
    out["sample_tokens"] = [t[:8] for t in toks]
    check(all(len(t) == gen for t in toks), "each long request got its tokens")
    check(all(same), "engine greedy tokens match the sequential parity path")

    rng = np.random.RandomState(1)
    short = [rng.randint(0, cfg.vocab_size, (short_len,)).astype(np.int32)
             for _ in range(4)]
    engine = ServeEngine(cfg, params, max_batch=4)
    t0 = time.perf_counter()
    stoks, srep = engine.generate(short, short_gen)
    out["short_wall_s"] = time.perf_counter() - t0
    out["short_tokens_per_s"] = srep["tokens_per_s"]
    check(all(len(t) == short_gen for t in stoks),
          "each short request got its tokens")
    log("phase3 serve", **out)
    return out


# ---------------------------------------------------------------------------
# Phase 4: group-sharded federation on four chips
# ---------------------------------------------------------------------------


def phase_four_chips(groups: int = 8, devices: int = 64, samples: int = 2048,
                     rounds: int = 4):
    """``HSGDRunner.run`` on a 4x1 ("data", "model") mesh vs one device,
    and the mesh's compress kernel against the jitted reference.

    ``groups`` is a multiple of 4 so the group axis M genuinely shards (the
    CLI default of 10 would fall back to replication)."""
    from repro.common.sharding import mesh_context
    from repro.core.baselines import make_runner
    from repro.core.compression import compress_rows_ref
    from repro.core.hsgd import init_state, make_group_weights, place_on_mesh
    from repro.kernels.compress import compress_rows
    from repro.launch import train as TR
    from repro.launch.mesh import make_mesh

    args = TR.parse_args(EHEALTH_ARGS + ["--groups", str(groups), "--devices",
                                         str(devices), "--samples", str(samples)])
    _, fed, train, model, _, _, data = TR.ehealth_setup(args)
    runner, fed = make_runner(args.algorithm, model, fed, train)
    w = make_group_weights(data)
    mesh = make_mesh((4, 1), ("data", "model"), devices=jax.devices()[:4])

    def fresh():
        return init_state(jax.random.PRNGKey(args.seed), model, fed, data)

    # Under a mesh the kernel runs per device on a block of rows; rows are
    # independent, so the result must equal the reference bit for bit.
    mat, k, levels = _exchange_matrix(runner, model, fresh(), data, fed)
    with mesh_context(mesh):
        got = jax.jit(lambda x: compress_rows(x, k, levels))(mat)
    want = jax.jit(compress_rows_ref, static_argnames="levels")(
        mat, k, levels=levels)
    mismatches = int(np.sum(np.asarray(got) != np.asarray(want)))

    # Both runs at float32 matmul precision. At the default precision the
    # MXU rounds f32 operands to bf16, so a one-ulp difference from summing
    # across groups in another order can move an operand by 2^-9 relative;
    # the quantizer's grid turns such moves into whole grid steps. On a v5e
    # the sharded losses then drifted from one device's by 4e-6 at the third
    # step and by 2e-3 at the sixteenth, while the same comparison in full
    # f32 on the CPU stays near 1e-7. A sharding fault moves the losses by
    # far more than 1e-5, so full precision keeps the check able to see it.
    with jax.default_matmul_precision("highest"):
        _, l_one = runner.run(fresh(), data, w, rounds=rounds)
        st, l_mesh = runner.run(fresh(), data, w, rounds=rounds, mesh=mesh)
    l_one, l_mesh = np.asarray(l_one), np.asarray(l_mesh)
    leaves = jax.tree_util.tree_leaves(st.theta0)
    n_shards = [len(x.sharding.device_set) for x in leaves]
    replicated = [x.sharding.is_fully_replicated for x in leaves]

    # collectives around the compress kernel in the group-sharded round
    s2, d2, w2 = place_on_mesh(fresh(), data, w, mesh)
    P, Q = fed.local_interval * fed.lam, fed.local_interval
    with mesh_context(mesh):
        text = runner.round_fn(P, Q, collect_stats=False).lower(
            s2, d2, w2, jnp.float32(args.lr)).compile().as_text()
    hlo = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
           for op in ("all-gather", "all-reduce", "reduce-scatter",
                      "all-to-all", "collective-permute")}
    hlo["tpu_custom_call"] = text.count('custom_call_target="tpu_custom_call"')
    rel = float(np.max(np.abs(l_one - l_mesh) / np.maximum(np.abs(l_one), 1e-30)))
    out = {"groups": groups, "devices_per_group": devices, "steps": len(l_one),
           "matmul_precision": "highest", "loss_one": l_one.tolist(),
           "loss_mesh": l_mesh.tolist(), "max_rel_diff": rel,
           "n_shards": sorted(set(n_shards)),
           "theta0_fully_replicated": any(replicated),
           "mesh_compress_matrix": list(mat.shape),
           "mesh_compress_mismatches": mismatches, "round_hlo": hlo}
    log("phase4 four-chip", **out)
    check(mismatches == 0, "the mesh's compress kernel equals the reference")
    np.testing.assert_allclose(l_mesh, l_one, rtol=1e-5)
    check(all(n == 4 for n in n_shards), "every theta0 leaf spans 4 devices")
    check(not any(replicated), "theta0 is sharded over the group axis")
    check(hlo["tpu_custom_call"] > 0, "group-sharded round runs the kernel")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the group-sharded four-chip phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    want = 4 if args.four_chips else 1
    check(len(jax.devices()) >= want, f"{want} TPU chip(s) present")
    from repro.common.backend import enable_compile_cache

    cache = enable_compile_cache()
    log("device", platform=dev.platform, kind=dev.device_kind,
        count=len(jax.devices()), jax=jax.__version__, compile_cache=cache)
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips()
    else:
        for name, phase in (("phase1", phase_ehealth), ("phase2", phase_llm),
                            ("phase3", phase_serve)):
            t = time.perf_counter()
            phase()
            log(f"{name} done", seconds=time.perf_counter() - t)
    log("total", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
