"""Serving under test: ``launch/engine.py::ServeEngine`` under open-loop
traffic from ``bench/loadgen.py``.

Set-up makes the weights on the device from the seed (float32, as the serve
CLI builds them), builds the engine with the configuration's slots, decode
block and cache type, and warms every prefill block, the insert and the
decode executor that this run's requests will use, at the one cache length
that holds the longest request. The window submits each request when it is
due and steps the engine whenever work is pending; after the window the
engine drains what was submitted. Every request is timed from its due time.
A sample of finished requests, drawn from the seed and holding the longest,
is then compared with the plain decoder (``bench/reference/stablelm.py``).
"""
from __future__ import annotations

import sys
import time

import numpy as np

import harness as H
import loadgen
import weights as W
from decoder import model_config

sys.path.insert(0, str(H.BENCH / "reference"))
import stablelm as REF  # noqa: E402


def prefill_blocks(length: int, max_block: int):
    """(block, first) pairs the engine's power-of-two prefill runs for a
    prompt of ``length`` (``ServeEngine._prefill_group``)."""
    out, idx = [], 0
    while idx < length:
        blk = min(1 << ((length - idx).bit_length() - 1), max_block)
        out.append((blk, idx == 0))
        idx += blk
    return out


def warm_lengths(lengths, max_block: int):
    """Prompt lengths whose prefills together run every (block, first) pair
    that ``lengths`` need: each first block alone, and each later block
    behind the largest first block."""
    need = {p for n in lengths for p in prefill_blocks(n, max_block)}
    firsts = sorted(b for b, f in need if f)
    top = firsts[-1]
    return firsts + [top + b for b, f in sorted(need) if not f]


def p95(xs):
    return H.quantile(xs, 0.95)


class Cell:
    def __init__(self, run, devices):
        self.config, self.traffic = run.config, run.traffic
        self.cfg = model_config(self.config)
        s = self.config["serving"]
        self.max_batch, self.block = s["max_batch"], s["decode_block"]
        self.cache_len, self.max_prefill = s["cache_len"], s["max_prefill_block"]
        self.engine = None

    def setup(self, seed: int, seconds: float):
        import jax
        import jax.numpy as jnp
        from repro.models import layers as L
        from repro.models import transformer as T

        key, _ = H.seed_parts(seed)
        self.seed = seed
        specs = T.model_specs(self.cfg)
        shapes = jax.eval_shape(lambda k: L.init_params(specs, k, jnp.float32), key)
        self.params = W.make(key, shapes)
        self.arrivals = loadgen.arrivals(self.traffic, seed, seconds, self.cfg.vocab_size)
        longest = max(len(a.prompt) + a.max_new for a in self.arrivals)
        if longest > self.cache_len:
            raise H.BenchError(f"a request needs {longest} cache rows; the "
                               f"configuration holds {self.cache_len}")

    def warm(self, lengths=None):
        """Compile (or load) every executor the requests will use."""
        import jax.numpy as jnp
        from repro.launch.engine import ServeEngine

        if self.engine is None:
            self.engine = ServeEngine(
                self.cfg, self.params, max_batch=self.max_batch,
                cache_dtype=getattr(jnp, self.config["serving"]["cache_dtype"]),
                decode_block=self.block, temperature=0.0,
                max_prefill_block=self.max_prefill)
        eng = self.engine
        eng.params = self.params
        lengths = lengths or [len(a.prompt) for a in self.arrivals]
        rng = np.random.default_rng(0)
        warm = warm_lengths(lengths, self.max_prefill)
        # one request long enough that the decode batch's cache is sized to
        # cache_len; lengths stay distinct, so every prefill runs one row
        # (Bp = 1), as the window's prompts of distinct lengths do
        if max(warm) + 2 <= self.cache_len // 2:
            warm.append(self.cache_len // 2 + 1)
        for n in warm:
            eng.submit(rng.integers(0, self.cfg.vocab_size, n, dtype=np.int32), 2)
        eng.run()
        if eng._cache_len != self.cache_len:
            raise H.BenchError(f"the engine sized its cache to {eng._cache_len}")
        eng.done.clear()

    def window(self, run, seconds: float):
        """Offer the arrivals open loop for ``seconds``, then drain.
        Returns the requests with their due times, and what the window saw."""
        from repro.analysis.compile_guard import compile_guard

        eng, arr = self.engine, self.arrivals
        eng.done.clear()
        due, late, i = {}, [], 0
        with compile_guard(track=r"serve_") as guard:
            with run.window() as win:
                with run.span("bench_window"):
                    t0 = time.perf_counter()
                    while True:
                        now = time.perf_counter() - t0
                        while i < len(arr) and arr[i].due_s <= now:
                            with run.span("submit"):
                                rid = eng.submit(arr[i].prompt, arr[i].max_new)
                            due[rid] = t0 + arr[i].due_s
                            late.append(now - arr[i].due_s)
                            i += 1
                        if now >= seconds:
                            break
                        if eng.pending():
                            with run.span("engine step"):
                                eng.step()
                        else:
                            nxt = arr[i].due_s if i < len(arr) else seconds
                            with run.span("idle: no request pending"):
                                time.sleep(max(0.0, min(nxt, seconds) - now))
                    t_close = time.perf_counter()
            live = list(eng.done) + [r for r in eng._slots if r is not None]
            in_window = sum(len(r.tokens) for r in live)
            prefilled = sum(len(r.prompt) for r in live if r.t_admit <= t_close)
            backlog = eng.pending()
            while eng.pending():
                eng.step()
        reqs = [r for r in eng.done if r.rid in due]
        return reqs, due, {"window_s": win["window_s"], "tokens": in_window,
                           "prompt_tokens": prefilled, "backlog": backlog,
                           "late_max_s": max(late) if late else 0.0,
                           "offered": i, "compiles": guard.total,
                           "trace_dir": win.get("trace_dir")}

    def metrics(self, reqs, due, w):
        ttft = [(r.t_first - due[r.rid]) * 1e3 for r in reqs]
        queue = [(r.t_admit - due[r.rid]) * 1e3 for r in reqs]
        tpot = [(r.t_done - r.t_first) * 1e3 / (len(r.tokens) - 1)
                for r in reqs if len(r.tokens) > 1]
        return {"ttft_p95_ms": p95(ttft), "tpot_p95_ms": p95(tpot),
                "serve_tokens_per_s": w["tokens"] / w["window_s"],
                "queue_p95_ms": p95(queue), "ttft_p50_ms": H.quantile(ttft, 0.5)}

    def sample(self, reqs):
        """Finished requests to check: the longest, then others drawn from
        the seed until ``check_tokens`` served tokens are in the sample."""
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, self.seed >> 32, 11])
        order = sorted(reqs, key=lambda r: -(len(r.prompt) + len(r.tokens)))
        pick, rest = [order[0]], list(rng.permutation(len(order) - 1) + 1)
        while rest and sum(len(r.tokens) for r in pick) < self.traffic["check_tokens"]:
            pick.append(order[rest.pop()])
        return [(r.prompt, np.asarray(r.tokens, np.int32)) for r in pick]

    def reference(self, sample, control_dtype=None):
        return REF.served_gaps(self.params, sample, self.cfg.rope_theta,
                               self.cache_len, self.traffic["output_len"]["max"],
                               control_dtype=control_dtype)


def run(run, devices):
    cell = Cell(run, devices)
    with run.part("weights and traffic"):
        cell.setup(run.seed, run.seconds)
    with run.part("warm-up (compile or load every executor the traffic uses)"):
        cell.warm()
    run.end_setup()
    reqs, due, w = cell.window(run, run.seconds)
    mem = H.memory_peak(devices)
    cell.engine._state = None  # free the decode caches before the reference
    m = cell.metrics(reqs, due, w)
    run.log("window", offered=w["offered"], finished=len(reqs), backlog=w["backlog"],
            tokens=w["tokens"], window_s=w["window_s"], late_max_s=w["late_max_s"],
            compiles_in_window=w["compiles"], **m)
    t = time.perf_counter()
    sample = cell.sample(reqs)
    gaps = cell.reference(sample)
    run.log("reference", seconds=time.perf_counter() - t, requests=len(sample),
            tokens=int(sum(len(s) for _, s in sample)), gaps=gaps)
    run.check("logit_gap", max(gaps), run.limits["logit_gap"])
    failed = sum(1 for r in reqs if len(r.tokens) != r.max_new) + (w["offered"] - len(reqs))
    import counts

    # 2 FLOPs per weight a token passes (attention over the context left out)
    flops_tok = counts.decoder_flops_per_token(run.config, run.config["num_hidden_layers"], 0)
    facts = {"window_s": w["window_s"], "tokens": w["tokens"],
             "prompt_tokens": w["prompt_tokens"], "flops_per_token": flops_tok,
             "queue_p95_ms": m["queue_p95_ms"], "decode_module": "serve_decode"}
    return {"end_to_end": m, "attempted": w["offered"], "failed": failed,
            "memory_peak_bytes": mem, "facts": facts, "trace_dir": w["trace_dir"],
            "window_span": "bench_window"}
