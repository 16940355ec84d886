"""Shared plumbing of the benchmark: the cell's files, the chip check, the
compile cache, set-up logging, tracing and the result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json``   -- the configuration as it is run
* ``bench/traffic/<traffic>.json``  -- the traffic mix (parameters only)
* ``bench/systems/<system>.py``     -- the module that drives the system the
  mix names (its ``"system"`` key): set-up, the window and the check
* ``bench/limits/<workload>.json``  -- the limits of the correctness check
* ``bench/metrics/<metric>.py``     -- one reader per per-layer metric

so adding a cell, a mix, a configuration or a metric adds files and never
edits one.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class BenchError(SystemExit):
    """A run that cannot produce a result: exits non-zero, prints none."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file of the benchmark by path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(f"bench_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_files(workload: str, bench_json: Optional[Path] = None) -> Dict[str, Any]:
    """The cell's entry, configuration, traffic mix and limits, by name."""
    bench = load_json(bench_json or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    return {
        "bench": bench,
        "cell": cell,
        "config": load_json(ROOT / entry["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "limits": load_json(BENCH / "limits" / f"{workload}.json"),
    }


def cell_metrics(bench: Dict, workload: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    if kind == "end_to_end":
        return [m for m in bench["end_to_end"]
                if "workloads" not in m or workload in m["workloads"]]
    e2e = {m["name"] for m in cell_metrics(bench, workload, "end_to_end")}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in e2e)]


def peaks_for(kind: str) -> Dict[str, Any]:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def seed_parts(seed: int):
    """(jax key, numpy seed) from a seed of up to 64 bits."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return key, seed % (2 ** 32)


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile (numpy's default) of a non-empty list."""
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


class Run:
    """One process of the benchmark: arguments, set-up log, checks."""

    def __init__(self, args, files: Dict[str, Any], t_start: float):
        self.workload = args.workload
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.t_start = t_start
        self.config = files["config"]
        self.traffic = files["traffic"]
        self.limits = files["limits"]
        self.checks: List[Dict[str, Any]] = []
        self.setup_s: Optional[float] = None

    # -- logging ----------------------------------------------------------

    def log(self, what: str, **numbers) -> None:
        print(f"[{what}] " + json.dumps(numbers, default=float), file=sys.stderr,
              flush=True)

    @contextlib.contextmanager
    def part(self, name: str):
        """Time one part of set-up (data, weights, a compile, warm-up)."""
        t = time.perf_counter()
        yield
        self.log("setup", part=name, seconds=time.perf_counter() - t)

    def end_setup(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start
        self.log("setup", part="total", seconds=self.setup_s)

    # -- correctness ------------------------------------------------------

    def check(self, name: str, value: float, limit: float) -> None:
        """A compared number beside its limit; ``correct`` needs value <= limit."""
        value = float(value)
        self.checks.append({"name": name, "value": value, "limit": float(limit),
                            "ok": bool(value <= limit)})

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c["ok"] for c in self.checks)

    # -- tracing ----------------------------------------------------------

    @contextlib.contextmanager
    def window(self):
        """The measured window, under the profiler when ``--trace 1``."""
        import jax

        out = {}
        if self.trace:
            d = TRACE_DIR / self.workload
            shutil.rmtree(d, ignore_errors=True)
            d.mkdir(parents=True)
            out["trace_dir"] = d
            jax.profiler.start_trace(str(d))
        t0 = time.perf_counter()
        try:
            yield out
        finally:
            out["window_s"] = time.perf_counter() - t0
            if self.trace:
                jax.profiler.stop_trace()

    @staticmethod
    def span(name: str):
        """A host span on the profiler's clock (a no-op when not tracing)."""
        import jax

        return jax.profiler.TraceAnnotation(name)


def enable_compile_cache() -> str:
    """The persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else ``<checkout>/.jax_cache`` -- a fixed path, since the directory is
    part of each entry's key. Every program is cached, however fast it
    compiled, so that a cell's second run finds all of them."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(n: int):
    """The accelerator the cell asks for, or no result at all."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < n:
        raise BenchError(f"needs {n} chips; JAX found {len(devs)}")
    peaks = peaks_for(devs[0].device_kind)
    return devs[:n], peaks


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)
