#!/usr/bin/env python3
"""The benchmark's one command.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process runs one cell: it builds the cell from its files (see
``harness.py``), sets up and warms every shape the cell uses, measures for
``--seconds``, checks what the timed path produced against the plain
reference in ``bench/reference/``, and prints one JSON line last:

    {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"], "checks"}

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer metrics, read from the trace by ``bench/metrics/<name>.py``.
The numbers compared for ``correct`` end standard error, each beside its
limit, and close the result line under ``checks``.

It exits non-zero and prints no result when JAX finds no TPU, fewer chips
than the cell asks for, a device kind missing from ``bench/peaks.json``, or
when the program under test is not in the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness as H  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def read_per_layer(bench, workload: str, ctx) -> dict:
    """Each per-layer metric of the cell from its own reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in H.cell_metrics(bench, workload, "per_layer"):
        reader = H.load_module(H.BENCH / "metrics" / f"{m['name']}.py", m["name"])
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    files = H.cell_files(args.workload)
    src = H.ROOT / "src"
    if not (src / "repro").is_dir():
        raise H.BenchError("the program under test (src/repro) is not in the checkout")
    sys.path.insert(0, str(src))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp

    H.enable_compile_cache()
    devices, peaks = H.require_chips(files["cell"]["chips"])
    run = H.Run(args, files, T_START)
    name = files["traffic"]["system"]
    system = H.load_module(H.BENCH / "systems" / f"{name}.py", name)
    res = system.run(run, devices)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": res["memory_peak_bytes"]}
    result = {"correct": run.correct, "attempted": res["attempted"],
              "failed": res["failed"]}
    bench = files["bench"]
    if args.trace:
        TR = H.load_module(H.BENCH / "trace.py", "trace")
        red = TR.reduce(TR.load(res["trace_dir"]), res["window_span"])
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = {"trace": red, "facts": res["facts"], "peaks": peaks}
        result["metrics"] = read_per_layer(bench, args.workload, ctx)
        result["device"] = device
        result["breakdown"] = TR.breakdown(red)
        shutil.rmtree(res["trace_dir"], ignore_errors=True)
    else:
        metrics = {}
        for m in H.cell_metrics(bench, args.workload, "end_to_end"):
            value = run.setup_s if m["name"] == "setup_s" else res["end_to_end"][m["name"]]
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    for c in run.checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr, flush=True)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in run.checks}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
