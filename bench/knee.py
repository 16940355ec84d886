#!/usr/bin/env python3
"""Sweep of offered load for a serving cell, to find its knee (on the chip).

    python bench/knee.py --workload <cell> --rates 4,8,12 --seconds 20 --seed 1

One process: weights from the seed, every prefill block warmed once, then
for each rate a window of the cell's traffic mix at that rate. Each rate
prints one JSON line (also appended to ``<out>/knee_<cell>.jsonl``):
offered and finished requests, the backlog when the window closed, output
tokens per second, and time-to-first-token percentiles. The knee is the
highest rate whose backlog does not grow through the window. The
benchmark's own runs never run this; the cell's rate is fixed in its mix.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness as H  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=".bench_out", help="directory for the readings")
    a = ap.parse_args(argv)
    files = H.cell_files(a.workload)
    sys.path.insert(0, str(H.ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    H.enable_compile_cache()
    import loadgen

    devices, _ = H.require_chips(files["cell"]["chips"])
    run = H.Run(argparse.Namespace(workload=a.workload, seed=a.seed, seconds=a.seconds,
                                   trace=0), files, T_START)
    cell = H.load_module(H.BENCH / "systems" / "serve.py", "serve").Cell(run, devices)
    cell.setup(a.seed, a.seconds)
    p = cell.traffic["prompt_len"]
    cell.warm(lengths=list(range(p["min"], p["max"] + 1)))
    out = Path(a.out)
    out.mkdir(parents=True, exist_ok=True)
    with open(out / f"knee_{a.workload}.jsonl", "a") as log:
        for rate in [float(r) for r in a.rates.split(",")]:
            cell.traffic["rate_per_s"] = rate
            cell.arrivals = loadgen.arrivals(cell.traffic, a.seed, a.seconds,
                                             cell.cfg.vocab_size)
            reqs, due, w = cell.window(run, a.seconds)
            m = cell.metrics(reqs, due, w)
            line = json.dumps({"rate_per_s": rate, "offered": w["offered"],
                               "backlog_at_close": w["backlog"],
                               "compiles_in_window": w["compiles"], **m,
                               "window_s": w["window_s"]})
            print(line, flush=True)
            log.write(line + "\n")
            cell.engine.done.clear()


if __name__ == "__main__":
    main()
