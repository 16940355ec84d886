#!/usr/bin/env python3
"""The readings that a cell's limits are set from (on the chip).

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 [--control] [--faults half_batch]
                              [--seconds 15]

In one process, for each seed. Training cells: the program's first round
through the cell's own executor against the plain float32 reference (the
lower readings); with ``--control`` the reference computed in bfloat16 put
in the program's place (the control, which has to fail); with ``--faults``
the reference with a planted fault put in the program's place. Serving
cells: a window of ``--seconds`` at the cell's own load, then the widest
logit gap of the sampled served tokens (the lower readings) and, with
``--control``, the gap of the tokens that the bfloat16 reference puts first
at the same positions. Each reading is one JSON line on standard output,
also appended to ``<out>/calib_<cell>.jsonl``.
The benchmark's own runs never run this.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--out", default=".bench_out", help="directory for the readings")
    a = ap.parse_args(argv)
    files = H.cell_files(a.workload)
    sys.path.insert(0, str(H.ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    H.enable_compile_cache()
    import jax.numpy as jnp

    from training import readings

    devices, _ = H.require_chips(files["cell"]["chips"])
    args = argparse.Namespace(workload=a.workload, seed=0, seconds=a.seconds, trace=0)
    run = H.Run(args, files, T_START)
    name = files["traffic"]["system"]
    cell = H.load_module(H.BENCH / "systems" / f"{name}.py", name).Cell(run, devices)
    out_dir = Path(a.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    faults = [f for f in a.faults.split(",") if f]
    with open(out_dir / f"calib_{a.workload}.jsonl", "a") as log:
        def emit(seed, kind, got, t):
            line = json.dumps({"workload": a.workload, "seed": seed, "kind": kind,
                               **got, "seconds": time.perf_counter() - t})
            print(line, flush=True)
            log.write(line + "\n")

        for seed in [int(s) for s in a.seeds.split(",")]:
            t = time.perf_counter()
            if name == "serve":
                cell.setup(seed, a.seconds)
                cell.warm()
                reqs, due, w = cell.window(run, a.seconds)
                cell.engine._state = None
                sample = cell.sample(reqs)
                emit(seed, "program", {"logit_gap": max(cell.reference(sample)),
                                       "tokens": int(sum(len(x) for _, x in sample)),
                                       **cell.metrics(reqs, due, w)}, t)
                if a.control:
                    t = time.perf_counter()
                    gaps = cell.reference(sample, control_dtype=jnp.bfloat16)
                    emit(seed, "control_bf16", {"logit_gap": max(gaps)}, t)
                continue
            state = cell.setup(seed)
            cell.compile(state)
            state, losses, change = cell.first_round(state)
            del state
            ref = cell.reference(jnp.float32)
            emit(seed, "program", readings(losses, change, *ref), t)
            if a.control:
                t = time.perf_counter()
                ctl = cell.reference(jnp.bfloat16)
                emit(seed, "control_bf16", readings(ctl[0], ctl[1], *ref), t)
            for f in faults:
                t = time.perf_counter()
                bad = cell.reference(jnp.float32, fault=f)
                emit(seed, f"fault_{f}", readings(bad[0], bad[1], *ref), t)


if __name__ == "__main__":
    main()
