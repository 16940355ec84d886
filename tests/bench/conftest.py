"""The benchmark's own modules import each other by bare name, as
``bench/run.py`` arranges it; the tests arrange the same (at the end of the
path, so that no module of the benchmark hides one of the standard
library's, such as ``trace``)."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[2] / "bench"
for p in (BENCH, BENCH / "reference"):
    if str(p) not in sys.path:
        sys.path.append(str(p))


import argparse  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

import harness as H  # noqa: E402

# Each cell at a size a CPU test run can hold: the same files, with the
# fleet, the widths and the lengths cut. Cells of BENCHMARK.json keep their
# limits; the systems that no cell runs yet (LLM-scale training, serving)
# are driven through their configuration and mix with limits for this size.
TINY_DECODER = dict(hidden_size=128, num_attention_heads=8, num_key_value_heads=8,
                    intermediate_size=256, vocab_size=512, num_hidden_layers=2)
NOT_IN_BENCHMARK = {
    # workload: (configuration, traffic mix, limits at the test's size)
    "stablelm-d8-chsgd": ("stablelm-1.6b-d8", "chsgd-token-rounds",
                          {"loss_gap": 1e-3, "change_gap": 0.05}),
    "stablelm-d8-chat": ("stablelm-1.6b-d8", "chat", {"logit_gap": 0.05}),
}


def cell_files(workload):
    if workload not in NOT_IN_BENCHMARK:
        return H.cell_files(workload)
    config, traffic, limits = NOT_IN_BENCHMARK[workload]
    return {"bench": H.load_json(H.ROOT / "BENCHMARK.json"), "cell": {"chips": 1},
            "config": H.load_json(H.BENCH / "configs" / f"{config}.json"),
            "traffic": H.load_json(H.BENCH / "traffic" / f"{traffic}.json"),
            "limits": limits}


def _tiny(workload, files):
    c, t = files["config"], files["traffic"]
    if workload == "ehealth-cnn-chsgd":
        c["federation"].update(num_groups=2, devices_per_group=32, samples=64)
    elif workload == "stablelm-d8-chsgd":
        c.update(TINY_DECODER)
        t.update(batch=2, seq=64)
    elif workload == "stablelm-d8-chat":
        c.update(TINY_DECODER)
        c["serving"].update(cache_len=256, max_batch=4)
        t.update(rate_per_s=6.0, check_tokens=60,
                 prompt_len={"median": 40, "sigma": 1.0, "min": 8, "max": 120},
                 output_len={"median": 12, "sigma": 0.8, "min": 4, "max": 40})
    else:
        raise KeyError(workload)
    return files


@pytest.fixture
def tiny_run():
    """A harness Run of a cell at a CPU test's size."""
    def make(workload, seed=1, seconds=1.0, trace=0):
        files = _tiny(workload, cell_files(workload))
        args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                                  trace=trace)
        return H.Run(args, files, time.perf_counter())
    return make


@pytest.fixture
def system():
    """The system module that drives a run's cell."""
    def of(run):
        name = run.traffic["system"]
        return H.load_module(H.BENCH / "systems" / f"{name}.py", name)
    return of
