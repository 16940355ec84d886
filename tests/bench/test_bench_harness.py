"""The harness: cells found by name, the result line, and no result off the
chip. Runs that need a chip are driven here with the chip check replaced,
in a subprocess over a copy of the benchmark's files."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

import harness as H

ROOT = H.ROOT

LAUNCHER = textwrap.dedent("""
    import sys
    sys.path.insert(0, sys.argv[1] + "/bench")
    import jax
    import harness as H
    H.require_chips = lambda n: (jax.devices()[:n], H.peaks_for("TPU v5 lite"))
    import run
    run.main(sys.argv[2:])
""")


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def _copy(tmp_path, with_src=True):
    """A checkout holding BENCHMARK.json, the benchmark's paths and (unless
    asked not to) the program."""
    shutil.copytree(H.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    if with_src:
        (tmp_path / "src").symlink_to(ROOT / "src")
    return tmp_path


def test_no_result_without_a_tpu():
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "ehealth-cnn-chsgd",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_no_result_without_the_program(tmp_path):
    co = _copy(tmp_path, with_src=False)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "ehealth-cnn-chsgd",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=co, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_a_cell_added_as_files_is_found_and_run(tmp_path):
    """A new configuration, traffic mix, limits and per-layer reader, added
    as files with a BENCHMARK.json entry and no other edit, run end to end
    (chip check replaced), and the result line holds exactly the keys of the
    result, with the compared numbers last."""
    co = _copy(tmp_path)
    b = co / "bench"
    conf = json.loads((b / "configs" / "ehealth-cnn-paper-fleet.json").read_text())
    conf["name"] = "ehealth-cnn-tiny-fleet"
    conf["federation"].update(num_groups=2, devices_per_group=32, samples=64)
    (b / "configs" / "ehealth-cnn-tiny-fleet.json").write_text(json.dumps(conf))
    mix = json.loads((b / "traffic" / "chsgd-fleet-rounds.json").read_text())
    mix.update(name="chsgd-p2q1", global_interval_P=2, local_interval_Q=1)
    (b / "traffic" / "chsgd-p2q1.json").write_text(json.dumps(mix))
    (b / "limits" / "tiny-p2q1.json").write_text(
        (b / "limits" / "ehealth-cnn-chsgd.json").read_text())
    (b / "metrics" / "rounds_per_window.py").write_text(
        "def read(ctx):\n    return ctx['facts']['steps'] / 2\n")
    bench = json.loads((co / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "ehealth-cnn-tiny-fleet", "source": "test",
                             "file": "bench/configs/ehealth-cnn-tiny-fleet.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-p2q1", "config": "ehealth-cnn-tiny-fleet",
                               "traffic": "chsgd-p2q1", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_step_ms":
            m["workloads"].append("tiny-p2q1")
    bench["per_layer"].append({"name": "rounds_per_window", "unit": "1", "better": "higher",
                               "source": "host_clock", "layer": "test",
                               "moves": "train_step_ms", "workloads": ["tiny-p2q1"]})
    (co / "BENCHMARK.json").write_text(json.dumps(bench))
    (co / "launch.py").write_text(LAUNCHER)

    p = subprocess.run([sys.executable, "launch.py", str(co), "--workload", "tiny-p2q1",
                        "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
                       cwd=co, env=_env(), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert last["correct"] is True
    assert set(last["metrics"]) == {"train_step_ms", "setup_s"}
    assert last["device"]["count"] == 1 and "kind" in last["device"]
    assert set(last["checks"]) == {"loss_gap", "change_gap"}
    tail = p.stderr.strip().splitlines()[-2:]
    assert all(line.startswith("check ") and "limit" in line for line in tail)

    # the new reader is found by its name and reads the cell's facts
    reader = ("import sys, json; sys.path.insert(0, sys.argv[1] + '/bench'); import run; "
              "print(json.dumps(run.read_per_layer(json.load(open('BENCHMARK.json')), "
              "'tiny-p2q1', {'facts': {'steps': 8}, 'trace': {}, 'peaks': {}})))")
    p = subprocess.run([sys.executable, "-c", reader, str(co)], cwd=co, env=_env(),
                       capture_output=True, text=True, timeout=120)
    assert json.loads(p.stdout) == {"rounds_per_window": {"value": 4.0, "unit": "1"}}


@pytest.mark.parametrize(
    "workload", [w["name"] for w in H.load_json(ROOT / "BENCHMARK.json")["workloads"]])
def test_every_cell_has_its_files(workload):
    files = H.cell_files(workload)
    bench = files["bench"]
    e2e = {m["name"] for m in H.cell_metrics(bench, workload, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = H.cell_metrics(bench, workload, "per_layer")
    assert layer and all(m["moves"] in e2e for m in layer)
    for m in layer:
        assert (H.BENCH / "metrics" / f"{m['name']}.py").is_file()
    assert (H.BENCH / "systems" / f"{files['traffic']['system']}.py").is_file()
    assert files["limits"]
