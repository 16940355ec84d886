"""The device towers' lane-dense conv stack under its own scope,
``local_step/device/conv``: found in a compiled round's HLO metadata on the
CPU and in a recorded chip trace of the same fleet as ``test_bench_scopes.py``
(10 groups x 64 devices, v5 lite), and the ``device_conv_share`` reader."""
import json
import shutil
from pathlib import Path

import pytest

import harness as H
import scopes as S
from repro.core import hsgd
from test_bench_scopes import FIXTURE, SHARES, red, tiny_round  # noqa: F401 (fixtures)

DATA = Path(__file__).parent / "data"
CONV_FIXTURE = DATA / "conv_scoped_trace_fixture.xplane.pb"
CONV_FIXTURE_HLO = DATA / "conv_scoped_trace_fixture_hlo_scopes.json"


def test_the_lane_dense_conv_stack_keeps_its_scope(tiny_round):  # noqa: F811
    """Every instruction of the device towers' conv stack, its backward pass
    included, falls under ``local_step/device/conv``: an op that lost the
    scope would read as unscoped and fake a drop in the device step's share."""
    text, scope_map = tiny_round
    conv, backward = [], []
    for line in text.splitlines():
        m, op = S._INSTRUCTION.match(line), S._OP_NAME.search(line)
        if m and op and "conv" in S.components(op.group(1)):
            conv.append(scope_map[m.group(1)])
            backward += ["transpose(" in op.group(1)]
    assert conv and any(backward) and not all(backward)
    assert set(conv) == {"local_step/device/conv"}


# -- a chip trace of the lane-dense conv stack --------------------------------


@pytest.fixture(scope="module")
def conv_red():
    from jax.profiler import ProfileData

    TR = H.load_module(H.BENCH / "trace.py", "trace")
    return TR.reduce(ProfileData.from_file(str(CONV_FIXTURE)), "bench_window")


@pytest.fixture(scope="module")
def conv_trace_map():
    return S.trace_scopes(CONV_FIXTURE, hsgd.PHASE_SCOPES)


def test_the_conv_trace_and_its_executable_map_alike(conv_red, conv_trace_map):
    hlo_map = json.loads(CONV_FIXTURE_HLO.read_text())
    window = {S.instruction(name) for name, _ in conv_red["events"]}
    both = window & set(conv_trace_map) & set(hlo_map)
    assert len(both) > 0.9 * len(window)
    assert {n: conv_trace_map[n] for n in both} == {n: hlo_map[n] for n in both}
    assert "local_step/device/conv" in {conv_trace_map[n] for n in both}


def test_no_op_of_the_conv_stack_is_unscoped_on_the_chip(conv_trace_map):
    """Every device op whose ``tf_op`` names the conv stack, forward or
    backward, is under ``local_step/device/conv``."""
    names = S.trace_op_names(CONV_FIXTURE)
    conv = {S.instruction(ev): op for ev, op in names.items() if "conv" in S.components(op)}
    assert any("transpose(" in op for op in conv.values())
    assert {conv_trace_map[n] for n in conv} == {"local_step/device/conv"}


def test_the_conv_profile_shares_partition_the_leaf_time(conv_red, conv_trace_map):
    r = conv_red
    total = sum(d for _, d in r["events"])
    parts = sum(S.scope_seconds(r, conv_trace_map, p) for p in SHARES.values())
    assert parts == pytest.approx(total, rel=1e-12)
    conv = S.scope_seconds(r, conv_trace_map, "local_step/device/conv")
    assert 0 < conv < S.scope_seconds(r, conv_trace_map, "local_step/device")


def _lay_out(fixture, tmp_path, monkeypatch):
    """A profile laid out as ``bench/run.py`` leaves a traced run's."""
    root = tmp_path / fixture.stem
    d = root / "ehealth-cnn-chsgd" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(fixture, d / "host.xplane.pb")
    monkeypatch.setattr(H, "TRACE_DIR", root)


def test_the_device_conv_share_reader(red, conv_red, tmp_path, monkeypatch):  # noqa: F811
    """A share on a profile of the lane-dense path, inside the device step's;
    None on a profile without it (the program before the path) and for a
    program that does not declare the scope."""
    reader = H.load_module(H.BENCH / "metrics" / "device_conv_share.py", "device_conv_share")
    device = H.load_module(H.BENCH / "metrics" / "device_step_share.py", "device_step_share")
    _lay_out(FIXTURE, tmp_path, monkeypatch)
    assert reader.read({"trace": red[1]}) is None
    _lay_out(CONV_FIXTURE, tmp_path, monkeypatch)
    ctx = {"trace": conv_red, "facts": {"steps": 24}, "peaks": {}}
    value = reader.read(ctx)
    assert isinstance(value, float) and 0 < value < device.read(ctx) <= 100
    monkeypatch.setattr(hsgd, "PHASE_SCOPES", tuple(
        p for p in hsgd.PHASE_SCOPES if p != "local_step/device/conv"))
    assert reader.read(ctx) is None
