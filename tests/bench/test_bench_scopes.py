"""Device time by program scope (``bench/scopes.py``): the phases of
Algorithm 1 that ``repro.core.hsgd.PHASE_SCOPES`` names, found in a compiled
round's HLO metadata on the CPU and in a recorded chip trace (the e-health
cell at the training CLI's fleet, 10 groups x 64 devices, a few compiled
rounds on one TPU v5 lite), and the per-layer readers of their shares."""
import json
import re
import shutil
from pathlib import Path

import pytest

import harness as H
import scopes as S
from repro.core import hsgd

DATA = Path(__file__).parent / "data"
FIXTURE = DATA / "scoped_trace_fixture.xplane.pb"
FIXTURE_HLO = DATA / "scoped_trace_fixture_hlo_scopes.json"
SHARES = {"hospital_step_share": "local_step/hospital",
          "device_step_share": "local_step/device",
          "exchange_share": "exchange",
          "global_agg_share": "global_aggregation",
          "unscoped_share": S.UNSCOPED}


# -- parsing op_name --------------------------------------------------------


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/while/body/closed_call/exchange/compress/jit(compress_rows_ref)/abs",
     "exchange/compress"),
    ("jit(f)/while/body/local_step/device/vmap(vmap(transpose(jvp())))/dot_general",
     "local_step/device"),
    ("jit(f)/vmap(transpose(jvp(local_step/hospital)))/mul", "local_step/hospital"),
    ("jit(f)/exchange/sample/jit(_threefry_split)/exchange/while/body/add",
     "exchange/sample"),
    ("jit(f)/exchange/hospital/add", "exchange"),
    ("jit(hsgd_round)/global_aggregation/reduce_sum", "global_aggregation"),
    ("jit(hsgd_round)/while/body/closed_call/mul", S.UNSCOPED),
    # a bare top-level component that is not itself declared
    ("jit(f)/while/body/local_step/vmap(jvp())/mul", S.UNSCOPED),
    ("state.theta0[\\'fc1\\'][\\'w\\']", S.UNSCOPED),
])
def test_scope_of_an_op_name(op_name, scope):
    assert S.scope_of(op_name, hsgd.PHASE_SCOPES) == scope


# -- a compiled round on the CPU ---------------------------------------------


@pytest.fixture(scope="module")
def tiny_round():
    """The optimized HLO text of ``hsgd_round`` for a tiny C-HSGD fleet."""
    import jax
    import jax.numpy as jnp
    from repro.common.config import FederationConfig, TrainConfig
    from repro.data.partition import hybrid_partition
    from repro.data.synthetic import ORGANAMNIST, make_dataset
    from repro.models.split_model import cnn_hybrid

    fed = FederationConfig(num_groups=2, devices_per_group=8, alpha=0.5,
                           local_interval=2, global_interval=4)
    X, y = make_dataset(ORGANAMNIST, 16, seed=0)
    parts = hybrid_partition(ORGANAMNIST, X, y, fed, seed=0).stacked()
    data = {k: jnp.asarray(v) for k, v in parts.items()}
    model = cnn_hybrid(h_rows=11)
    state = hsgd.init_state(jax.random.PRNGKey(0), model, fed, data)
    runner = hsgd.HSGDRunner(model, fed, TrainConfig(compression_k=0.25,
                                                     quantization_bits=128))
    text = runner.round_fn(4, 2, collect_stats=False).lower(
        state, data, hsgd.make_group_weights(data), jnp.float32(0.01)).compile().as_text()
    return text, S.hlo_scopes(text, hsgd.PHASE_SCOPES)


@pytest.mark.parametrize("scope", hsgd.PHASE_SCOPES)
def test_every_phase_scope_names_ops_of_the_round(tiny_round, scope):
    _, scope_map = tiny_round
    assert scope in set(scope_map.values())


def test_the_compress_route_falls_under_exchange_compress(tiny_round):
    text, scope_map = tiny_round
    # off the TPU, compress_pytree's rows go through the jitted jnp reference
    hits = [m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*op_name=\"[^\"]*compress_rows_ref", text, re.M)]
    assert hits
    assert {scope_map[h] for h in hits} == {"exchange/compress"}


def test_the_map_covers_most_instructions(tiny_round):
    text, scope_map = tiny_round
    scoped = [s != S.UNSCOPED for s in scope_map.values()]
    assert sum(scoped) > 0.5 * len(scoped)
    # of the instructions whose metadata names an op, nearly all are in a phase
    named = [scope_map[m.group(1)] != S.UNSCOPED for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*op_name=\"jit", text, re.M)]
    assert sum(named) > 0.9 * len(named)


# -- a recorded chip trace ---------------------------------------------------


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData

    TR = H.load_module(H.BENCH / "trace.py", "trace")
    return TR, TR.reduce(ProfileData.from_file(str(FIXTURE)), "bench_window")


@pytest.fixture(scope="module")
def trace_map():
    return S.trace_scopes(FIXTURE, hsgd.PHASE_SCOPES)


def test_the_trace_and_the_executable_map_alike(red, trace_map):
    """The trace's ``tf_op`` and the executable's HLO text give every op of
    the window the same scope."""
    _, r = red
    hlo_map = json.loads(FIXTURE_HLO.read_text())
    window = {S.instruction(name) for name, _ in r["events"]}
    both = window & set(trace_map) & set(hlo_map)
    assert len(both) > 0.9 * len(window)
    assert {n: trace_map[n] for n in both} == {n: hlo_map[n] for n in both}


def test_the_shares_cover_the_busy_time(red, trace_map):
    _, r = red
    shares = [100 * S.scope_seconds(r, trace_map, p) / r["busy_s"] for p in SHARES.values()]
    assert all(0 <= s <= 100 for s in shares)
    assert 95 <= sum(shares) <= 100.5


def test_the_shares_partition_the_leaf_time(red, trace_map):
    """Every leaf op event lands in exactly one of the five shares' paths."""
    _, r = red
    total = sum(d for _, d in r["events"])
    parts = sum(S.scope_seconds(r, trace_map, p) for p in SHARES.values())
    assert parts == pytest.approx(total, rel=1e-12)


def test_the_compress_kernel_is_under_exchange_compress(red, trace_map):
    TR, r = red
    rx = re.compile(TR.KERNELS["compress"])
    kernels = {S.instruction(n) for n, _ in r["events"] if rx.search(n)}
    assert kernels and {trace_map[k] for k in kernels} == {"exchange/compress"}
    compress, _ = TR.op_seconds(r, TR.KERNELS["compress"])
    assert compress <= S.scope_seconds(r, trace_map, "exchange/compress") * (1 + 1e-9)
    assert S.scope_seconds(r, trace_map, "exchange/compress") <= S.scope_seconds(
        r, trace_map, "exchange") * (1 + 1e-9)


@pytest.fixture
def traced_run(tmp_path, monkeypatch):
    """The fixture laid out as ``bench/run.py`` leaves a traced run's profile."""
    d = tmp_path / "ehealth-cnn-chsgd" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(FIXTURE, d / "host.xplane.pb")
    monkeypatch.setattr(H, "TRACE_DIR", tmp_path)
    return tmp_path


@pytest.mark.parametrize("metric", sorted(SHARES))
def test_each_share_reader(metric, red, traced_run, monkeypatch):
    _, r = red
    reader = H.load_module(H.BENCH / "metrics" / f"{metric}.py", metric)
    ctx = {"trace": r, "facts": {"steps": 24}, "peaks": {}}
    value = reader.read(ctx)
    assert isinstance(value, float) and 0 <= value <= 100
    compress = H.load_module(H.BENCH / "metrics" / "compress_share.py", "compress_share")
    if metric == "exchange_share":
        assert compress.read(ctx) <= value
    # a program that names no scopes, or no profile on disk: nothing to read
    monkeypatch.delattr(hsgd, "PHASE_SCOPES")
    assert reader.read(ctx) is None
    monkeypatch.undo()
    monkeypatch.setattr(H, "TRACE_DIR", traced_run / "none")
    assert reader.read(ctx) is None


@pytest.mark.parametrize("path", ["local_step", "swap", "exchange/nothing"])
def test_a_share_with_nothing_to_read_is_none(path, red, traced_run, monkeypatch):
    """A path the program does not declare, or declares but never enters,
    reads None and not 0, so a dropped scope cannot pass for a gain."""
    _, r = red
    monkeypatch.setattr(hsgd, "PHASE_SCOPES", hsgd.PHASE_SCOPES + ("exchange/nothing",))
    assert S.share({"trace": r}, path) is None


def test_a_renamed_scope_moves_its_time_to_unscoped(red, traced_run, monkeypatch):
    """A program that renames ``global_aggregation`` (the trace still carries
    the old name): its reader falls silent and the time shows as unscoped."""
    _, r = red
    before = S.share({"trace": r}, S.UNSCOPED)
    agg = S.share({"trace": r}, "global_aggregation")
    renamed = tuple("global_agg" if p == "global_aggregation" else p
                    for p in hsgd.PHASE_SCOPES)
    monkeypatch.setattr(hsgd, "PHASE_SCOPES", renamed)
    reader = H.load_module(H.BENCH / "metrics" / "global_agg_share.py", "global_agg_share")
    assert reader.read({"trace": r}) is None
    assert S.share({"trace": r}, "global_agg") is None
    assert S.share({"trace": r}, S.UNSCOPED) == pytest.approx(before + agg)


def test_the_profile_is_parsed_once(traced_run):
    profile = S.newest_trace()
    first = S.trace_op_names(profile)
    hits = S._trace_op_names.cache_info().hits
    assert S.trace_op_names(profile) is first
    assert S._trace_op_names.cache_info().hits == hits + 1
