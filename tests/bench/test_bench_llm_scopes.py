"""Algorithm 1's phases named inside the LLM round
(``launch/steps.py::LLMRoundRunner``): each scope the round uses is one that
``repro.core.hsgd.PHASE_SCOPES`` declares and names ops of the compiled CPU
round; the scopes change metadata only; and a recorded chip trace of
``llm_round`` (the cell ``stablelm-d8-plain``'s executor at a small size,
on the generic ``stablelm-1.6b`` decoder, one TPU v5 lite: the scopes do not
depend on the decoder's rotary, biases or embedding scale) maps each op of
its window to the same scope as its executable's HLO text, the shares
partitioning the leaf time."""
import contextlib
import gzip
import json
import re
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import harness as H
import scopes as S
from conftest import TINY_DECODER
from repro.core import hsgd
from repro.data.synthetic import llm_batch_fn
from repro.launch.steps import LLMRoundRunner
from repro.models.split_model import llm_hybrid

DATA = Path(__file__).parent / "data"
FIXTURE_GZ = DATA / "llm_scoped_trace_fixture.xplane.pb.gz"
FIXTURE_HLO = DATA / "llm_scoped_trace_fixture_hlo_scopes.json"
SHARES = {"hospital_step_share": "local_step/hospital",
          "device_step_share": "local_step/device",
          "exchange_share": "exchange",
          "unscoped_share": S.UNSCOPED}
# the LLM round's scopes: the phases it runs at every size, and the two it
# runs only with compression on (k or b set) and with several pod groups
PLAIN = {"local_step/hospital", "local_step/device", "exchange",
         "exchange/intermediate"}
COMPRESSED_PODS = PLAIN | {"exchange/compress", "global_aggregation"}


def _round_text(executor, k, b, pods):
    """Optimized HLO of one of the LLM round's executors for the cell's
    decoder (published stablelm-2) at the benchmark's tiny widths, two
    layers, batch 2, 32 tokens."""
    files = H.cell_files("stablelm-d8-plain")
    conf = dict(files["config"], **TINY_DECODER)
    name = files["traffic"]["system"]
    cfg = H.load_module(H.BENCH / "systems" / f"{name}.py", name).model_config(conf)
    model = llm_hybrid(cfg, n_tower=1, remat=True)
    runner = LLMRoundRunner(model, n_pods=pods)
    params = jax.eval_shape(lambda k: jax.tree.map(
        lambda x: jnp.stack([x] * pods), model.init(k)), jax.random.PRNGKey(0))
    batches = llm_batch_fn(cfg, 2, 32, n_pods=pods, seed=0)(0, 2)
    eta = jnp.float32(0.01)
    if executor == "dp":
        fn = runner.round_fn(4, 2, k, b, collect_stats=False, dp=True)
        low = fn.lower(params, batches, eta, jnp.float32(1.0), jnp.float32(0.5),
                       jax.random.PRNGKey(1))
    else:
        fn = runner.round_fn(4, 2, k, b, collect_stats=executor == "stats")
        low = fn.lower(params, batches, eta)
    return low.compile().as_text()


def _entered_scopes(executor, k, b, pods):
    """(HLO text, the scope paths the round enters while it is traced)."""
    entered, stack = set(), []
    real = jax.named_scope

    @contextlib.contextmanager
    def recording(name):
        stack.append(name)
        entered.add("/".join(stack))
        try:
            with real(name):
                yield
        finally:
            stack.pop()

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", recording)
        return _round_text(executor, k, b, pods), entered


@pytest.fixture(scope="module")
def scoped():
    cache = {}

    def of(executor, k, b, pods):
        key = (executor, k, b, pods)
        if key not in cache:
            cache[key] = _entered_scopes(*key)
        return cache[key]
    return of


@pytest.mark.parametrize("k,b,pods,want", [(0.0, 0, 1, PLAIN),
                                           (0.25, 128, 2, COMPRESSED_PODS)])
def test_each_scope_of_the_llm_round_names_its_ops(scoped, k, b, pods, want):
    """The scope paths the round enters are declared phases, and each of
    them holds ops of the compiled round."""
    text, entered = scoped("plain", k, b, pods)
    assert entered == want
    assert entered <= set(hsgd.PHASE_SCOPES)
    got = set(S.hlo_scopes(text, hsgd.PHASE_SCOPES).values()) - {S.UNSCOPED}
    # ``exchange`` holds ops of its own only where something runs beside its
    # children; every op under it falls under one of them here
    assert got <= want and want - got <= {"exchange"}


_METADATA = re.compile(r', metadata=\{(?:[^}"]|"(?:[^"\\]|\\.)*")*\}')


def _program_text(hlo: str) -> str:
    """Optimized HLO text without what names and places the ops: each
    instruction's metadata and the module's file/stack-frame tables."""
    out, skip = [], False
    for line in hlo.splitlines():
        if line == "FileNames":
            skip = True
        if skip and line.startswith(("%", "ENTRY")):
            skip = False
        if not skip:
            out.append(_METADATA.sub("", line))
    return "\n".join(out)


@pytest.mark.parametrize("executor", ["plain", "stats", "dp"])
def test_llm_phase_scopes_change_metadata_only(scoped, executor, monkeypatch):
    """The plain, probe-collecting and private executors carry the scopes,
    and each is the same program without them once the metadata is out."""
    text, entered = scoped(executor, 0.25, 128, 2)
    assert entered == COMPRESSED_PODS
    got = set(S.hlo_scopes(text, hsgd.PHASE_SCOPES).values())
    assert COMPRESSED_PODS - {"exchange"} <= got
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _round_text(executor, 0.25, 128, 2)
    assert "local_step/" not in plain
    assert _program_text(text) == _program_text(plain)


# -- a recorded chip trace of llm_round ---------------------------------------


@pytest.fixture(scope="module")
def profile(tmp_path_factory):
    """The recorded profile (kept gzipped: one round of ``llm_round``)."""
    path = tmp_path_factory.mktemp("llm_trace") / "host.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE_GZ.read_bytes()))
    return path


@pytest.fixture(scope="module")
def red(profile):
    from jax.profiler import ProfileData

    TR = H.load_module(H.BENCH / "trace.py", "trace")
    return TR.reduce(ProfileData.from_file(str(profile)), "bench_window")


@pytest.fixture(scope="module")
def trace_map(profile):
    return S.trace_scopes(profile, hsgd.PHASE_SCOPES)


def test_the_llm_trace_and_its_executable_map_alike(red, trace_map):
    """The trace's ``tf_op`` and the executable's HLO text give every op of
    the window the same scope. The ops whose trace event carries no
    ``tf_op`` are XLA's own copies (``copy-start``/``copy-done`` of the
    memory-space prefetches), which name no op in the HLO either: both maps
    put them under no scope, as ``scope_seconds`` reads an op missing from
    the map."""
    hlo_map = json.loads(FIXTURE_HLO.read_text())
    window = {S.instruction(name) for name, _ in red["events"]}
    of = lambda m: {n: m.get(n, S.UNSCOPED) for n in window}
    assert of(trace_map) == of(hlo_map)
    named = window & set(trace_map)
    assert len(named) > 0.8 * len(window)
    assert all(re.match(r"copy|custom-call", n) for n in window - named)
    assert {"local_step/hospital", "local_step/device",
            "exchange/intermediate"} <= {trace_map[n] for n in named}


def test_the_llm_shares_partition_the_leaf_time(red, trace_map):
    """The four shares the cell reports cover every leaf op once: with no
    pod groups and no compression nothing runs under ``global_aggregation``
    or ``exchange/compress``."""
    total = sum(d for _, d in red["events"])
    parts = [S.scope_seconds(red, trace_map, p) for p in SHARES.values()]
    assert sum(parts) == pytest.approx(total, rel=1e-3)
    assert all(p > 0 for p in parts[:3])
    assert S.scope_seconds(red, trace_map, "global_aggregation") == 0
    assert S.scope_seconds(red, trace_map, "exchange/compress") == 0


@pytest.fixture
def traced_run(profile, tmp_path, monkeypatch):
    """The fixture laid out as ``bench/run.py`` leaves a traced run's profile."""
    d = tmp_path / "stablelm-d8-plain" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(profile, d / "host.xplane.pb")
    monkeypatch.setattr(H, "TRACE_DIR", tmp_path)
    return tmp_path


@pytest.mark.parametrize("metric", sorted(SHARES))
def test_each_share_the_cell_reports(metric, red, traced_run):
    reader = H.load_module(H.BENCH / "metrics" / f"{metric}.py", metric)
    value = reader.read({"trace": red, "facts": {"steps": 4}, "peaks": {}})
    assert isinstance(value, float) and 0 <= value <= 100


@pytest.mark.parametrize("metric", ["global_agg_share", "compress_share",
                                    "device_conv_share"])
def test_the_shares_the_cell_leaves_out_read_none(metric, red, traced_run):
    """The metrics not listed for the cell find nothing to read in its trace."""
    reader = H.load_module(H.BENCH / "metrics" / f"{metric}.py", metric)
    assert reader.read({"trace": red, "facts": {"steps": 4}, "peaks": {}}) is None
