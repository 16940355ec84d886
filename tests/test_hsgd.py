"""Algorithm-level HSGD tests: staleness semantics, intervals, compression."""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import FederationConfig, TrainConfig
from repro.core import federation as F
from repro.core.hsgd import (
    HSGDRunner,
    exchange,
    global_aggregation,
    global_model,
    init_state,
    local_sgd_step,
    make_group_weights,
    state_shardings,
)
from repro.data.partition import hybrid_partition
from repro.data.synthetic import ORGANAMNIST, make_dataset
from repro.launch.mesh import make_mesh
from repro.models.split_model import cnn_hybrid


def _mini(M=2, K=8, A_frac=0.5, q=2, p=4):
    fed = FederationConfig(num_groups=M, devices_per_group=K, alpha=A_frac,
                           local_interval=q, global_interval=p)
    X, y = make_dataset(ORGANAMNIST, M * K, seed=0)
    fd = hybrid_partition(ORGANAMNIST, X, y, fed, seed=0)
    data = {k: jnp.asarray(v) for k, v in fd.stacked().items()}
    model = cnn_hybrid(h_rows=11)
    return model, fed, data


def test_stale_context_frozen_within_interval():
    """ζ and θ0-snapshot must NOT change between exchanges (Alg. 1 reuse)."""
    model, fed, data = _mini()
    state = init_state(jax.random.PRNGKey(0), model, fed, data)
    state = exchange(model, state, data, fed)
    z1_before = jax.tree.map(jnp.copy, state.stale["z1"])
    for _ in range(3):
        state, _ = local_sgd_step(model, state, 0.05)
    np.testing.assert_array_equal(np.asarray(state.stale["z1"]), np.asarray(z1_before))


def test_exchange_refreshes_stale_context():
    model, fed, data = _mini()
    state = init_state(jax.random.PRNGKey(0), model, fed, data)
    state = exchange(model, state, data, fed)
    for _ in range(3):
        state, _ = local_sgd_step(model, state, 0.05)
    z2_old = np.asarray(state.stale["z2"])
    state = exchange(model, state, data, fed)
    assert np.abs(np.asarray(state.stale["z2"]) - z2_old).max() > 0


def test_local_aggregation_resets_devices_to_group_mean():
    model, fed, data = _mini()
    state = init_state(jax.random.PRNGKey(0), model, fed, data)
    state = exchange(model, state, data, fed)
    for _ in range(2):
        state, _ = local_sgd_step(model, state, 0.05)
    group_mean = F.local_aggregate(state.theta2)
    state2 = exchange(model, state, data, fed)
    # all devices now equal the pre-exchange group mean (eq 1 + line 15)
    for leaf_mean, leaf_dev in zip(jax.tree_util.tree_leaves(group_mean),
                                   jax.tree_util.tree_leaves(state2.theta2)):
        np.testing.assert_allclose(np.asarray(leaf_dev),
                                   np.broadcast_to(np.asarray(leaf_mean)[:, None],
                                                   leaf_dev.shape), rtol=1e-6)


def test_global_aggregation_makes_groups_identical():
    model, fed, data = _mini()
    state = init_state(jax.random.PRNGKey(0), model, fed, data)
    state = exchange(model, state, data, fed)
    for _ in range(2):
        state, _ = local_sgd_step(model, state, 0.1)
    w = make_group_weights(data)
    state = global_aggregation(state, fed, w)
    for leaf in jax.tree_util.tree_leaves(state.theta0):
        np.testing.assert_allclose(np.asarray(leaf[0]), np.asarray(leaf[1]), rtol=1e-6)


def test_hospital_and_device_updates_touch_right_parts():
    """Eq (5)(6) update θ0,θ1 every step; eq (7) updates θ2; cross-terms frozen."""
    model, fed, data = _mini()
    state = init_state(jax.random.PRNGKey(0), model, fed, data)
    state = exchange(model, state, data, fed)
    s2, _ = local_sgd_step(model, state, 0.05)
    for part_old, part_new in ((state.theta0, s2.theta0), (state.theta1, s2.theta1),
                               (state.theta2, s2.theta2)):
        moved = max(jax.tree_util.tree_leaves(
            jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), part_old, part_new)))
        assert moved > 0


def test_compression_changes_exchange_but_training_still_converges():
    model, fed, data = _mini(M=2, K=16, q=1, p=2)
    train_c = TrainConfig(learning_rate=0.05, compression_k=0.25, quantization_bits=128)
    runner = HSGDRunner(model, fed, train_c)
    state = init_state(jax.random.PRNGKey(0), model, fed, data)
    w = make_group_weights(data)
    state, losses = runner.run(state, data, w, rounds=10)
    assert losses[-1] < losses[0]


def test_legacy_sort_path_still_converges():
    """The pre-fusion sort-based compression path (bench baseline) works."""
    model, fed, data = _mini(M=2, K=16, q=1, p=2)
    train_c = TrainConfig(learning_rate=0.05, compression_k=0.25, quantization_bits=128)
    runner = HSGDRunner(model, fed, train_c, fused_compression=False)
    state = init_state(jax.random.PRNGKey(0), model, fed, data)
    w = make_group_weights(data)
    state, losses = runner.run(state, data, w, rounds=10)
    assert losses[-1] < losses[0]


def test_run_donates_state_buffers():
    """run() consumes its input state: no double-buffering of [M, A, ...]."""
    model, fed, data = _mini()
    runner = HSGDRunner(model, fed, TrainConfig(learning_rate=0.01))
    state = init_state(jax.random.PRNGKey(0), model, fed, data)
    in_leaves = jax.tree_util.tree_leaves((state.theta0, state.theta1, state.theta2))
    w = make_group_weights(data)
    new_state, _ = runner.run(state, data, w, rounds=1)
    donated = [leaf.is_deleted() for leaf in in_leaves]
    if not any(donated):
        pytest.skip("buffer donation not supported on this backend")
    assert all(donated)
    # the returned state is live and usable
    assert np.isfinite(np.asarray(jax.tree_util.tree_leaves(new_state.theta0)[0])).all()


def test_run_with_trivial_mesh_matches_no_mesh():
    model, fed, data = _mini()
    runner = HSGDRunner(model, fed, TrainConfig(learning_rate=0.02))
    w = make_group_weights(data)
    s1 = init_state(jax.random.PRNGKey(0), model, fed, data)
    s2 = init_state(jax.random.PRNGKey(0), model, fed, data)
    mesh = make_mesh((1, 1), ("data", "model"))
    _, l_plain = runner.run(s1, data, w, rounds=2)
    _, l_mesh = runner.run(s2, data, w, rounds=2, mesh=mesh)
    np.testing.assert_allclose(np.asarray(l_plain), np.asarray(l_mesh), rtol=1e-6)


def test_state_shardings_group_axis_and_replicated_scalars():
    model, fed, data = _mini()
    state = init_state(jax.random.PRNGKey(0), model, fed, data)
    mesh = make_mesh((1, 1), ("data", "model"))
    sh = state_shardings(state, mesh)
    theta0_spec = jax.tree_util.tree_leaves(sh.theta0)[0].spec
    assert theta0_spec and theta0_spec[0] in ("data", ("data",))  # M rides "data"
    assert sh.key.spec == () or all(s is None for s in sh.key.spec)  # replicated
    assert sh.step.spec == () or all(s is None for s in sh.step.spec)


# ---------------------------------------------------------------------------
# Sharded-exchange test matrix: {2, 4} fake devices × {compression on, off}
# × {do_global_agg on, off}. The device count must be fixed before jax
# initializes, hence ONE subprocess per device count (memoized) that runs all
# four configs and reports plain-vs-mesh loss curves as JSON; the parametrized
# tests then assert each combo to fp32 tolerance.
# ---------------------------------------------------------------------------

_SHARDED_MATRIX_CACHE = {}

_SHARDED_MATRIX_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(n_dev)d"
import sys, json
sys.path.insert(0, os.path.join(%(repo)r, "src"))
sys.path.insert(0, %(repo)r)
import jax, numpy as np
from tests.test_hsgd import _mini
from repro.common.config import TrainConfig
from repro.core.hsgd import HSGDRunner, init_state, make_group_weights
from repro.launch.mesh import make_mesh
model, fed, data = _mini(M=4)  # M=4 divides both mesh sizes -> genuinely sharded
w = make_group_weights(data)
mesh = make_mesh((%(n_dev)d, 1), ("data", "model"))
out = {}
for compression in (False, True):
    for do_agg in (False, True):
        train = TrainConfig(learning_rate=0.02,
                            compression_k=0.25 if compression else 0.0,
                            quantization_bits=128 if compression else 0)
        runner = HSGDRunner(model, fed, train, do_global_agg=do_agg)
        s1 = init_state(jax.random.PRNGKey(0), model, fed, data)
        s2 = init_state(jax.random.PRNGKey(0), model, fed, data)
        _, l_plain = runner.run(s1, data, w, rounds=2)
        st, l_mesh = runner.run(s2, data, w, rounds=2, mesh=mesh)
        leaf = jax.tree_util.tree_leaves(st.theta0)[0]
        out["%%s-%%s" %% (compression, do_agg)] = {
            "plain": np.asarray(l_plain).tolist(),
            "mesh": np.asarray(l_mesh).tolist(),
            "n_shards": len(leaf.sharding.device_set),
        }
# the compress kernel's row-sharded path (shard_map over every mesh axis),
# in interpret mode, against the jitted reference; 37 rows pad to the mesh
from repro.core.compression import compress_rows_ref
from repro.kernels.compress import _compress_rows_sharded
ref = jax.jit(compress_rows_ref, static_argnames="levels")
x = jax.random.normal(jax.random.PRNGKey(1), (37, 96))
noise = jax.random.normal(jax.random.PRNGKey(2), (37, 96))
clip, sigma = np.float32(1.0), np.float32(0.5)
got = _compress_rows_sharded(mesh, x, 24, 128, None, None, None, None,
                             interpret=True)
got_dp = _compress_rows_sharded(mesh, x, 24, 128, None, clip, sigma, noise,
                                interpret=True)
out["sharded_compress"] = {
    "plain": bool(np.array_equal(got, ref(x, 24, levels=128))),
    "dp": bool(np.array_equal(got_dp, ref(x, 24, levels=128, dp_clip=clip,
                                          dp_sigma=sigma, dp_noise=noise))),
}
print("RESULT::" + json.dumps(out))
"""


def _sharded_matrix(n_dev):
    """Run (once per device count) the full plain-vs-mesh config matrix."""
    if n_dev in _SHARDED_MATRIX_CACHE:
        return _SHARDED_MATRIX_CACHE[n_dev]
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = _SHARDED_MATRIX_CODE % {"n_dev": n_dev, "repo": repo}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    payload = [l for l in out.stdout.splitlines() if l.startswith("RESULT::")]
    assert payload, out.stdout[-2000:]
    res = json.loads(payload[0][len("RESULT::"):])
    _SHARDED_MATRIX_CACHE[n_dev] = res
    return res


@pytest.mark.slow
@pytest.mark.parametrize("n_dev", [2, 4])
@pytest.mark.parametrize("compression", [False, True])
@pytest.mark.parametrize("do_global_agg", [False, True])
def test_group_sharded_run_matrix(n_dev, compression, do_global_agg):
    """Per-step losses of the mesh-sharded run must match the single-device
    run to fp32 tolerance, for every exchange configuration."""
    res = _sharded_matrix(n_dev)
    entry = res[f"{compression}-{do_global_agg}"]
    assert entry["n_shards"] == n_dev  # genuinely sharded, not replicated
    np.testing.assert_allclose(np.asarray(entry["plain"]),
                               np.asarray(entry["mesh"]), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n_dev", [2, 4])
def test_row_sharded_compress_matches_reference(n_dev):
    """Under a mesh the compress kernel runs per device on a block of rows
    (Mosaic kernels cannot be auto-partitioned); rows are independent, so
    the result must equal the unsharded reference bit for bit."""
    entry = _sharded_matrix(n_dev)["sharded_compress"]
    assert entry == {"plain": True, "dp": True}


def test_sampled_participants_valid_and_distinct():
    fed = FederationConfig(num_groups=3, devices_per_group=10, alpha=0.4)
    idx = F.sample_participants(jax.random.PRNGKey(0), fed)
    assert idx.shape == (3, 4)
    a = np.asarray(idx)
    assert (a >= 0).all() and (a < 10).all()
    for row in a:
        assert len(set(row.tolist())) == len(row)  # without replacement


def test_q_interval_counts():
    """A run of R rounds yields exactly R*P loss entries (Q steps × Λ × R)."""
    model, fed, data = _mini(q=3, p=6)
    runner = HSGDRunner(model, fed, TrainConfig(learning_rate=0.01))
    state = init_state(jax.random.PRNGKey(0), model, fed, data)
    w = make_group_weights(data)
    state, losses = runner.run(state, data, w, rounds=4)
    assert len(losses) == 4 * 6


# ---------------------------------------------------------------------------
# Named scopes of Algorithm 1's phases: metadata only
# ---------------------------------------------------------------------------

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


def _compiled_round(executor: str) -> str:
    """The optimized HLO of one of the runner's executors at a tiny size."""
    model, fed, data = _mini()
    state = init_state(jax.random.PRNGKey(0), model, fed, data)
    w, lr = make_group_weights(data), jnp.float32(0.05)
    runner = HSGDRunner(model, fed, TrainConfig(compression_k=0.25, quantization_bits=128))
    M, A = fed.num_groups, fed.sampled_devices
    idx = F.sample_participants(jax.random.PRNGKey(1), fed)
    pmask = jnp.ones((M, A), jnp.float32)
    if executor == "round":
        low = runner.round_fn(4, 2, collect_stats=False).lower(state, data, w, lr)
    elif executor == "adaptive":
        low = runner.round_fn(4, 2, collect_stats=True).lower(state, data, w, lr)
    elif executor == "cohort":
        low = runner.cohort_round_fn(4, 2, A, collect_stats=False).lower(
            state, data, w, lr, idx, pmask)
    elif executor == "robust":
        low = runner.fault_round_fn(4, 2, A).lower(
            state, data, w, lr, idx, pmask, jnp.zeros((M, A)), jnp.zeros((M,)))
    else:  # private
        low = runner.round_fn(4, 2, collect_stats=False, dp=True).lower(
            state, data, w, lr, jnp.float32(1.0), jnp.float32(0.5))
    return low.compile().as_text()


@pytest.mark.parametrize("executor", ["round", "adaptive", "cohort", "robust", "private"])
def test_phase_scopes_change_metadata_only(executor, monkeypatch):
    """Every executor carries the phase scopes, and the program with them is
    the program without them once the metadata is taken out."""
    scoped = _compiled_round(executor)
    for name in ("local_step/hospital", "local_step/device", "exchange/compress",
                 "exchange/local_aggregation"):
        assert f"/{name}/" in scoped, name
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _compiled_round(executor)
    assert "local_step/" not in plain
    assert _program_text(scoped) == _program_text(plain)


# ---------------------------------------------------------------------------
# The model's batched device-tower gradients against the generic path
# ---------------------------------------------------------------------------


def _step(name):
    """One of the three step functions that share ``_local_grads``, as
    (model, state) -> its outputs."""
    from repro.core.hsgd import local_sgd_step_guarded, local_sgd_step_stats

    def guarded(model, state):
        M, A = state.batch["y"].shape
        fault = jnp.zeros((M, A)).at[1, A - 1].set(1e4)  # one device's g2 blown up
        return local_sgd_step_guarded(model, state, 0.05, jnp.ones((M, A)),
                                      grad_fault=fault, screen=True)

    def stats(model, state):
        return local_sgd_step_stats(model, state, 0.05, jnp.ones((state.batch["y"].shape[0],)))

    def plain(model, state):
        return local_sgd_step(model, state, 0.05)

    return {"plain": plain, "stats": stats, "guarded": guarded}[name]


@pytest.mark.parametrize("step", ["plain", "stats", "guarded"])
@pytest.mark.parametrize("h_rows", [11, 12])  # 17 and 16 device rows: odd and even pool crops
@pytest.mark.parametrize("A", [3, 4, 9])  # M·A = 6, 8, 18 devices on the lanes
def test_lane_dense_device_grads_match_the_generic_path(A, h_rows, step, monkeypatch):
    """cnn_hybrid's lane-dense ``device_grads`` gives every step function the
    g2 of vmap(vmap(grad)) over batch-1 towers, to f32 rounding."""
    spec = dataclasses.replace(ORGANAMNIST, hospital_size=h_rows)
    fed = FederationConfig(num_groups=2, devices_per_group=2 * A, alpha=0.5,
                           local_interval=2, global_interval=4)
    X, y = make_dataset(spec, 4 * A, seed=A)
    data = {k: jnp.asarray(v) for k, v in hybrid_partition(spec, X, y, fed, seed=0).stacked().items()}
    model = cnn_hybrid(h_rows=h_rows)
    assert model.device_grads is not None
    generic = dataclasses.replace(model, device_grads=None)
    state = init_state(jax.random.PRNGKey(A), model, fed, data)
    # one step past the exchange, so the devices differ from their group
    state = jax.jit(lambda s: local_sgd_step(generic, exchange(generic, s, data, fed), 0.05)[0])(state)
    # each step function then returns as its new θ2 the g2 it would apply
    # (after the guarded step's fault injection and screening)
    monkeypatch.setattr("repro.core.hsgd._apply_sgd",
                        lambda state, lr, g0, g1, g2: state._replace(theta2=g2))
    fn = _step(step)
    (got, *rest) = jax.jit(lambda s: fn(model, s))(state)
    (want, *rest_want) = jax.jit(lambda s: fn(generic, s))(state)
    got, want = got.theta2, want.theta2
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape and np.isfinite(g).all()
        # rtol for the entries, plus f32 rounding of the leaf's largest one
        # (summation order differs) for the entries near zero
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5 * np.abs(w).max())
    if step == "guarded":  # the same device flagged by the screen
        np.testing.assert_array_equal(np.asarray(rest[1]), np.asarray(rest_want[1]))
        assert float(np.asarray(rest[1])[1, A - 1]) == 0.0  # dev_ok


# ---------------------------------------------------------------------------
# θ2 leaves held lane-dense: the layout only
# ---------------------------------------------------------------------------


def _round_of(model, fed, data, monkeypatch, bypass: bool, executor: str = "round"):
    """(lowered text, compiled text, state, losses) of one C-HSGD round with
    ``lane_dense`` in place or bypassed. ``robust``: the fault executor with
    one device's gradient blown up, so the screen flags it and eq. (1) takes
    the coordinate-wise median."""
    with monkeypatch.context() as m:
        if bypass:
            m.setattr(F, "lane_dense", lambda x: x)
        runner = HSGDRunner(model, fed, TrainConfig(compression_k=0.25, quantization_bits=128))
        state = init_state(jax.random.PRNGKey(3), model, fed, data)
        args = (state, data, make_group_weights(data), jnp.float32(0.05))
        if executor == "round":
            fn = runner.round_fn(4, 2, collect_stats=False)
        else:
            M, A = fed.num_groups, fed.sampled_devices
            fn = runner.fault_round_fn(4, 2, A)
            fault = jnp.zeros((M, A)).at[1, A - 1].set(1e4)
            args += (F.sample_participants(jax.random.PRNGKey(1), fed),
                     jnp.ones((M, A)), fault, jnp.zeros((M,)))
        lowered = fn.lower(*args)
        state, losses, *flagged = fn(*args)
    assert executor == "round" or float(flagged[0]) > 0  # the robust branch ran
    return lowered.as_text(), lowered.compile().as_text(), state, losses


@pytest.mark.parametrize("executor", ["round", "robust"])
def test_lane_dense_theta2_keeps_the_round(executor, monkeypatch):
    """cnn_hybrid's θ2 proj [896, 64] is pinned lane-dense, and the round
    gives the same losses and θ leaves as the round without the pin."""
    model, fed, data = _mini()
    fed = dataclasses.replace(fed, robust_agg="median")
    low, _, got, got_losses = _round_of(model, fed, data, monkeypatch, False, executor)
    low_plain, _, want, want_losses = _round_of(model, fed, data, monkeypatch, True, executor)
    assert "@LayoutConstraint" in low and "@LayoutConstraint" not in low_plain
    np.testing.assert_array_equal(np.asarray(got_losses), np.asarray(want_losses))
    for part in ("theta0", "theta1", "theta2"):
        for g, w in zip(jax.tree_util.tree_leaves(getattr(got, part)),
                        jax.tree_util.tree_leaves(getattr(want, part))):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_lane_dense_rule_leaves_lstm_alone(monkeypatch):
    """lstm_hybrid's θ2 leaves (wx [40, 256], wh [64, 256], proj [64, 64],
    b [256]) are all lane-wide or short: its compiled round is the same
    program with the helper and without it."""
    from repro.data.synthetic import MIMIC3
    from repro.models.split_model import lstm_hybrid

    fed = FederationConfig(num_groups=2, devices_per_group=6, alpha=0.5,
                           local_interval=2, global_interval=4)
    X, y = make_dataset(MIMIC3, 12, seed=0)
    data = {k: jnp.asarray(v) for k, v in hybrid_partition(MIMIC3, X, y, fed, seed=0).stacked().items()}
    model = lstm_hybrid(n_features=76, hospital_features=36, n_classes=MIMIC3.n_classes)
    low, compiled, _, _ = _round_of(model, fed, data, monkeypatch, bypass=False)
    _, compiled_plain, _, _ = _round_of(model, fed, data, monkeypatch, bypass=True)
    assert "@LayoutConstraint" not in low
    assert _program_text(compiled) == _program_text(compiled_plain)


def test_models_without_device_grads_keep_the_generic_path():
    """lstm_hybrid supplies no ``device_grads``: its g2 is vmap(vmap(grad))
    of the batch-1 device loss, bit for bit."""
    from repro.core.hsgd import _device_loss, _local_grads
    from repro.data.synthetic import MIMIC3
    from repro.models.split_model import lstm_hybrid

    fed = FederationConfig(num_groups=2, devices_per_group=6, alpha=0.5,
                           local_interval=2, global_interval=4)
    X, y = make_dataset(MIMIC3, 12, seed=0)
    data = {k: jnp.asarray(v) for k, v in hybrid_partition(MIMIC3, X, y, fed, seed=0).stacked().items()}
    model = lstm_hybrid(n_features=76, hospital_features=36, n_classes=MIMIC3.n_classes)
    assert model.device_grads is None
    state = jax.jit(lambda s: exchange(model, s, data, fed))(
        init_state(jax.random.PRNGKey(0), model, fed, data))
    got = jax.jit(lambda s: _local_grads(model, s)[3])(state)

    def d_loss(t2, x2, y, t0, z1):
        return _device_loss(model, t2, x2, y, t0, z1)

    want = jax.jit(lambda s: jax.vmap(jax.vmap(jax.grad(d_loss), in_axes=(0, 0, 0, None, 0)))(
        s.theta2, s.batch["x2"], s.batch["y"], s.stale["theta0"], s.stale["z1"]))(state)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
