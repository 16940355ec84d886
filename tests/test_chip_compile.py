"""Compile the main path's Pallas kernels for a TPU v5e with no chip attached.

The TPU compiler is installed with JAX and compiles for a described
``v5e:2x2`` topology, so these tests catch what interpret mode cannot:
Mosaic lowering failures, tiling rules and scoped-VMEM overflows. Nothing
runs. The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels.compress import _fused_compress_call, _fused_compress_dp_call
from repro.kernels.flash_attention import flash_attention_pallas

KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the installed libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# Row matrices the exchange hands the kernel (compress_pytree stacks leaves
# of one width): the e-health CNN message at the CLI's 10 groups, and the
# stablelm-1.6b llm_hybrid message — ζ / d_model rows, MLP rows, and the
# vocabulary-wide head rows that need the raised VMEM limit.
EXCHANGE_MATRICES = {
    "ehealth-theta0": (1290, 128),
    "stablelm-d_model": (8192, 2048),
    "stablelm-d_ff": (2048, 5632),
    "stablelm-head": (2048, 100352),
}


@pytest.mark.parametrize("dp", [False, True], ids=["plain", "dp"])
@pytest.mark.parametrize("matrix", sorted(EXCHANGE_MATRICES))
def test_compress_compiles_for_v5e(one_chip, matrix, dp):
    rows, n = EXCHANGE_MATRICES[matrix]
    x = _sds((rows, n), jnp.float32, one_chip)
    col = _sds((rows, 1), jnp.int32, one_chip)
    if dp:
        scalar = _sds((), jnp.float32, one_chip)
        fn = jax.jit(lambda x, k, l, z, c, s: _fused_compress_dp_call(
            x, k, l, z, c, s, 128, 8, False))
        lowered = fn.lower(x, col, col, x, scalar, scalar)
    else:
        fn = jax.jit(lambda x, k, l: _fused_compress_call(x, k, l, 128, 8, False))
        lowered = fn.lower(x, col, col)
    assert KERNEL in lowered.compile().as_text()


@pytest.mark.parametrize("window", [0, 1024], ids=["causal", "windowed"])
def test_flash_attention_compiles_for_v5e(one_chip, window):
    """The serving prefill's flash kernel at S=4096, D=64 in bf16."""
    x = _sds((32, 4096, 64), jnp.bfloat16, one_chip)
    fn = jax.jit(lambda q, k, v: flash_attention_pallas(
        q, k, v, window=window, interpret=False))
    assert KERNEL in fn.lower(x, x, x).compile().as_text()


def test_compress_under_a_mesh_compiles_for_v5e(topo, monkeypatch):
    """Under a 4-device mesh the router runs the kernel per row block in a
    shard_map: Mosaic kernels cannot be partitioned automatically."""
    from repro.common.sharding import mesh_context
    from repro.kernels import compress as C
    from repro.launch.mesh import make_mesh

    # the router picks the kernel only on a TPU backend; this process's
    # backend is the CPU, so steer it here
    monkeypatch.setattr(C, "default_interpret", lambda: False)
    mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices)
    # 1290 rows: not a multiple of the device count, so the router pads
    x = _sds((1290, 128), jnp.float32, NamedSharding(mesh, P()))
    with mesh_context(mesh):
        compiled = jax.jit(lambda x: C.compress_rows(x, 32, 128)).lower(x).compile()
    assert KERNEL in compiled.as_text()


@pytest.mark.parametrize("executor", ["round", "cohort", "robust", "mesh"])
def test_theta2_proj_held_lane_dense_for_v5e(topo, one_chip, executor, monkeypatch):
    """The compiled C-HSGD round (P 4, Q 2, k 0.25, b 128) of the paper's CNN
    at 2 groups x 64 devices, α 0.25 (A = 16): no buffer of the per-device θ2
    proj leaf f32[2, 16, 896, 64] puts its 64-wide dim on the lanes, where
    every tile would be half padding, and no θ2-sized relayout copy runs.
    ``robust`` is the fault executor, whose conditionals (fault injection,
    robust eq. (1)) take no layout from their operands. ``mesh`` is the plain
    round of 4 groups sharded over a 4 x 1 mesh of the four chips: the leaf
    stays one group a chip, f32[1, 16, 896, 64], and no collective moves it."""
    from repro.common.config import FederationConfig, TrainConfig
    from repro.common.sharding import group_sharding, mesh_context
    from repro.core.hsgd import (HSGDRunner, init_state, make_group_weights,
                                 state_shardings)
    from repro.data.partition import hybrid_partition
    from repro.data.synthetic import ORGANAMNIST, make_dataset
    from repro.kernels import compress as C
    from repro.launch.mesh import make_mesh
    from repro.models.split_model import cnn_hybrid

    monkeypatch.setattr(C, "default_interpret", lambda: False)
    M, K = (4 if executor == "mesh" else 2), 64
    fed = FederationConfig(num_groups=M, devices_per_group=K, alpha=0.25,
                           local_interval=2, global_interval=4)
    X, y = make_dataset(ORGANAMNIST, M * K, seed=0)
    data = {k: jnp.asarray(v) for k, v in
            hybrid_partition(ORGANAMNIST, X, y, fed, seed=0).stacked().items()}
    model = cnn_hybrid()
    state = jax.eval_shape(lambda: init_state(jax.random.PRNGKey(0), model, fed, data))
    on_chip = lambda tree: jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)
    args = on_chip((state, data, make_group_weights(data), jnp.float32(0.01)))
    runner = HSGDRunner(model, fed, TrainConfig(compression_k=0.25, quantization_bits=128))
    A = fed.sampled_devices
    idx = on_chip(jax.ShapeDtypeStruct((M, A), jnp.int32))
    mask = on_chip(jax.ShapeDtypeStruct((M, A), jnp.float32))
    held = M  # groups of the leaf on one chip
    if executor == "round":
        lowered = runner.round_fn(4, 2, collect_stats=False).lower(*args)
    elif executor == "cohort":
        lowered = runner.cohort_round_fn(4, 2, A, collect_stats=False).lower(*args, idx, mask)
    elif executor == "robust":
        msg_fault = on_chip(jax.ShapeDtypeStruct((M,), jnp.float32))
        lowered = runner.fault_round_fn(4, 2, A).lower(*args, idx, mask, mask, msg_fault)
    else:
        mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices)
        placed = lambda tree, sh: jax.tree.map(lambda s, h: _sds(s.shape, s.dtype, h), tree, sh)
        repl = NamedSharding(mesh, P())
        args = (placed(state, state_shardings(state, mesh)),
                placed(data, jax.tree.map(lambda x: group_sharding(x.shape, mesh), data)),
                _sds((M,), jnp.float32, repl), _sds((), jnp.float32, repl))
        with mesh_context(mesh):
            lowered = runner.round_fn(4, 2, collect_stats=False).lower(*args)
        held = 1
    text = lowered.compile().as_text()
    proj = rf"f32\[{held},{A},896,64\]\{{(\d+),"
    minor = [int(d) for d in re.findall(proj, text)]
    assert minor, "no θ2 proj buffer in the compiled round"
    assert minor.count(3) == 0, f"{minor.count(3)} of {len(minor)} θ2 proj buffers padded"
    assert not re.search(rf"= f32\[{held},{A},896,64\]\{{[^}}]*\}} copy\(", text)
    if executor == "mesh":
        assert f"f32[{M},{A},896,64]" not in text  # never gathered whole
        moved = [l for l in text.splitlines()
                 if re.search(r"(all-gather|all-to-all|collective-permute)(-start)?\(", l)
                 and ",896,64]" in l.split("(")[0]]
        assert not moved, moved
