"""Compile the main path's Pallas kernels for a TPU v5e with no chip attached.

The TPU compiler is installed with JAX and compiles for a described
``v5e:2x2`` topology, so these tests catch what interpret mode cannot:
Mosaic lowering failures, tiling rules and scoped-VMEM overflows. Nothing
runs. The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

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
