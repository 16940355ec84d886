"""Infrastructure tests: checkpointing, config registry, comm model, sharding
helpers, and a small-mesh dry-run lowering (4 fake devices via subprocess)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.common.config import INPUT_SHAPES, get_config, list_configs
from repro.common.sharding import DEFAULT_RULES, divisible_spec, logical_to_spec
from repro.core.comm_model import ICI, WAN, MessageSizes, round_time, total_comm_cost
from repro.common.config import FederationConfig
from repro.launch.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_checkpoint_roundtrip(tmp_path):
    params = {"a": {"w": jnp.arange(6.0).reshape(2, 3)},
              "b": (jnp.ones((4,)), jnp.zeros((2, 2)))}
    save_checkpoint(str(tmp_path / "ck"), params, step=7, extra={"note": "x"})
    loaded, step, extra = load_checkpoint(str(tmp_path / "ck"))
    assert step == 7 and extra["note"] == "x"
    np.testing.assert_array_equal(loaded["a"]["w"], np.arange(6.0).reshape(2, 3))
    # sequences come back as the SAME container type, not __seq{i} dicts
    assert isinstance(loaded["b"], tuple) and len(loaded["b"]) == 2
    np.testing.assert_array_equal(loaded["b"][0], np.ones((4,)))
    np.testing.assert_array_equal(loaded["b"][1], np.zeros((2, 2)))


def test_registry_has_all_assigned():
    from repro.configs import ASSIGNED

    names = list_configs()
    for a in ASSIGNED:
        assert a in names
    assert len(ASSIGNED) == 10
    # smoke variants exist and are reduced
    for a in ASSIGNED:
        s = get_config(a, smoke=True)
        assert s.num_layers <= 4 and s.d_model <= 512


def test_input_shapes_assigned():
    assert INPUT_SHAPES["train_4k"].seq_len == 4096
    assert INPUT_SHAPES["train_4k"].global_batch == 256
    assert INPUT_SHAPES["prefill_32k"].seq_len == 32768
    assert INPUT_SHAPES["decode_32k"].global_batch == 128
    assert INPUT_SHAPES["long_500k"].seq_len == 524288


def test_federation_config_validates_interval_ratio():
    """P must be a positive multiple of Q at construction — no silent
    flooring of Λ anywhere downstream (round_time used to do P // Q)."""
    import pytest

    with pytest.raises(ValueError):
        FederationConfig(local_interval=3, global_interval=4)
    with pytest.raises(ValueError):
        FederationConfig(local_interval=0, global_interval=4)
    assert FederationConfig(local_interval=2, global_interval=6).lam == 3


def test_comm_model_paper_formula():
    """C(P,Q) matches eq. (19) hand-computed."""
    sizes = MessageSizes(theta0=100.0, theta1=200.0, theta2=50.0, z1=10.0, z2=20.0,
                         n_active=4)
    fed = FederationConfig(local_interval=2, global_interval=4)
    per_iter = 200.0 / 4 + (4 * 50.0 + 100.0 + 10.0 + 20.0) / 2
    assert abs(total_comm_cost(sizes, fed, 10) - per_iter * 10) < 1e-9


def test_round_time_positive_and_orders():
    sizes = MessageSizes(theta0=1e6, theta1=1e6, theta2=1e5, z1=1e5, z2=1e5, n_active=8)
    fed = FederationConfig(local_interval=1, global_interval=2)
    t_wan = round_time(sizes, fed, t_compute=0.05, links=WAN)
    t_ici = round_time(sizes, fed, t_compute=0.05, links=ICI)
    assert t_ici < t_wan  # pod links dwarf WAN
    assert t_wan > 0.1  # includes compute


def test_logical_to_spec_dedupes_axes():
    spec = logical_to_spec(("batch", "seq", "embed"), DEFAULT_RULES)
    flat = []
    for s in spec:
        if s is None:
            continue
        flat.extend(s if isinstance(s, tuple) else (s,))
    assert len(flat) == len(set(flat))  # no mesh axis used twice


def test_divisible_spec_drops_non_divisible():
    mesh = make_mesh((1,), ("model",))
    from jax.sharding import PartitionSpec as P

    spec = divisible_spec((7, 16), P("model", "model"), mesh)
    assert spec[0] is None or 7 % mesh.shape["model"] == 0


@pytest.mark.slow
def test_small_mesh_dryrun_subprocess():
    """Lower + compile a reduced arch on a 2x2 debug mesh in a subprocess
    (device count must be set before jax init)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, os.path.join(%r, "src"))
import jax
from repro.common.config import get_config, INPUT_SHAPES, InputShape
from repro.common.sharding import mesh_context
from repro.launch.mesh import make_debug_mesh
from repro.launch.steps import build_programs, build_shardings

mesh = make_debug_mesh(2, 2)
cfg = get_config("gemma3-1b", smoke=True)
shape = InputShape("t", 64, 8, "train")
progs = build_programs(cfg, shape)
for name, (fn, sds, axes) in progs.entries.items():
    sh = tuple(build_shardings(s, a, mesh) for s, a in zip(sds, axes))
    with mesh_context(mesh):
        c = jax.jit(fn, in_shardings=sh).lower(*sds).compile()
        assert c.cost_analysis() is not None
print("OK")
""" % REPO
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_collective_byte_parser():
    from repro.launch.dryrun import collective_bytes

    hlo = """
  %ag = f32[16,128]{1,0} all-gather(f32[4,128]{1,0} %x), dimensions={0}
  %ar = (bf16[64]{0}, bf16[32]{0}) all-reduce-start(...), replica_groups={}
  %d = bf16[64]{0} all-reduce-done(%ar)
  %cp = u32[8]{0} collective-permute(%y), source_target_pairs={{0,1}}
"""
    got = collective_bytes(hlo)
    assert got["all-gather"] == 16 * 128 * 4
    assert got["all-reduce"] == 64 * 2 + 32 * 2
    assert got["collective-permute"] == 8 * 4
