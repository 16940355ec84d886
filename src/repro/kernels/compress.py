"""Pallas TPU kernel: fused top-k sparsify + b-level quantize (C-HSGD §VII-A1).

The communication hot-spot of C-HSGD/C-TDCD is the intermediate-result
exchange: every message row is top-k sparsified and b-level quantized before
it goes on the wire. Doing those as separate ops costs two full passes over
the message (and a sort, for a sort-based top-k). This kernel fuses both into
one VMEM-resident pass — one read, one write per row:

  1. threshold refinement: a fixed-iteration binary search on the magnitude
     threshold against the row max (pure elementwise VPU work + row
     reductions; no sort). 16 iterations give a threshold tight to
     max|x| / 2^16 — bit-identical to the jnp reference
     ``core/compression.py::compress_rows_ref`` (same op sequence).
  2. mask: entries below the threshold are zeroed (>= k survivors; the exact
     top-k support is always preserved, ties can add a few).
  3. b-level quantize/dequantize of the surviving row against its post-mask
     [min, max] grid, when ``levels > 1``.

Ragged rows: a per-row ``row_len`` (int32) marks the valid prefix so that
rows of different widths can be padded to a common width and compressed in
one call; padding columns are excluded from every reduction and zeroed on
write-back.

BlockSpec: rows are tiled by ``block_rows``; the full feature axis stays
resident in VMEM. Most rows are ζ embeddings or model-parameter rows of a
few thousand floats, but an LLM head row is vocabulary-wide (100352 floats
for stablelm), so the scoped-VMEM limit is raised from the block size (see
``_compiler_params``). Per-row k and row_len ride along as [rows, 1] int32
operands tiled with the same row index map.

Backend selection: ``interpret`` defaults to auto-detect — compiled Mosaic on
TPU, interpret mode elsewhere. The ``compress_rows`` router additionally
short-circuits to the fused jnp reference off-TPU, where interpret-mode
Pallas would only add overhead.
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.common.backend import default_interpret  # noqa: F401  (re-export)
from repro.core.compression import N_REFINE, compress_rows_ref


def _compress_kernel(x_ref, k_ref, len_ref, o_ref, *, levels: int):
    # The kernel body IS the canonical math: compress_rows_ref traces into
    # the VMEM-resident block (elementwise VPU ops + row reductions only),
    # so the bit-identity contract with the oracle holds by construction.
    o_ref[...] = compress_rows_ref(
        x_ref[...],  # [block_rows, n]
        k_ref[...],  # [block_rows, 1] int32 per-row keep count
        levels,
        len_ref[...],  # [block_rows, 1] int32 valid prefix length
    ).astype(o_ref.dtype)


def _compress_dp_kernel(x_ref, k_ref, len_ref, noise_ref, clip_ref, sigma_ref,
                        o_ref, *, levels: int):
    # DP twin: same traced math plus the fused per-row clip+noise stage. The
    # noise rows ride in VMEM with the same row index map as x (precomputed
    # standard normals, so the kernel stays deterministic and bit-identical
    # to the jnp fallback); clip/σ are (1, 1) SMEM-friendly scalar operands.
    o_ref[...] = compress_rows_ref(
        x_ref[...],
        k_ref[...],
        levels,
        len_ref[...],
        dp_clip=clip_ref[0, 0],
        dp_sigma=sigma_ref[0, 0],
        dp_noise=noise_ref[...],  # [block_rows, n] standard-normal rows
    ).astype(o_ref.dtype)


_DEFAULT_SCOPED_VMEM = 16 << 20  # Mosaic's default scoped-VMEM limit on v5e
_MAX_SCOPED_VMEM = 100 << 20  # of the 128 MiB VMEM of a v5e core


def _compiler_params(block_rows: int, n: int, row_operands: int):
    """Scoped-VMEM limit for one [block_rows, n] f32 grid step.

    Each row operand (x, out, and the DP noise) is double-buffered, and the
    threshold refinement keeps about five block-sized temporaries. Compiled
    for v5e, the plain kernel needed 9 blocks and the DP kernel 11 at widths
    32768, 100352 and 262144, i.e. 2 * row_operands + 5. One block of margin.
    """
    need = (2 * row_operands + 6) * block_rows * n * 4
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(max(need, _DEFAULT_SCOPED_VMEM), _MAX_SCOPED_VMEM))


@functools.partial(jax.jit, static_argnames=("levels", "block_rows", "interpret"))
def _fused_compress_call(x, k_arr, len_arr, levels: int, block_rows: int, interpret: bool):
    rows, n = x.shape
    block_rows = min(block_rows, rows)
    pad_rows = (-rows) % block_rows
    if pad_rows:
        x = jnp.pad(x, ((0, pad_rows), (0, 0)))
        k_arr = jnp.pad(k_arr, ((0, pad_rows), (0, 0)))
        len_arr = jnp.pad(len_arr, ((0, pad_rows), (0, 0)))
    grid = (x.shape[0] // block_rows,)
    out = pl.pallas_call(
        functools.partial(_compress_kernel, levels=levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_compiler_params(block_rows, n, row_operands=2),
        interpret=interpret,
    )(x, k_arr, len_arr)
    return out[:rows]


@functools.partial(jax.jit, static_argnames=("levels", "block_rows", "interpret"))
def _fused_compress_dp_call(x, k_arr, len_arr, noise, clip, sigma,
                            levels: int, block_rows: int, interpret: bool):
    # Separate jitted entry so the non-DP call keeps its exact trace (and
    # executor caches keyed on it stay warm); DP only adds a `dp_enabled` bit
    # upstream — clip/σ/noise are traced operands, never static.
    rows, n = x.shape
    block_rows = min(block_rows, rows)
    pad_rows = (-rows) % block_rows
    if pad_rows:
        x = jnp.pad(x, ((0, pad_rows), (0, 0)))
        k_arr = jnp.pad(k_arr, ((0, pad_rows), (0, 0)))
        len_arr = jnp.pad(len_arr, ((0, pad_rows), (0, 0)))
        noise = jnp.pad(noise, ((0, pad_rows), (0, 0)))
    grid = (x.shape[0] // block_rows,)
    clip = jnp.asarray(clip, jnp.float32).reshape(1, 1)
    sigma = jnp.asarray(sigma, jnp.float32).reshape(1, 1)
    out = pl.pallas_call(
        functools.partial(_compress_dp_kernel, levels=levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_compiler_params(block_rows, n, row_operands=3),
        interpret=interpret,
    )(x, k_arr, len_arr, noise, clip, sigma)
    return out[:rows]


def fused_compress_pallas(
    x: jnp.ndarray,
    k: Union[int, jnp.ndarray],
    levels: int = 0,
    row_len: Optional[jnp.ndarray] = None,
    block_rows: int = 8,
    interpret: Optional[bool] = None,
    dp_clip=None,
    dp_sigma=None,
    dp_noise: Optional[jnp.ndarray] = None,
):
    """x: [rows, n] -> fused-compressed x, same shape/dtype.

    k: scalar or per-row [rows] keep count (k >= n is a per-row no-op).
    levels: b-level quantization grid size (<= 1 disables).
    row_len: optional per-row valid length for ragged/padded rows.
    interpret: None -> auto-detect (compiled on TPU, interpret elsewhere).
    dp_noise: optional [rows, n] precomputed standard-normal rows enabling the
    fused per-row L2-clip (``dp_clip``) + Gaussian noise (``dp_sigma``) stage.
    """
    rows, n = x.shape
    if interpret is None:
        interpret = default_interpret()
    k_arr = jnp.broadcast_to(jnp.asarray(k, jnp.int32).reshape(-1, 1), (rows, 1))
    if row_len is None:
        len_arr = jnp.full((rows, 1), n, jnp.int32)
    else:
        len_arr = jnp.asarray(row_len, jnp.int32).reshape(-1, 1)
    if dp_noise is not None:
        return _fused_compress_dp_call(
            x, k_arr, len_arr, dp_noise.astype(jnp.float32), dp_clip, dp_sigma,
            int(levels), block_rows, bool(interpret))
    return _fused_compress_call(x, k_arr, len_arr, int(levels), block_rows, bool(interpret))


# jitted fallback so eager call sites don't pay op-by-op dispatch; inside an
# outer jit this inlines.
_compress_rows_ref_jit = jax.jit(compress_rows_ref, static_argnames=("levels",))


def compress_rows(
    x: jnp.ndarray,
    k: Union[int, jnp.ndarray],
    levels: int = 0,
    row_len: Optional[jnp.ndarray] = None,
    dp_clip=None,
    dp_sigma=None,
    dp_noise: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Backend router for the fused compression op.

    On TPU this launches the compiled Mosaic kernel; elsewhere it runs the
    bit-identical fused jnp reference — interpret-mode Pallas is for
    validation, not the hot path.
    """
    if not default_interpret():
        mesh = jax.sharding.get_abstract_mesh()
        if not mesh.empty and mesh.size > 1:
            return _compress_rows_sharded(mesh, x, k, levels, row_len,
                                          dp_clip, dp_sigma, dp_noise)
        return fused_compress_pallas(x, k, levels, row_len, interpret=False,
                                     dp_clip=dp_clip, dp_sigma=dp_sigma,
                                     dp_noise=dp_noise)
    return _compress_rows_ref_jit(x, k, levels=levels, row_len=row_len,
                                  dp_clip=dp_clip, dp_sigma=dp_sigma,
                                  dp_noise=dp_noise)


def _compress_rows_sharded(mesh, x, k, levels, row_len, dp_clip, dp_sigma,
                           dp_noise, interpret: bool = False):
    """The kernel on each device's contiguous block of rows.

    Mosaic kernels cannot be partitioned automatically, so under a mesh the
    call is a ``shard_map`` over every mesh axis. Rows are independent, so
    splitting them changes no result; rows are padded to a multiple of the
    device count, and the padding (k = 0, row_len = 0) comes back zeroed.
    """
    rows, n = x.shape
    pad = (-rows) % mesh.size
    k = jnp.broadcast_to(jnp.asarray(k, jnp.int32).reshape(-1), (rows,))
    if row_len is None:
        row_len = jnp.full((rows,), n, jnp.int32)
    row_len = jnp.asarray(row_len, jnp.int32).reshape(-1)
    dp = dp_noise is not None
    parts = [x, k, row_len] + ([dp_noise.astype(jnp.float32)] if dp else [])
    if pad:
        parts = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                 for a in parts]
    rows_spec = P(mesh.axis_names)

    def local(x, k, row_len, noise=None, clip=None, sigma=None):
        return fused_compress_pallas(x, k, levels, row_len, interpret=interpret,
                                     dp_clip=clip, dp_sigma=sigma,
                                     dp_noise=noise)

    in_specs = (rows_spec,) * len(parts)
    if dp:
        parts += [jnp.asarray(dp_clip, jnp.float32),
                  jnp.asarray(dp_sigma, jnp.float32)]
        in_specs += (P(), P())
    # check_vma off: the pallas_call's out_shape carries no varying-axes
    # annotation, and every output row depends on its own input row only
    out = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                        out_specs=rows_spec, check_vma=False)(*parts)
    return out[:rows]


def compress_pytree(tree, k_frac: float, levels: int = 0,
                    dp_clip=None, dp_sigma=None, dp_key=None):
    """Compress every leaf of a message pytree, one row-matrix call per width.

    Each leaf is viewed as rows of its trailing axis, and leaves of equal
    width are stacked into one matrix, so the whole exchange message (θ0
    pytree + ζ1 + ζ2) costs one kernel launch per distinct width instead of
    one per leaf. Stacking never pads: an LLM message holds vocabulary-wide
    head rows beside head_dim-wide rows, and padding all rows to the widest
    would multiply its size in HBM by orders of magnitude. Per-leaf k is
    ``max(1, round(k_frac * width))``; rows are independent, so the result
    is bit-identical to compressing each leaf separately.

    ``dp_key`` (a jax PRNG key) enables the fused DP stage: standard-normal
    noise rows for each width's matrix are drawn from
    ``fold_in(dp_key, width)`` and ride into the kernel as an operand, with
    per-row L2 clip ``dp_clip`` and noise multiplier ``dp_sigma`` (std =
    σ·clip) — traced scalars, so re-picking them never recompiles.
    """
    do_topk = 0.0 < k_frac < 1.0
    dp = dp_key is not None
    if not do_topk and not (levels and levels > 1) and not dp:
        return tree
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    widths = [int(leaf.shape[-1]) if leaf.ndim else 1 for leaf in leaves]
    new_leaves = [None] * len(leaves)
    for n in sorted(set(widths)):
        members = [i for i, w in enumerate(widths) if w == n]
        mats = [leaves[i].astype(jnp.float32).reshape(-1, n) for i in members]
        ks = []
        for m in mats:
            k = max(1, int(round(k_frac * n))) if do_topk else n
            ks.append(jnp.full((m.shape[0],), k, jnp.int32))
        mat = jnp.concatenate(mats, axis=0) if len(mats) > 1 else mats[0]
        noise = (jax.random.normal(jax.random.fold_in(dp_key, n), mat.shape,
                                   jnp.float32) if dp else None)
        out = compress_rows(mat, jnp.concatenate(ks), levels,
                            dp_clip=dp_clip, dp_sigma=dp_sigma, dp_noise=noise)
        off = 0
        for i, m in zip(members, mats):
            r = m.shape[0]
            new_leaves[i] = out[off: off + r].reshape(leaves[i].shape).astype(
                leaves[i].dtype)
            off += r
    return jax.tree_util.tree_unflatten(treedef, new_leaves)
