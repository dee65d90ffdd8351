"""Pallas kernel: multi-byte pattern scan over packed uint8 row-groups.

TPU adaptation of FastWARC's SIMD bulk scanning (DESIGN.md §4): the VPU is
an (8, 128) vector unit, so a byte-compare sweep maps onto it directly.
For a pattern ``p`` of length P, the match mask is

    mask[i] = AND_{j<P} (buf[i+j] == p[j])

computed as P shifted compares over a VMEM-resident tile — no per-byte
control flow: the host parser's per-record work becomes a handful of wide
vector ops.

Layout: the input is a ``(B, W + ROWGROUP_PAD)`` uint8 matrix in the
shared row-group layout (:mod:`repro.kernels.bucketing`): payload bytes
left-justified, zero tail. Grid step ``(i, j)`` maps the ``(rows, cols)``
tile at row ``i·rows``, column ``j·cols``, and — through a second
``BlockSpec`` over the *same* array — the 128 columns just past the
tile: the halo that match windows crossing the tile's right edge read.
The last tile's halo is the zero tail, where windows compare against
zeros and lose (all-zero patterns are rejected by the wrappers).

Bytes are widened to int32 before comparing: v5e's VPU has no 8-bit
vector compare. A single pattern arrives as ``MAX_PATTERN`` int32
scalars in SMEM (scalar prefetch); per-row patterns (cross-request
batching) arrive as a ``(B, MAX_PATTERN)`` int32 VMEM block, with ``-1``
at the positions past a row's pattern length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.kernels.bucketing import LANES, ROWGROUP_PAD, grid_tiles

DEFAULT_BLOCK = 64 * 1024  # width-bucket granularity of the ragged-batch path
MAX_PATTERN = 16
# Payload bytes per grid step. Mosaic keeps a widened int32 copy of the
# tile live for every shifted compare (~5.5 B of scoped VMEM per payload
# byte each, against v5e's 16 MiB limit), so the step shrinks as the
# pattern grows: 64 KiB at 16 bytes, 256 KiB at 4 or fewer.
STEP_BYTES = 1 << 18
SHIFTED_STEP_BYTES = 1 << 20


def _widened(main_ref, halo_ref) -> jax.Array:
    """The step's tile plus its right halo, as int32."""
    return jnp.concatenate([main_ref[...].astype(jnp.int32),
                            halo_ref[...].astype(jnp.int32)], axis=1)


def _scan_kernel(pat_ref, main_ref, halo_ref, mask_ref, *, cols: int,
                 pat_len: int):
    """One grid step: compare a (rows, cols) tile against one pattern."""
    ext = _widened(main_ref, halo_ref)
    acc = ext[:, :cols] == pat_ref[0]
    for j in range(1, pat_len):  # unrolled: P is static
        acc = jnp.logical_and(acc, ext[:, j:j + cols] == pat_ref[j])
    mask_ref[...] = acc.astype(jnp.int32).astype(jnp.uint8)


def _scan_kernel_multi(pat_ref, main_ref, halo_ref, mask_ref, *, cols: int,
                       max_len: int):
    """One grid step with a *per-row* pattern (cross-request batching):
    pattern positions holding -1 (past the row's length) always match."""
    ext = _widened(main_ref, halo_ref)
    acc = ext[:, :cols] == pat_ref[:, 0:1]
    for j in range(1, max_len):  # unrolled: max_len is static per dispatch
        pj = pat_ref[:, j:j + 1]
        acc = jnp.logical_and(
            acc, jnp.logical_or(ext[:, j:j + cols] == pj, pj < 0))
    mask_ref[...] = acc.astype(jnp.int32).astype(jnp.uint8)


def _geometry(matrix: jax.Array, pat_len: int
              ) -> tuple[int, int, int, int]:
    nrows, padded_width = matrix.shape
    width = padded_width - ROWGROUP_PAD
    assert width > 0 and width % LANES == 0, \
        "matrix must be lane-aligned and carry the ROWGROUP_PAD zero tail"
    assert 0 < pat_len <= MAX_PATTERN
    budget = min(STEP_BYTES, SHIFTED_STEP_BYTES // pat_len)
    rows, cols = grid_tiles(nrows, width, unit=LANES, budget=budget)
    return nrows, width, rows, cols


@functools.partial(jax.jit, static_argnames=("pat_len",))
def pattern_scan_rowgroup(matrix: jax.Array, pattern_vec: jax.Array, *,
                          pat_len: int) -> jax.Array:
    """Match mask over a packed row-group matrix — one dispatch.

    ``matrix`` is ``(B, W + ROWGROUP_PAD)`` uint8 in the row-group
    layout; ``pattern_vec`` is ``(MAX_PATTERN,)`` int32 holding the
    pattern's bytes first. Returns a ``(B, W)`` uint8 mask (positions
    past each row's true length must be trimmed by the caller).
    """
    nrows, width, rows, cols = _geometry(matrix, pat_len)
    halo = cols // LANES
    kernel = functools.partial(_scan_kernel, cols=cols, pat_len=pat_len)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nrows // rows, width // cols),
            in_specs=[
                pl.BlockSpec((rows, cols), lambda i, j, p: (i, j)),
                pl.BlockSpec((rows, LANES),
                             lambda i, j, p: (i, (j + 1) * halo)),
            ],
            out_specs=pl.BlockSpec((rows, cols), lambda i, j, p: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((nrows, width), jnp.uint8),
        interpret=kernels.interpret_mode(),
    )(pattern_vec, matrix, matrix)


@functools.partial(jax.jit, static_argnames=("max_len",))
def pattern_scan_batch_multi(matrix: jax.Array, pattern_mat: jax.Array, *,
                             max_len: int) -> jax.Array:
    """Per-row-pattern match masks — **one** dispatch for a mixed batch.

    The cross-request primitive behind ``repro.serve.archive``: rows
    belonging to *different* queries (different patterns, same width
    bucket) share a single ``pallas_call``. ``pattern_mat`` is
    ``(B, MAX_PATTERN)`` int32, each row's pattern bytes followed by
    ``-1``; ``max_len`` bounds the static compare unroll (the longest
    true pattern in the batch). Layout and result as
    :func:`pattern_scan_rowgroup`.
    """
    nrows, width, rows, cols = _geometry(matrix, max_len)
    assert pattern_mat.shape == (nrows, MAX_PATTERN)
    halo = cols // LANES
    kernel = functools.partial(_scan_kernel_multi, cols=cols,
                               max_len=max_len)
    return pl.pallas_call(
        kernel,
        grid=(nrows // rows, width // cols),
        in_specs=[
            pl.BlockSpec((rows, MAX_PATTERN), lambda i, j: (i, 0)),
            pl.BlockSpec((rows, cols), lambda i, j: (i, j)),
            pl.BlockSpec((rows, LANES), lambda i, j: (i, (j + 1) * halo)),
        ],
        out_specs=pl.BlockSpec((rows, cols), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nrows, width), jnp.uint8),
        interpret=kernels.interpret_mode(),
    )(pattern_mat, matrix, matrix)
