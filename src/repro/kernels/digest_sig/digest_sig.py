"""Pallas kernel: fused Adler-32 + n-gram-signature sweep (DESIGN.md §9).

CDX index construction needs two per-record byte reductions: the Adler-32
content digest and the Bloom-style n-gram signature
(:mod:`repro.index.signature`). Shipping them as separate passes walks
every payload byte twice; this kernel fuses both into **one** batched
sweep over a padded ``(B, W + HPAD)`` byte matrix:

* per ``block``-byte sub-block it emits the Adler partials
  ``S_j = Σ b, T_j = Σ t·b`` (same partial layout and the same in-kernel
  reduction as :mod:`repro.kernels.adler32` — the host combiner is
  shared), and
* the rolling polynomial hash of every overlapping byte n-gram,
  ``h_i = Σ_{j<n} b_{i+j}·P^{n-1-j}`` (uint32 wraparound, the exact
  formula of :func:`repro.index.signature._ngram_hashes`), one lane per
  position.

Tiling: grid step ``(i, j)`` maps a ``(rows, cols)`` tile of the payload
columns (:func:`repro.kernels.bucketing.grid_tiles`, ``cols`` a multiple
of ``block``) plus, through a second ``BlockSpec`` over the same array,
the 128 columns just past it — the halo every n-gram starting in the tile
reads (the last tile's halo is the ``HPAD`` zero tail). Width is walked
by the grid, so a 1 MiB row costs grid steps, not a static unroll. Bytes
are widened to int32 in VMEM (v5e's VPU does no 8-bit arithmetic); int32
wraparound multiplies match uint32 mod-2³² semantics on both TPU and in
interpret mode.

The (cheap, O(#n-grams)) double-hash fold of hash values into signature
bit positions stays on the host (:mod:`.ops`): it touches hashes, not
payload bytes, so the "each payload byte is touched once" property of
the fused build is preserved.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels
from repro.kernels.adler32.adler32 import (
    MAX_SUBBLOCKS,
    block_partials,
    gather_partials,
)
from repro.kernels.bucketing import LANES, ROWGROUP_PAD, grid_tiles

BLOCK = 2048          # Adler overflow bound: T_j < 2048·2047/2·255 < 2³¹
HPAD = ROWGROUP_PAD   # zero right-padding (lane-aligned); bounds n − 1
FNV_PRIME = 0x01000193  # matches repro.index.signature._FNV_PRIME
MAX_NGRAM = 16          # longest n-gram the kernel takes (VMEM, below)
# Payload bytes per grid step. Besides the double-buffered int32 hash
# output, Mosaic keeps a widened int32 copy of the tile live for every
# n-gram shift (~3.2 B of scoped VMEM per payload byte each, against
# v5e's 16 MiB limit), so the step shrinks as n grows past 8 and n is
# capped at MAX_NGRAM.
STEP_BYTES = 1 << 18
SHIFTED_STEP_BYTES = 1 << 21


def _digest_sig_kernel(main_ref, halo_ref, s_ref, t_ref, h_ref, *,
                       cols: int, block: int, n: int):
    """One grid step: Adler partials + n-gram hashes of a (rows, cols) tile."""
    ext = jnp.concatenate([main_ref[...].astype(jnp.int32),
                           halo_ref[...].astype(jnp.int32)], axis=1)
    data = ext[:, :cols]
    s_ref[...], t_ref[...] = block_partials(data, block)
    h = data
    for j in range(1, n):                      # static unroll: n-gram poly
        h = h * FNV_PRIME + ext[:, j:j + cols]  # int32 wrap == mod 2^32
    h_ref[...] = h


@functools.partial(jax.jit, static_argnames=("n", "block"))
def digest_sig_partials_batch(padded_bufs: jax.Array, *, n: int,
                              block: int = BLOCK
                              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused per-(row, block) partials over a padded byte matrix.

    ``padded_bufs`` is ``(B, W + HPAD)`` uint8 — payload bytes in the
    first ``W`` columns (``W % block == 0``, ``block`` a lane multiple of
    at most 2048), zeros after. Returns ``(S, T, H)``: two
    ``(B, W // block)`` int32 Adler partial arrays plus the ``(B, W)``
    int32 n-gram hash matrix (uint32 bit patterns). One call sweeps the
    whole batch once.
    """
    nrows, padded_width = padded_bufs.shape
    width = padded_width - HPAD
    assert 0 < block <= BLOCK and block % LANES == 0, \
        "block must be a lane multiple of at most the overflow bound"
    assert width > 0 and width % block == 0, \
        "wrapper must pad to HPAD plus a block multiple"
    assert 1 < n <= MAX_NGRAM
    rows, cols = grid_tiles(
        nrows, width, unit=block,
        budget=min(STEP_BYTES, SHIFTED_STEP_BYTES // n),
        max_cols=MAX_SUBBLOCKS * block)
    steps = width // cols
    halo = cols // LANES
    kernel = functools.partial(_digest_sig_kernel, cols=cols, block=block,
                               n=n)
    partial_spec = pl.BlockSpec((rows, LANES), lambda i, j: (i, j))
    s, t, h = pl.pallas_call(
        kernel,
        grid=(nrows // rows, steps),
        in_specs=[
            pl.BlockSpec((rows, cols), lambda i, j: (i, j)),
            pl.BlockSpec((rows, LANES), lambda i, j: (i, (j + 1) * halo)),
        ],
        out_specs=[partial_spec, partial_spec,
                   pl.BlockSpec((rows, cols), lambda i, j: (i, j))],
        out_shape=[
            jax.ShapeDtypeStruct((nrows, steps * LANES), jnp.int32),
            jax.ShapeDtypeStruct((nrows, steps * LANES), jnp.int32),
            jax.ShapeDtypeStruct((nrows, width), jnp.int32),
        ],
        interpret=kernels.interpret_mode(),
    )(padded_bufs, padded_bufs)
    per_step = cols // block
    return gather_partials(s, per_step), gather_partials(t, per_step), h
