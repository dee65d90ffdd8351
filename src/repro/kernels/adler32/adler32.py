"""Pallas kernel: Adler-32 rolling checksum as blocked reductions.

The TPU-side record checksum (DESIGN.md §4). CRC-32's per-bit feedback
loop has no VPU mapping, but Adler-32 — zlib's other checksum —
decomposes into two reductions. With ``b`` the bytes and n = len(b):

    A = 1 + Σ b_i                      (mod 65521)
    B = n + Σ (n - i) · b_i            (mod 65521, i zero-based)

Per block j at offset o_j of length L, the kernel emits

    S_j = Σ_t b_{o_j+t}              (plain sum)
    T_j = Σ_t t · b_{o_j+t}          (dot with iota)

and the wrapper combines: B = n + Σ_j [(n − o_j)·S_j − T_j]  (mod 65521).

Block length 2048 keeps T_j < 2³¹ in int32 (2048·2047/2·255 ≈ 5.3e8), so
the kernel needs no in-loop modulo; the wrapper reduces in int64 once.
The byte sum and the iota dot both vectorize across the (8, 128) VPU.

**Batched dispatch**: record payloads are stacked into a ``(B, W)`` byte
matrix (rows zero-padded — zero bytes contribute nothing to either sum)
and one ``pallas_call`` checksums the whole batch. Grid step ``(i, j)``
maps a ``(rows, cols)`` tile (:func:`repro.kernels.bucketing.grid_tiles`)
and writes its ``cols // block`` partials into the first lanes of one
128-lane output block; the jitted wrapper gathers them back into
``(B, W // block)`` order. Bytes are widened to int32 in VMEM: v5e's
VPU does no 8-bit arithmetic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels
from repro.kernels.bucketing import LANES, grid_tiles

BLOCK = 2048
MOD = 65521
STEP_BYTES = 1 << 18  # payload bytes per grid step (VMEM budget)
MAX_SUBBLOCKS = 16    # partials per grid step: bounds the static unroll


def block_partials(data: jax.Array, block: int
                   ) -> tuple[jax.Array, jax.Array]:
    """``(S, T)`` of each ``block``-wide column block of int32 ``data``,
    partial ``c`` in lane ``c`` of two ``(rows, LANES)`` arrays. Shared
    with the fused :mod:`repro.kernels.digest_sig` kernel."""
    rows, cols = data.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    s = t = jnp.zeros((rows, LANES), jnp.int32)
    for c in range(cols // block):  # static: at most MAX_SUBBLOCKS
        seg = data[:, c * block:(c + 1) * block]
        s = jnp.where(lane == c, jnp.sum(seg, axis=1, keepdims=True), s)
        t = jnp.where(lane == c, jnp.sum(seg * iota, axis=1, keepdims=True),
                      t)
    return s, t


def gather_partials(p: jax.Array, per_step: int) -> jax.Array:
    """``(B, steps·LANES)`` kernel partials → ``(B, steps·per_step)``."""
    nrows = p.shape[0]
    return p.reshape(nrows, -1, LANES)[:, :, :per_step].reshape(nrows, -1)


def _adler_kernel(buf_ref, s_ref, t_ref, *, block: int):
    s_ref[...], t_ref[...] = block_partials(
        buf_ref[...].astype(jnp.int32), block)


@functools.partial(jax.jit, static_argnames=("block",))
def adler32_partials_batch(padded_bufs: jax.Array, *, block: int = BLOCK
                           ) -> tuple[jax.Array, jax.Array]:
    """Per-(row, block) ``(S, T)`` int32 partials over a padded byte matrix.

    ``padded_bufs`` is ``(B, W)`` uint8 with ``W % block == 0`` and
    ``block`` a lane multiple of at most 2048 (the overflow bound);
    returns two ``(B, W // block)`` arrays. One call covers the whole
    batch.
    """
    nrows, width = padded_bufs.shape
    assert 0 < block <= BLOCK and block % LANES == 0 and width % block == 0
    rows, cols = grid_tiles(nrows, width, unit=block, budget=STEP_BYTES,
                            max_cols=MAX_SUBBLOCKS * block)
    steps = width // cols
    kernel = functools.partial(_adler_kernel, block=block)
    spec = pl.BlockSpec((rows, LANES), lambda i, j: (i, j))
    s, t = pl.pallas_call(
        kernel,
        grid=(nrows // rows, steps),
        in_specs=[pl.BlockSpec((rows, cols), lambda i, j: (i, j))],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((nrows, steps * LANES), jnp.int32)
                   ] * 2,
        interpret=kernels.interpret_mode(),
    )(padded_bufs)
    return (gather_partials(s, cols // block),
            gather_partials(t, cols // block))
