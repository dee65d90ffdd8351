"""Public wrappers: Adler-32 of byte buffers via the Pallas kernel.

``adler32`` checksums one buffer; ``adler32_batch`` stacks a ragged batch
of payloads into one ``(B, W)`` matrix per width bucket and issues one
gridded ``pallas_call`` per bucket — N record checksums for a few
dispatches (DESIGN.md §4).
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.kernels.bucketing import (
    LANES,
    as_u8 as _as_u8,
    bucket_width,
    quantize_count,
)
from repro.obs.kernels import record_dispatch
from .adler32 import BLOCK, MOD, adler32_partials_batch

__all__ = ["adler32", "adler32_batch", "combine_partials"]


def combine_partials(s: np.ndarray, t: np.ndarray, lengths: np.ndarray,
                     block: int) -> np.ndarray:
    """Host-side reduction of per-block partials to final checksums.

    Zero padding contributes nothing to S or T, so full-row sums with each
    row's *true* length are exact for every ragged entry. Shared with the
    fused ``digest_signature_batch`` wrapper, whose kernel emits the same
    ``(S, T)`` partial layout.
    """
    s = s.astype(np.int64)
    t = t.astype(np.int64)
    offsets = np.arange(s.shape[1], dtype=np.int64) * block   # o_j
    n = lengths.astype(np.int64)[:, None]                     # (B, 1)
    a = (1 + s.sum(axis=1)) % MOD
    b = (n[:, 0] + ((n - offsets) * s - t).sum(axis=1)) % MOD
    out = ((b << 16) | a).astype(np.uint32)
    out[lengths == 0] = 1  # adler32(b"") == 1
    return out


def adler32_batch(payloads, *, block: int = BLOCK) -> np.ndarray:
    """Adler-32 of every payload in a ragged batch (few kernel dispatches).

    Returns a uint32 array matching ``zlib.adler32`` entry-wise. Payloads
    are zero-padded and grouped into half-step width buckets — one
    gridded call per bucket — so a uniform batch costs a single dispatch
    while one giant outlier cannot inflate every row to its width
    (padding waste is bounded at 1.5× per row, not B × max_len). Row
    counts are half-step quantized too, so the kernel's row tiles divide
    them.
    """
    if not 0 < block <= BLOCK or block % LANES:
        raise ValueError(f"block must be a multiple of {LANES} in "
                         f"[{LANES}, {BLOCK}], got {block}")
    bufs = [_as_u8(p) for p in payloads]
    nrows = len(bufs)
    if nrows == 0:
        return np.empty(0, np.uint32)
    out = np.empty(nrows, np.uint32)
    buckets: dict[int, list[int]] = {}
    for i, buf in enumerate(bufs):
        buckets.setdefault(bucket_width(buf.size, block), []).append(i)
    for width, idxs in buckets.items():
        padded = np.zeros((quantize_count(len(idxs)), width), dtype=np.uint8)
        for row, i in enumerate(idxs):
            padded[row, :bufs[i].size] = bufs[i]
        lengths = np.asarray([bufs[i].size for i in idxs], np.int64)
        record_dispatch("adler32_batch", width=width, rows=len(idxs),
                        padded_rows=padded.shape[0],
                        useful_bytes=int(lengths.sum()))
        s, t = adler32_partials_batch(jnp.asarray(padded), block=block)
        live = len(idxs)
        out[idxs] = combine_partials(np.asarray(s)[:live],
                                     np.asarray(t)[:live], lengths, block)
    return out


def adler32(data, *, block: int = BLOCK) -> int:
    """Adler-32 checksum (matches ``zlib.adler32``)."""
    buf = _as_u8(data)
    if buf.size == 0:
        return 1
    return int(adler32_batch([buf], block=block)[0])
