"""Public jit'd wrappers for the pattern-scan kernel.

``find_pattern_mask`` scans one buffer; ``find_pattern_mask_batch`` packs
a ragged batch of payloads into padded byte matrices and issues one
gridded dispatch per half-step **width bucket** (parity with
``adler32_batch``): a uniform batch costs a single dispatch, repeated
ragged batches reuse a handful of compiled shapes instead of recompiling
per max-length, and one giant outlier cannot inflate every row to its
width. Every matrix is packed in the row-group layout (payload
left-justified, ``ROWGROUP_PAD`` zero tail) the kernel reads.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.kernels.bucketing import (
    LANES,
    ROWGROUP_PAD,
    as_u8 as _as_u8,
    bucket_width,
    quantize_count,
)
from repro.obs.kernels import record_dispatch
from .pattern_scan import (
    DEFAULT_BLOCK,
    MAX_PATTERN,
    pattern_scan_batch_multi,
    pattern_scan_rowgroup,
)

__all__ = ["find_pattern_mask", "find_pattern_mask_batch",
           "find_pattern_mask_rowgroup", "find_pattern_masks_multi",
           "find_pattern_positions", "count_matches"]


def _check_pattern(pattern) -> tuple[np.ndarray, int]:
    """The kernel's pattern vector: ``(MAX_PATTERN,)`` int32, the bytes
    first and -1 after them, plus the true length."""
    pat = _as_u8(pattern)
    if not 0 < pat.size <= MAX_PATTERN:
        raise ValueError(f"pattern length must be in [1, {MAX_PATTERN}]")
    # the zero tail never false-positives: pattern bytes are non-zero in
    # WARC use; all-zero patterns are rejected to keep that invariant
    if not pat.any():
        raise ValueError("all-zero patterns are not supported")
    vec = np.full(MAX_PATTERN, -1, dtype=np.int32)
    vec[:pat.size] = pat
    return vec, int(pat.size)


def _check_block(block: int) -> None:
    if block <= 0 or block % LANES:
        raise ValueError(f"block must be a positive multiple of {LANES}, "
                         f"got {block}")


def _pack(bufs: list[np.ndarray], width: int) -> np.ndarray:
    """Stack ragged buffers into the ``(B, width + ROWGROUP_PAD)``
    row-group layout."""
    mat = np.zeros((len(bufs), width + ROWGROUP_PAD), dtype=np.uint8)
    for i, buf in enumerate(bufs):
        mat[i, :buf.size] = buf
    return mat


def _pad_rows(n: int) -> int:
    """Row-count bucket: half-step quantized (1, 2, 3, 4, 6, 8, 12, …),
    so repeated ragged batches reuse a bounded set of compiled ``(B, W)``
    shapes along B as well as W while row padding stays ≤ 1.5× (pad rows
    are all-zero buffers; their masks are discarded)."""
    return quantize_count(n)


def _trim(mask_row: np.ndarray, n: int, plen: int) -> np.ndarray:
    out = np.array(mask_row[:n])  # own the buffer: device arrays are read-only
    # matches that would read past the true end are padding artifacts
    if plen > 1 and n >= plen:
        out[n - plen + 1:] = 0
    elif n < plen:
        out[:] = 0
    return out


def find_pattern_mask_batch(bufs, pattern, *, block: int = DEFAULT_BLOCK
                            ) -> list[np.ndarray]:
    """uint8 match masks for a ragged batch — few kernel dispatches.

    Returns one mask per input, each the same length as its buffer.
    Inputs are grouped into half-step width buckets of ``block``-byte
    units — one gridded call per bucket — so a uniform batch is a single
    dispatch and ragged query batches hit a bounded set of compiled
    shapes (padding waste ≤ 1.5× per row).
    """
    pat_vec, plen = _check_pattern(pattern)
    _check_block(block)
    arrs = [_as_u8(b) for b in bufs]
    if not arrs:
        return []
    out: list = [None] * len(arrs)
    buckets: dict[int, list[int]] = {}
    for i, arr in enumerate(arrs):
        buckets.setdefault(bucket_width(arr.size, block), []).append(i)
    empty = np.empty(0, np.uint8)
    for width, idxs in buckets.items():
        rows = [arrs[i] for i in idxs]
        rows += [empty] * (_pad_rows(len(rows)) - len(rows))
        record_dispatch("find_pattern_mask_batch", width=width,
                        rows=len(idxs), padded_rows=len(rows),
                        useful_bytes=sum(arrs[i].size for i in idxs))
        masks = pattern_scan_rowgroup(jnp.asarray(_pack(rows, width)),
                                      jnp.asarray(pat_vec), pat_len=plen)
        masks = np.asarray(masks)
        for row, i in enumerate(idxs):
            out[i] = _trim(masks[row], arrs[i].size, plen)
    return out


def find_pattern_masks_multi(bufs, patterns, *, block: int = DEFAULT_BLOCK
                             ) -> list[np.ndarray]:
    """Match masks for a ragged batch where **each row has its own
    pattern** — the cross-request batching entry point.

    ``patterns[i]`` scans ``bufs[i]``; rows from different queries that
    land in the same width bucket share one
    ``pattern_scan_batch_multi`` dispatch (the unroll bound is the
    bucket's longest pattern). Same bucketing/trim semantics as
    :func:`find_pattern_mask_batch`, so for equal patterns the two are
    interchangeable.
    """
    if len(bufs) != len(patterns):
        raise ValueError("bufs and patterns must pair up")
    _check_block(block)
    arrs = [_as_u8(b) for b in bufs]
    pats: list[np.ndarray] = []
    plens: list[int] = []
    for p in patterns:
        vec, n = _check_pattern(p)
        pats.append(vec)
        plens.append(n)
    if not arrs:
        return []
    out: list = [None] * len(arrs)
    buckets: dict[int, list[int]] = {}
    for i, arr in enumerate(arrs):
        buckets.setdefault(bucket_width(arr.size, block), []).append(i)
    empty = np.empty(0, np.uint8)
    pad_pat = np.full(MAX_PATTERN, -1, np.int32)
    pad_pat[0] = 1  # inert: never matches an all-zero pad row
    for width, idxs in buckets.items():
        rows = [arrs[i] for i in idxs]
        n_pad = _pad_rows(len(rows)) - len(rows)
        rows += [empty] * n_pad
        pat_mat = np.stack([pats[i] for i in idxs] + [pad_pat] * n_pad)
        record_dispatch("find_pattern_masks_multi", width=width,
                        rows=len(idxs), padded_rows=len(rows),
                        useful_bytes=sum(arrs[i].size for i in idxs))
        masks = pattern_scan_batch_multi(
            jnp.asarray(_pack(rows, width)), jnp.asarray(pat_mat),
            max_len=max(plens[i] for i in idxs))
        masks = np.asarray(masks)
        for row, i in enumerate(idxs):
            out[i] = _trim(masks[row], arrs[i].size, plens[i])
    return out


def _trim_rows(masks: np.ndarray, lengths: np.ndarray, plen: int
               ) -> np.ndarray:
    """Vectorized :func:`_trim` over row-group masks: zero every position
    whose match window would read past its row's true length."""
    last = np.maximum(lengths[:, None] - plen + 1, 0)
    return np.where(np.arange(masks.shape[1])[None, :] < last, masks, 0)


def find_pattern_mask_rowgroup(matrix, lengths, pattern, *,
                               trim: bool = True) -> np.ndarray:
    """Match masks over an **already-packed row-group** — one dispatch.

    The columnar scan entry point: ``matrix`` is ``(B, width +
    ROWGROUP_PAD)`` uint8 in the shared row-group layout (typically a
    zero-copy mmap view of a columnar shard), ``lengths`` the true
    payload lengths of the first ``len(lengths)`` rows (trailing rows
    are padding). No per-payload copy or re-bucketing —
    the packing cost was paid once at derive time. Returns a
    ``(live, width)`` uint8 mask, trimmed per row exactly like
    :func:`find_pattern_mask_batch` trims its outputs.

    ``trim=False`` skips the per-row trim and hands back the raw
    kernel output (a read-only view of the device buffer): positions
    past ``length - len(pattern) + 1`` may carry padding artifacts the
    caller must filter out. The column-scan hot path does exactly that
    on the compacted hit list, saving the full-matrix where-copy.
    """
    pat_vec, plen = _check_pattern(pattern)
    mat = np.ascontiguousarray(matrix, np.uint8)
    nrows, padded_width = mat.shape
    width = padded_width - ROWGROUP_PAD
    if width <= 0 or width % LANES:
        raise ValueError("matrix must be lane-aligned and carry the "
                         "ROWGROUP_PAD zero tail")
    lengths = np.asarray(lengths, np.int64)
    live = lengths.size
    if not 0 < live <= nrows:
        raise ValueError(f"need 1 <= live rows <= {nrows}, got {live}")
    record_dispatch("find_pattern_mask_rowgroup", width=width, rows=live,
                    padded_rows=nrows, useful_bytes=int(lengths.sum()))
    masks = pattern_scan_rowgroup(jnp.asarray(mat), jnp.asarray(pat_vec),
                                  pat_len=plen)
    if not trim:
        return np.asarray(masks)[:live]
    return _trim_rows(np.asarray(masks)[:live], lengths, plen)


def find_pattern_mask(buf, pattern, *, block: int = DEFAULT_BLOCK):
    """uint8 match mask (same length as ``buf``)."""
    return find_pattern_mask_batch([buf], pattern, block=block)[0]


def find_pattern_positions(buf, pattern, **kw) -> np.ndarray:
    """Sorted match start offsets (host-side compaction of the mask)."""
    return np.flatnonzero(find_pattern_mask(buf, pattern, **kw))


def count_matches(buf, pattern, **kw) -> int:
    return int(find_pattern_mask(buf, pattern, **kw).sum())
