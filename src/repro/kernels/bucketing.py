"""Shared ragged-batch helpers for the byte kernels' public wrappers.

The byte kernels (`adler32`, `pattern_scan`, `digest_sig`) batch ragged
payload lists into padded ``(B, W)`` matrices; ``bucket_width`` is the
common width-bucketing rule (one gridded dispatch per bucket, repeated
ragged batches reuse a bounded set of compiled shapes). Kept in one
place so the wrappers — and consumers that account dispatches, like the
index query engine — cannot drift apart.

Bucket boundaries are **half-step** quantized: sizes round up to
``m · 2^j`` blocks with mantissa ``m ∈ {2, 3}`` (plus the single-block
floor), i.e. the bucket ladder runs 1, 2, 3, 4, 6, 8, 12, 16, … blocks
instead of pure powers of two. The PR 7 dispatch profiler measured the
pure-pow2 rule shipping 90.2 % padding on ``digest_signature_batch``
(BENCH_ingest.json): a row just past a boundary padded up to 2×, and
row-count padding multiplied on top. Half-steps bound per-row width
waste at 1.5× (worst case, mean ≈1.2×) while only doubling the shape
ladder, so compiled-shape reuse stays high. The same quantizer pads
*row counts* (``quantize_count``), replacing the old pow2-multiple-of-
group rule that inflated a 6-row flush to 128 kernel rows.
"""
from __future__ import annotations

import numpy as np

__all__ = ["LANES", "ROWGROUP_PAD", "SMALL_BLOCK", "SUBLANES", "as_u8",
           "bucket_width", "dispatch_count", "grid_tiles", "payload_width",
           "quantize_count"]

# The TPU vector register is (SUBLANES, LANES) 32-bit words. A kernel
# block's last dimension is a multiple of LANES and its row count a
# multiple of SUBLANES, or either equals the whole array's extent.
LANES = 128
SUBLANES = 8

# Width-bucket granularity for payloads below one full kernel block
# (digest path: the 2048 Adler block is an overflow *bound*, not a width
# floor). Lane-aligned; yields the sub-block half-step ladder 256, 512,
# 768, 1024, 1536 under the 2048 boundary.
SMALL_BLOCK = 256

# Zero right-padding of packed row-group matrices: (B, width + ROWGROUP_PAD)
# uint8, payload left-justified, zeros after. Lane-aligned (128); bounds both
# the digest kernel's n-gram reach (n − 1) and the pattern kernel's window
# reach (MAX_PATTERN − 1), so one packed layout — in RAM or mmapped from a
# columnar shard — feeds both kernels with no halo input and no re-copy.
ROWGROUP_PAD = 128


def as_u8(data) -> np.ndarray:
    """View bytes-like or array input as a uint8 numpy array."""
    if isinstance(data, (bytes, bytearray, memoryview)):
        return np.frombuffer(bytes(data), dtype=np.uint8)
    return np.asarray(data, np.uint8)


def quantize_count(n: int) -> int:
    """Smallest half-step-pow2 value ≥ ``n``: 1, 2, 3, 4, 6, 8, 12, ….

    The shared shape quantizer for both bucket widths (in blocks) and
    padded row counts: worst-case padding 1.5× (3→4 gap is 1.33×,
    2→3 gap is 1.5×), shape ladder only 2× denser than pure pow2.
    """
    if n <= 1:
        return 1
    pow2 = 1 << (max(n, 1) - 1).bit_length()   # next power of two ≥ n
    half = (pow2 // 4) * 3                     # the 3·2^j step just below it
    return half if half >= n else pow2


def bucket_width(size: int, block: int) -> int:
    """Block-multiple width bucket: half-step quantized block count."""
    nblocks = max((size + block - 1) // block, 1)
    return block * quantize_count(nblocks)


def payload_width(size: int, block: int, small: int | None = SMALL_BLOCK
                  ) -> int:
    """Width bucket with sub-block granularity below one block.

    The digest/derive bucketing rule: payloads that fit in a single
    kernel block take finer ``small``-granular buckets (the whole row is
    one block, so nothing forces the full-block floor — a shard's tiny
    request/metadata records were the dominant term of the measured
    90.2% pad waste). Larger payloads use the block-multiple ladder.
    """
    if small and size <= block:
        return min(bucket_width(size, small), block)
    return bucket_width(size, block)


def grid_tiles(nrows: int, width: int, *, unit: int, budget: int,
               max_cols: int | None = None) -> tuple[int, int]:
    """``(rows, cols)`` of one kernel grid step over a ``(nrows, width)``
    byte matrix.

    A step costs its rows rounded up to :data:`SUBLANES` (the vector
    registers a short tile still fills) times ``cols``. ``cols`` is
    ``width`` halved while the step would exceed ``budget`` at its fewest
    rows (or exceed ``max_cols``), as long as the half stays a multiple
    of ``unit``; ``rows`` is all of ``nrows`` when that fits, else the
    largest multiple of SUBLANES that divides ``nrows`` and fits. A row
    count that is not a SUBLANES multiple can only be tiled whole.
    Counts from :func:`quantize_count` (``m·2^k``, m ∈ {1, 3}) above 12
    are SUBLANES multiples, so the divisor exists.
    """
    def cost(rows: int, cols: int) -> int:
        return -(-rows // SUBLANES) * SUBLANES * cols

    min_rows = SUBLANES if nrows % SUBLANES == 0 else nrows
    cols = width
    while ((cost(min_rows, cols) > budget or (max_cols and cols > max_cols))
           and cols % (2 * unit) == 0):
        cols //= 2
    if cost(nrows, cols) <= budget or min_rows == nrows:
        return nrows, cols
    rows = max(SUBLANES, budget // cols // SUBLANES * SUBLANES)
    while nrows % rows:
        rows -= SUBLANES
    return rows, cols


def dispatch_count(sizes, block: int) -> int:
    """Kernel dispatches a batch of these payload sizes costs: one per
    distinct width bucket (what the batched wrappers actually issue).
    Consumers that account dispatches (query engine, serve gateway)
    share this so their books match the wrappers."""
    return len({bucket_width(int(s), block) for s in sizes})
