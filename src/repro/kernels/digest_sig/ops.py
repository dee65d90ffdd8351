"""Public wrapper: fused digests + signatures for ragged payload batches.

``digest_signature_batch`` stacks a ragged batch of record payloads into
power-of-two width buckets (the shared :mod:`repro.kernels.bucketing`
rule, so dispatch accounting matches the other byte kernels), sweeps
each bucket **once** through the fused Pallas kernel, and finishes on
the host:

* Adler-32: the kernel's ``(S, T)`` partials reduce through the same
  :func:`repro.kernels.adler32.ops.combine_partials` the plain digest
  path uses — entry-wise equal to ``zlib.adler32``.
* signatures: the kernel's n-gram hash matrix feeds the shared
  double-hash position derivation
  (:func:`repro.index.signature.positions_from_hashes`) and the batch
  ``packbits`` fold — bit-identical to
  :func:`repro.index.signature.signature_of` per row.

This is the index build's single-sweep hot path: each payload byte is
read once by the kernel; all host work after it is O(#n-grams) on hash
values, never on payload bytes.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.kernels.adler32.ops import combine_partials
from repro.kernels.bucketing import (
    as_u8 as _as_u8,
    payload_width,
    quantize_count,
)
from repro.obs.kernels import record_dispatch
from .digest_sig import BLOCK, HPAD, MAX_NGRAM, digest_sig_partials_batch

__all__ = ["digest_signature_batch", "digest_signature_rowgroup"]


def _pad_rows(n: int) -> int:
    """Row-count bucket: half-step quantized (1, 2, 3, 4, 6, 8, 12, …),
    so repeated ragged batches reuse a bounded set of compiled shapes
    while row padding stays ≤ 1.5× (pad rows are all-zero; their outputs
    are discarded). The kernel's row tiles divide any quantized count."""
    return quantize_count(n)


def _sig_geometry(bits: int | None, n: int | None, k: int | None
                  ) -> tuple[int, int, int]:
    """Validated signature geometry, defaulting to the index constants."""
    from repro.index.signature import SIG_BITS, SIG_HASHES, SIG_NGRAM

    bits = SIG_BITS if bits is None else bits
    n = SIG_NGRAM if n is None else n
    k = SIG_HASHES if k is None else k
    if bits <= 0 or bits & (bits - 1) or bits % 64:
        raise ValueError(f"bits must be a power of two multiple of 64, "
                         f"got {bits}")
    if not 1 < n <= MAX_NGRAM or k < 1:
        raise ValueError(f"need 2 <= n <= {MAX_NGRAM} and k >= 1")
    return bits, n, k


def _host_fold(s, t, h, lengths: np.ndarray, *, width: int, bits: int,
               n: int, k: int, block: int) -> tuple[np.ndarray, np.ndarray]:
    """Finish the fused sweep on the host for the first ``len(lengths)``
    rows of the kernel partials: Adler combine + hash → k bit positions
    → flat packbits fold. All O(#n-grams) on hash values — payload bytes
    were consumed by the single kernel sweep. Valid n-grams are a
    per-row prefix, so the flat gather indices come from repeat/cumsum —
    no boolean mask sweep."""
    from repro.index.signature import fold_positions_rows, positions_from_hashes

    live = lengths.size
    # full-array np.asarray is zero-copy on the CPU backend; slicing
    # happens host-side (a device-side h[:live] would dispatch + copy)
    s_np, t_np, h_np = np.asarray(s), np.asarray(t), np.asarray(h)
    digests = combine_partials(s_np[:live], t_np[:live], lengths, block)
    hu = h_np.view(np.uint32)
    m = np.maximum(lengths - (n - 1), 0)         # valid n-grams per row
    rows = np.arange(live, dtype=np.int64)
    offs = np.cumsum(m) - m                      # per-row prefix starts
    gidx = np.arange(int(m.sum()), dtype=np.int64)
    gidx += np.repeat(rows * width - offs, m)    # flat (row, col) index
    hv = hu.ravel()[gidx]
    pos = positions_from_hashes(hv, bits, k)     # (k, total) planes
    sigs = fold_positions_rows(live, np.repeat(rows, m), pos, bits)
    return digests, sigs


def digest_signature_batch(payloads, *, bits: int | None = None,
                           n: int | None = None, k: int | None = None,
                           block: int = BLOCK
                           ) -> tuple[np.ndarray, np.ndarray]:
    """Adler-32 digests **and** n-gram signatures of a ragged batch,
    one fused kernel sweep per width bucket.

    Returns ``(digests, signatures)``: uint32 ``(B,)`` matching
    ``zlib.adler32`` and uint64 ``(B, bits // 64)`` matching
    ``signature_of`` row-wise. ``bits`` must be a power of two (the
    position masking and packbits fold rely on it); the signature
    geometry defaults to the :mod:`repro.index.signature` constants.
    """
    bits, n, k = _sig_geometry(bits, n, k)
    bufs = [_as_u8(p) for p in payloads]
    nrows = len(bufs)
    digests = np.empty(nrows, np.uint32)
    sigs = np.zeros((nrows, bits // 64), np.uint64)
    if nrows == 0:
        return digests, sigs
    buckets: dict[int, list[int]] = {}
    for i, buf in enumerate(bufs):
        # BLOCK is the Adler overflow *bound*, not a width floor: payloads
        # below one block take sub-block width buckets (the whole row is a
        # single Adler block) — see payload_width
        buckets.setdefault(payload_width(buf.size, block), []).append(i)
    for width, idxs in buckets.items():
        kblock = min(block, width)  # sub-2048 widths are one Adler block
        padded = np.zeros((_pad_rows(len(idxs)), width + HPAD), np.uint8)
        for row, i in enumerate(idxs):
            padded[row, :bufs[i].size] = bufs[i]
        lengths = np.asarray([bufs[i].size for i in idxs], np.int64)
        record_dispatch("digest_signature_batch", width=width,
                        rows=len(idxs), padded_rows=padded.shape[0],
                        useful_bytes=int(lengths.sum()))
        s, t, h = digest_sig_partials_batch(jnp.asarray(padded), n=n,
                                            block=kblock)
        digests[idxs], sigs[idxs] = _host_fold(
            s, t, h, lengths, width=width, bits=bits, n=n, k=k, block=kblock)
    return digests, sigs


def digest_signature_rowgroup(matrix, lengths, *, bits: int | None = None,
                              n: int | None = None, k: int | None = None,
                              block: int = BLOCK
                              ) -> tuple[np.ndarray, np.ndarray]:
    """Fused digests + signatures over an **already-packed row-group**.

    The columnar derive/scan entry point: ``matrix`` is a
    ``(B, width + HPAD)`` uint8 row-group in the kernel's native layout
    (payload bytes left-justified, zero tail ≥ HPAD — exactly what
    :mod:`repro.columnar.store` mmaps from disk), ``lengths`` the true
    payload lengths of the first ``len(lengths)`` rows; trailing rows
    are padding. No per-payload copy or re-bucketing happens here — the
    packing cost was paid once at derive time, so pad waste is whatever
    the row-group packer achieved, not the ragged-batch bucketing rule.

    Returns ``(digests, signatures)`` for the live rows, bit-identical
    to :func:`digest_signature_batch` on the same payloads.
    """
    bits, n, k = _sig_geometry(bits, n, k)
    mat = np.ascontiguousarray(matrix, np.uint8)
    nrows, padded_width = mat.shape
    width = padded_width - HPAD
    if width <= 0 or width % block:
        raise ValueError(f"row-group width {padded_width} must be HPAD "
                         f"plus a multiple of block={block}")
    lengths = np.asarray(lengths, np.int64)
    live = lengths.size
    if not 0 < live <= nrows:
        raise ValueError(f"need 1 <= live rows <= {nrows}, got {live}")
    if lengths.max(initial=0) > width:
        raise ValueError("length exceeds row-group width")
    record_dispatch("digest_signature_rowgroup", width=width, rows=live,
                    padded_rows=nrows, useful_bytes=int(lengths.sum()))
    s, t, h = digest_sig_partials_batch(jnp.asarray(mat), n=n, block=block)
    return _host_fold(s, t, h, lengths, width=width, bits=bits, n=n, k=k,
                      block=block)
