"""Fused Adler-32 + n-gram-signature batch kernel (DESIGN.md §9).

One batched Pallas sweep produces both per-record CDX byte columns —
the content digest and the query pre-filter signature — so index
construction touches each payload byte once.
"""
from .digest_sig import (
    BLOCK,
    FNV_PRIME,
    HPAD,
    MAX_NGRAM,
    digest_sig_partials_batch,
)
from .ops import digest_signature_batch, digest_signature_rowgroup
from .ref import digest_signature_reference

__all__ = [
    "BLOCK",
    "FNV_PRIME",
    "HPAD",
    "MAX_NGRAM",
    "digest_sig_partials_batch",
    "digest_signature_batch",
    "digest_signature_rowgroup",
    "digest_signature_reference",
]
