"""Record digest computation/verification (Table 1 "+Checksum" rows).

WARC records carry ``WARC-Block-Digest`` / ``WARC-Payload-Digest`` headers
of the form ``sha1:<base32>`` (also ``md5:``/``sha256:`` in the wild, and
``crc32:``/``adler32:`` as cheap in-pipeline checks). SHA-1/MD5/SHA-256 run
through hashlib's C core on the host; CRC-32 through ``zlib.crc32``.

Adler-32 additionally has a TPU-side Pallas kernel
(:mod:`repro.kernels.adler32`) — see DESIGN.md §4: CRC's bit-feedback loop
does not transfer to the TPU vector unit, Adler's two running sums do.
"""
from __future__ import annotations

import base64
import hashlib
import zlib

_HASHLIB_ALGOS = {"sha1", "md5", "sha256"}


def block_digest(data: bytes | memoryview, algo: str = "sha1") -> str:
    """Digest in WARC header notation, e.g. ``sha1:3I42H3S6...``."""
    algo = algo.lower()
    if algo in _HASHLIB_ALGOS:
        raw = hashlib.new(algo, data).digest()
        return f"{algo}:{base64.b32encode(raw).decode('ascii')}"
    if algo == "crc32":
        return f"crc32:{zlib.crc32(data) & 0xFFFFFFFF:08x}"
    if algo == "adler32":
        return f"adler32:{zlib.adler32(data) & 0xFFFFFFFF:08x}"
    raise ValueError(f"unsupported digest algorithm: {algo}")


def verify_digest(data: bytes | memoryview, header_value: str) -> bool:
    """Check ``data`` against a ``algo:value`` WARC digest header."""
    algo, _, expected = header_value.partition(":")
    algo = algo.strip().lower()
    expected = expected.strip()
    if algo in _HASHLIB_ALGOS:
        raw = hashlib.new(algo, data).digest()
        if base64.b32encode(raw).decode("ascii") == expected.upper():
            return True
        # tolerate hex notation, which some writers emit instead of base32
        try:
            return bytes.fromhex(expected) == raw
        except ValueError:
            return False
    if algo in ("crc32", "adler32"):
        try:
            want = int(expected, 16)
        except ValueError:  # malformed digest value: mismatch, not a crash
            return False
        got = zlib.crc32(data) if algo == "crc32" else zlib.adler32(data)
        return (got & 0xFFFFFFFF) == want
    return False


def verify_digests_bulk(datas, header_values, *, use_kernel: bool = True
                        ) -> list[bool]:
    """Verify many ``algo:value`` digest headers at once.

    The batched path exists for the Adler-32 entries: instead of one
    device dispatch per record, every adler32-digested payload in the
    batch is checksummed by a single ``(B, nblocks)``-gridded Pallas call
    (:func:`repro.kernels.adler32.adler32_batch`) and compared host-side.
    All other algorithms fall back to :func:`verify_digest` per item.
    ``use_kernel=False`` keeps everything on zlib (e.g. when JAX is
    unavailable in a worker process).
    """
    datas = list(datas)
    header_values = list(header_values)
    if len(datas) != len(header_values):
        raise ValueError("datas and header_values must have equal length")
    results: list[bool] = [False] * len(datas)
    adler_idx: list[int] = []
    adler_expected: list[int] = []
    for i, (data, header) in enumerate(zip(datas, header_values)):
        algo, _, expected = header.partition(":")
        if use_kernel and algo.strip().lower() == "adler32":
            try:
                adler_expected.append(int(expected.strip(), 16))
                adler_idx.append(i)
                continue
            except ValueError:
                results[i] = False
                continue
        results[i] = verify_digest(data, header)
    if adler_idx:
        from repro.kernels.adler32 import adler32_batch

        got = adler32_batch([datas[i] for i in adler_idx])
        for j, i in enumerate(adler_idx):
            results[i] = int(got[j]) == adler_expected[j]
    return results


def adler32_reference(data: bytes) -> int:
    """Pure-Python Adler-32 (oracle for the Pallas kernel tests)."""
    MOD = 65521
    s1, s2 = 1, 0
    for b in data:
        s1 = (s1 + b) % MOD
        s2 = (s2 + s1) % MOD
    return (s2 << 16) | s1
