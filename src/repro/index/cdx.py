"""CDX-style record index: build, merge, persist, random-access (DESIGN.md §7).

The paper's record-level compression "allows for constant-time random
access to all kinds of web data" — this module is the subsystem that
exercises the claim. An archive (or a sharded corpus) is swept **once**
with the optimized parser and every record's location and metadata are
captured into a compact binary *columnar* index:

    shard_id · offset · comp_len · uncomp_len · type · status ·
    uri · mime · adler32 digest · n-gram signature bitmap

Columns are numpy arrays (header predicates evaluate as vector compares
over the whole corpus, see :mod:`repro.index.query`); URIs/MIMEs live in
shared byte heaps addressed by offset columns, and the per-record
Bloom-style signature (:mod:`repro.index.signature`) lets pattern
queries skip decompression of records that cannot match.

Building fans out per shard through :func:`repro.core.parallel.map_shards`
(one picklable partial per shard, merged deterministically in shard
order); :class:`RandomAccessReader` then opens a shard at an indexed
offset and parses exactly one record — one seek, one member decode, one
record parse, independent of archive size. ``offset`` is the absolute
position in the *addressable* stream: the compressed file for gzip/LZ4
members, the raw file for uncompressed WARCs, and the decompressed
stream for zstd. zstd rows additionally store the compressed offset of
the frame containing the record (walked without decompression at build
time, :mod:`repro.core.warc.zstd_frames`), so random access seeks to the
containing frame and decompresses only from there instead of inflating
the whole shard on first read.
"""
from __future__ import annotations

import io
import os
import struct
import zlib
from dataclasses import dataclass

import numpy as np

from repro.core.warc.fastwarc import FastWARCIterator, read_record_at
from repro.core.warc.record import (
    RECORD_TYPE_FROM_VALUE,
    UNKNOWN_TYPE_VALUE,
    WarcRecord,
    WarcRecordType,
)
from repro.core.warc.streams import (
    ForwardWindow,
    ZstdStream,
    detect_compression,
)
from .signature import SIG_BITS, SIG_HASHES, SIG_NGRAM, signature_of

__all__ = [
    "CdxEntry",
    "CdxIndex",
    "NO_FRAME",
    "RandomAccessReader",
    "build_index",
    "verify_index",
]

_MAGIC = b"REPROCDX"
_VERSION = 2  # v2 adds the zstd frame columns (frame_off / frame_base)
_KIND_CODES = {"none": 0, "gzip": 1, "lz4": 2, "zstd": 3}
_KIND_NAMES = {v: k for k, v in _KIND_CODES.items()}

# rows without a usable compressed-frame mapping (legacy v1 zstd indexes,
# unwalkable frames) carry this sentinel: readers fall back to the
# decompress-whole-shard path
NO_FRAME = np.uint64(0xFFFFFFFFFFFFFFFF)


@dataclass
class CdxEntry:
    """One materialized index row (columnar storage is the truth)."""

    shard: str
    kind: str
    offset: int
    comp_len: int
    uncomp_len: int
    record_type: WarcRecordType
    status: int            # HTTP status, -1 when not an HTTP record
    uri: bytes
    mime: bytes
    digest: int            # adler32 of the record content block

    @property
    def digest_header(self) -> str:
        """WARC digest-header notation (``verify_digests_bulk`` input)."""
        return f"adler32:{self.digest:08x}"


class CdxIndex:
    """Columnar CDX index over one or many WARC shards."""

    def __init__(self, shard_paths: list[str], shard_kinds: list[str],
                 columns: dict[str, np.ndarray],
                 uri_heap: bytes, mime_heap: bytes,
                 *, sig_bits: int = SIG_BITS, sig_ngram: int = SIG_NGRAM,
                 sig_hashes: int = SIG_HASHES) -> None:
        self.shard_paths = list(shard_paths)
        self.shard_kinds = list(shard_kinds)
        self.shard_id = columns["shard_id"]
        self.offset = columns["offset"]
        self.comp_len = columns["comp_len"]
        self.uncomp_len = columns["uncomp_len"]
        self.rtype = columns["rtype"]
        self.status = columns["status"]
        self.digest = columns["digest"]
        self.signatures = columns["signatures"]
        # compressed-domain offset of the frame holding each record plus
        # that frame's decompressed base (zstd random access); identity
        # for member formats, NO_FRAME when unknown (legacy v1 indexes)
        if "frame_off" in columns:
            self.frame_off = columns["frame_off"]
            self.frame_base = columns["frame_base"]
        else:
            self.frame_off = self.offset.copy()
            self.frame_base = self.offset.copy()
            zstd_rows = np.asarray(
                [k == "zstd" for k in shard_kinds], bool)[self.shard_id]
            self.frame_off[zstd_rows] = NO_FRAME
            self.frame_base[zstd_rows] = NO_FRAME
        self.uri_off = columns["uri_off"]
        self.mime_off = columns["mime_off"]
        self.uri_heap = uri_heap
        self.mime_heap = mime_heap
        self.sig_bits = sig_bits
        self.sig_ngram = sig_ngram
        self.sig_hashes = sig_hashes
        # damage report of a tolerant build: LedgerEntry rows for every
        # byte range the sweep skipped (plus shard_quarantined entries).
        # In-memory only — not persisted by save()/load(); a reloaded
        # index starts with a clean slate.
        self.errors: list = []
        # observability: build_index attaches the merged ObsSnapshot of
        # its sweep (parent + pool + workers). In-memory only, like errors.
        self.obs = None
        self._uris: np.ndarray | None = None
        self._mimes: np.ndarray | None = None

    # -- access ----------------------------------------------------------
    def __len__(self) -> int:
        return int(self.offset.size)

    def uri(self, i: int) -> bytes:
        return self.uri_heap[self.uri_off[i]:self.uri_off[i + 1]]

    def mime(self, i: int) -> bytes:
        return self.mime_heap[self.mime_off[i]:self.mime_off[i + 1]]

    def uris(self) -> np.ndarray:
        """Fixed-width bytes array of URIs (built once; the query
        engine's URL-prefix predicate is a ``np.char`` vector compare)."""
        if self._uris is None:
            self._uris = np.array(
                [self.uri(i) for i in range(len(self))], dtype=np.bytes_)
        return self._uris

    def mimes(self) -> np.ndarray:
        """Fixed-width bytes array of MIME values (vector prefix filters)."""
        if self._mimes is None:
            self._mimes = np.array(
                [self.mime(i) for i in range(len(self))], dtype=np.bytes_)
        return self._mimes

    def frame_hint(self, i: int) -> tuple[int, int] | None:
        """``(frame_off, frame_base)`` for seek-to-frame reads of row ``i``,
        or ``None`` when no usable mapping is stored (legacy indexes)."""
        fo = int(self.frame_off[i])
        if np.uint64(fo) == NO_FRAME:
            return None
        return fo, int(self.frame_base[i])

    def entry(self, i: int) -> CdxEntry:
        i = int(i)
        sid = int(self.shard_id[i])
        return CdxEntry(
            shard=self.shard_paths[sid],
            kind=self.shard_kinds[sid],
            offset=int(self.offset[i]),
            comp_len=int(self.comp_len[i]),
            uncomp_len=int(self.uncomp_len[i]),
            record_type=RECORD_TYPE_FROM_VALUE.get(
                int(self.rtype[i]),
                RECORD_TYPE_FROM_VALUE[UNKNOWN_TYPE_VALUE]),
            status=int(self.status[i]),
            uri=self.uri(i),
            mime=self.mime(i),
            digest=int(self.digest[i]),
        )

    # -- persistence -----------------------------------------------------
    def save(self, path: str) -> int:
        """Write the binary columnar layout; returns bytes written.

        The column region is packed through the shared column codec
        (:mod:`repro.columnar.codec` — the same layer the derived
        columnar shards use); the v2 byte format is unchanged.
        """
        from repro.columnar.codec import pack_arrays

        n = len(self)
        out = io.BytesIO()
        out.write(_MAGIC)
        out.write(struct.pack("<IIIIIQ", _VERSION, self.sig_bits,
                              self.sig_ngram, self.sig_hashes,
                              len(self.shard_paths), n))
        for p, kind in zip(self.shard_paths, self.shard_kinds):
            raw = p.encode("utf-8")
            out.write(struct.pack("<IB", len(raw), _KIND_CODES[kind]))
            out.write(raw)
        pack_arrays(out, (self.shard_id, self.offset, self.comp_len,
                          self.uncomp_len, self.rtype, self.status,
                          self.digest, self.signatures, self.frame_off,
                          self.frame_base, self.uri_off, self.mime_off))
        out.write(struct.pack("<Q", len(self.uri_heap)))
        out.write(self.uri_heap)
        out.write(struct.pack("<Q", len(self.mime_heap)))
        out.write(self.mime_heap)
        blob = out.getvalue()
        with open(path, "wb") as f:
            f.write(blob)
        return len(blob)

    @classmethod
    def load(cls, path: str) -> "CdxIndex":
        with open(path, "rb") as f:
            blob = f.read()
        if blob[:8] != _MAGIC:
            raise ValueError(f"{path}: not a CDX index (bad magic)")
        version, bits, ngram, hashes, n_shards, n = struct.unpack_from(
            "<IIIIIQ", blob, 8)
        if version not in (1, _VERSION):  # v1 readable: frame cols absent
            raise ValueError(f"{path}: unsupported CDX version {version}")
        # signature geometry is a per-index build parameter — validate it
        # before trusting it to slice the column region
        if bits == 0 or bits % 64:
            raise ValueError(
                f"{path}: invalid signature width {bits} (need a positive "
                f"multiple of 64)")
        if ngram == 0 or hashes == 0:
            raise ValueError(
                f"{path}: invalid signature parameters "
                f"(ngram={ngram}, hashes={hashes})")
        from repro.columnar.codec import ArrayCursor

        pos = 8 + struct.calcsize("<IIIIIQ")
        shard_paths, shard_kinds = [], []
        for _ in range(n_shards):
            plen, kcode = struct.unpack_from("<IB", blob, pos)
            pos += struct.calcsize("<IB")
            shard_paths.append(blob[pos:pos + plen].decode("utf-8"))
            shard_kinds.append(_KIND_NAMES[kcode])
            pos += plen

        # the column region decodes through the shared column codec —
        # zero-copy views advancing one cursor, schema fixed by version
        cur = ArrayCursor(blob, pos)
        words = bits // 64
        columns = {
            "shard_id": cur.take(np.uint32, n),
            "offset": cur.take(np.uint64, n),
            "comp_len": cur.take(np.uint64, n),
            "uncomp_len": cur.take(np.uint64, n),
            "rtype": cur.take(np.uint16, n),
            "status": cur.take(np.int16, n),
            "digest": cur.take(np.uint32, n),
            "signatures": cur.take(np.uint64, n * words, (n, words)),
        }
        if version >= 2:
            columns["frame_off"] = cur.take(np.uint64, n)
            columns["frame_base"] = cur.take(np.uint64, n)
        # v1: constructor synthesizes identity/NO_FRAME frame columns
        columns["uri_off"] = cur.take(np.uint64, n + 1)
        columns["mime_off"] = cur.take(np.uint64, n + 1)
        pos = cur.pos
        (uri_len,) = struct.unpack_from("<Q", blob, pos)
        pos += 8
        uri_heap = blob[pos:pos + uri_len]
        pos += uri_len
        (mime_len,) = struct.unpack_from("<Q", blob, pos)
        pos += 8
        mime_heap = blob[pos:pos + mime_len]
        return cls(shard_paths, shard_kinds, columns, uri_heap, mime_heap,
                   sig_bits=bits, sig_ngram=ngram, sig_hashes=hashes)

    # -- merge -----------------------------------------------------------
    @classmethod
    def merge(cls, partials: list["CdxIndex"]) -> "CdxIndex":
        """Concatenate per-shard partial indexes (deterministic: input
        order is preserved; shard ids and heap offsets are rebased)."""
        if not partials:
            raise ValueError("nothing to merge")
        ref = partials[0]
        for p in partials[1:]:
            if (p.sig_bits, p.sig_ngram, p.sig_hashes) != (
                    ref.sig_bits, ref.sig_ngram, ref.sig_hashes):
                raise ValueError("signature parameter mismatch across partials")
        shard_paths: list[str] = []
        shard_kinds: list[str] = []
        cols: dict[str, list[np.ndarray]] = {k: [] for k in (
            "shard_id", "offset", "comp_len", "uncomp_len", "rtype",
            "status", "digest", "signatures", "frame_off", "frame_base")}
        uri_offs, mime_offs = [np.zeros(1, np.uint64)], [np.zeros(1, np.uint64)]
        uri_parts, mime_parts = [], []
        uri_base = mime_base = 0
        for p in partials:
            shard_base = len(shard_paths)
            shard_paths.extend(p.shard_paths)
            shard_kinds.extend(p.shard_kinds)
            cols["shard_id"].append(p.shard_id + np.uint32(shard_base))
            for name in ("offset", "comp_len", "uncomp_len", "rtype",
                         "status", "digest", "signatures", "frame_off",
                         "frame_base"):
                cols[name].append(getattr(p, name))
            uri_offs.append(p.uri_off[1:] + np.uint64(uri_base))
            mime_offs.append(p.mime_off[1:] + np.uint64(mime_base))
            uri_parts.append(p.uri_heap)
            mime_parts.append(p.mime_heap)
            uri_base += len(p.uri_heap)
            mime_base += len(p.mime_heap)
        merged = {name: np.concatenate(parts) for name, parts in cols.items()}
        merged["uri_off"] = np.concatenate(uri_offs)
        merged["mime_off"] = np.concatenate(mime_offs)
        out = cls(shard_paths, shard_kinds, merged,
                  b"".join(uri_parts), b"".join(mime_parts),
                  sig_bits=ref.sig_bits, sig_ngram=ref.sig_ngram,
                  sig_hashes=ref.sig_hashes)
        for p in partials:
            out.errors.extend(getattr(p, "errors", ()))
        return out


# --------------------------------------------------------------------------
# Builder (module-level worker: picklable under spawn, like core.parallel)
# --------------------------------------------------------------------------

def _record_span(record: WarcRecord) -> int:
    """Serialized record length in the decompressed stream (zstd tail)."""
    hdr = record._header_block  # raw block kept by the lazy-header parser
    hdr_len = len(hdr) if hdr else sum(
        len(n) + len(v) + 4 for n, v in record.headers.items_bytes()) + len(
            record.headers.status_line) + 2
    return hdr_len + 4 + record.content_length + 4


_FUSED_BATCH = 512           # records per fused-kernel flush
_FUSED_BATCH_BYTES = 32 << 20  # …or payload bytes, whichever trips first:
                               # pending borrowed views pin their arenas and
                               # the kernel pads a matching batch matrix, so
                               # MB-scale records must flush early


def _fused_supported(sig_bits: int, sig_ngram: int) -> bool:
    """Geometry the fused kernel path covers (else: host two-pass)."""
    from repro.kernels.digest_sig.digest_sig import MAX_NGRAM

    return (sig_bits & (sig_bits - 1) == 0
            and 2 <= sig_ngram <= MAX_NGRAM)


def _index_shard(path: str, *, sig_bits: int = SIG_BITS,
                 sig_ngram: int = SIG_NGRAM,
                 sig_hashes: int = SIG_HASHES,
                 fused: bool = False,
                 batch_records: int = _FUSED_BATCH,
                 readahead: bool | None = None,
                 tolerant: bool = False) -> CdxIndex:
    """One-pass sweep of one shard into a single-shard partial index.

    ``fused=True`` computes digest + signature through the batched
    :func:`repro.kernels.digest_sig.digest_signature_batch` sweep:
    record payloads are borrowed zero-copy out of the parse arena
    (``content_view()`` — the pending batch pins its arenas, bounded by
    ``batch_records`` records *and* ``_FUSED_BATCH_BYTES`` payload
    bytes) and each payload byte is touched by exactly one
    kernel pass instead of the two host passes (adler, then n-gram).
    Falls back to the host path when the geometry is outside the
    kernel's support (non-power-of-two ``sig_bits``).

    Publishes per-stage wall time to the process obs registry
    (``index.stage.parse_us`` / ``digest_sig_us`` / ``frame_walk_us`` /
    ``assemble_us``) — under ``map_shards`` fan-out the per-worker
    registries merge into the build's snapshot, so serial vs parallel
    builds can be attributed stage-by-stage (EXPERIMENTS.md §Columnar:
    where the negative workers=2 scaling goes).
    """
    import time as _time

    from repro import obs as _obs

    t_sweep0 = _time.perf_counter()
    t_sig = 0.0
    with open(path, "rb") as f:
        kind = detect_compression(f.read(8))
    use_fused = fused and _fused_supported(sig_bits, sig_ngram)
    offsets: list[int] = []
    uncomp: list[int] = []
    rtypes: list[int] = []
    statuses: list[int] = []
    digests: list = []           # ints (host path) / uint32 arrays (fused)
    sigs: list[np.ndarray] = []  # (words,) rows (host) / (B, words) (fused)
    pending: list[np.ndarray] = []  # borrowed payload views awaiting a flush
    pending_bytes = 0
    uri_parts: list[bytes] = []
    mime_parts: list[bytes] = []
    uri_off = [0]
    mime_off = [0]
    last_span = 0

    def flush() -> None:
        nonlocal pending_bytes, t_sig
        from repro.kernels.digest_sig import digest_signature_batch

        t0 = _time.perf_counter()
        d, s = digest_signature_batch(pending, bits=sig_bits, n=sig_ngram,
                                      k=sig_hashes)
        t_sig += _time.perf_counter() - t0
        digests.append(d)
        sigs.append(s)
        pending.clear()  # releases the arena pins
        pending_bytes = 0

    # readahead (default auto): member inflate runs on a decoder thread
    # while this loop builds columns and flushes fused kernel batches —
    # the index build overlaps decompression with signature/digest work.
    # Pending borrowed views pin their member-arena slots exactly like
    # RecordBuffer arenas, so the batched flush stays aliasing-safe.
    it = FastWARCIterator(path, parse_http=True, readahead=readahead,
                          tolerant=tolerant)
    try:
        for record in it:
            content = record.content_view()
            offsets.append(record.stream_offset)
            uncomp.append(record.content_length)
            rtypes.append(int(record.record_type))
            http = record.http_headers
            status = (http.status_code if http is not None
                      and http.status_code is not None else -1)
            # hostile/malformed status lines ("HTTP/1.1 99999 ...") must
            # not kill the shard sweep: anything outside the int16 column
            # is as good as no status
            statuses.append(status if 0 <= status <= 0x7FFF else -1)
            if use_fused:
                pending.append(np.frombuffer(content, np.uint8))
                pending_bytes += record.content_length
                if len(pending) >= batch_records or \
                        pending_bytes >= _FUSED_BATCH_BYTES:
                    flush()
            else:
                t0 = _time.perf_counter()
                digests.append(zlib.adler32(content) & 0xFFFFFFFF)
                sigs.append(signature_of(content, bits=sig_bits,
                                         n=sig_ngram, k=sig_hashes))
                t_sig += _time.perf_counter() - t0
            uri = record.header_bytes(b"WARC-Target-URI:") or b""
            mime = (http.get_bytes(b"Content-Type", b"") if http is not None
                    else record.header_bytes(b"Content-Type:") or b"")
            uri_parts.append(uri)
            mime_parts.append(mime)
            uri_off.append(uri_off[-1] + len(uri))
            mime_off.append(mime_off[-1] + len(mime))
            last_span = _record_span(record)
        if use_fused and pending:
            flush()
    finally:
        it.close()  # a failed sweep must still join the decoder thread
    t_parse = _time.perf_counter() - t_sweep0 - t_sig
    t_frame0 = _time.perf_counter()
    n = len(offsets)
    off = np.asarray(offsets, np.uint64)
    # comp_len = distance to the next record in the addressable stream;
    # the tail record ends at the file size (member formats) or at its
    # own serialized span (zstd: addressable space is the decompressed
    # stream, whose total length the compressed file size says nothing
    # about)
    if n:
        end = (off[-1] + np.uint64(last_span)) if kind == "zstd" \
            else np.uint64(os.path.getsize(path))
        comp = np.diff(np.concatenate([off, [end]])).astype(np.uint64)
    else:
        comp = np.empty(0, np.uint64)
    # frame mapping: member formats address the compressed stream, so a
    # record's "frame" is itself; zstd offsets live in the decompressed
    # stream, so map each record onto the compressed frame containing it
    # (walked without decompression — see core.warc.zstd_frames)
    frame_off, frame_base = off.copy(), off.copy()
    if kind == "zstd" and n:
        import mmap

        from repro.core.warc.zstd_frames import frame_table
        try:
            with open(path, "rb") as f, \
                    mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as mm:
                # the walk touches a few bytes per block header; mmap
                # keeps it O(1) resident even for multi-GB shards
                comp_offs, bases = frame_table(mm)
            which = np.searchsorted(bases, off, side="right") - 1
            frame_off = comp_offs[which]
            frame_base = bases[which]
        except (ValueError, RuntimeError):
            # unwalkable frames: index stays usable, reads fall back to
            # the decompress-whole-shard path
            frame_off = np.full(n, NO_FRAME, np.uint64)
            frame_base = np.full(n, NO_FRAME, np.uint64)
    t_assemble0 = _time.perf_counter()
    if use_fused:
        digest_col = (np.concatenate(digests) if digests
                      else np.empty(0, np.uint32))
        sig_col = (np.concatenate(sigs, axis=0) if sigs
                   else np.empty((0, sig_bits // 64), np.uint64))
    else:
        digest_col = np.asarray(digests, np.uint32)
        sig_col = (np.stack(sigs) if sigs
                   else np.empty((0, sig_bits // 64), np.uint64))
    columns = {
        "shard_id": np.zeros(n, np.uint32),
        "offset": off,
        "comp_len": comp,
        "uncomp_len": np.asarray(uncomp, np.uint64),
        "rtype": np.asarray(rtypes, np.uint16),
        "status": np.asarray(statuses, np.int16),
        "digest": digest_col,
        "signatures": sig_col,
        "frame_off": frame_off,
        "frame_base": frame_base,
        "uri_off": np.asarray(uri_off, np.uint64),
        "mime_off": np.asarray(mime_off, np.uint64),
    }
    out = CdxIndex([path], [kind], columns, b"".join(uri_parts),
                   b"".join(mime_parts), sig_bits=sig_bits,
                   sig_ngram=sig_ngram, sig_hashes=sig_hashes)
    reg = _obs.registry()
    reg.counter_add("index.shards", 1)
    reg.counter_add("index.records", n)
    reg.counter_add("index.stage.parse_us", int(t_parse * 1e6))
    reg.counter_add("index.stage.digest_sig_us", int(t_sig * 1e6))
    reg.counter_add("index.stage.frame_walk_us",
                    int((t_assemble0 - t_frame0) * 1e6))
    reg.counter_add("index.stage.assemble_us",
                    int((_time.perf_counter() - t_assemble0) * 1e6))
    if tolerant:
        # the damage ledger rides the (picklable) partial back to the
        # build_index parent, crossing the worker process boundary
        out.errors = list(it.error_ledger.entries())
    return out


def build_index(paths, *, workers: int = 0, sig_bits: int = SIG_BITS,
                sig_ngram: int = SIG_NGRAM,
                sig_hashes: int = SIG_HASHES,
                fused: bool | None = None,
                readahead: bool | None = None,
                tolerant: bool = False,
                supervise: bool = False) -> CdxIndex:
    """Index a sharded corpus: one parser sweep per shard, merged.

    ``workers > 0`` fans the per-shard sweeps out through
    :func:`repro.core.parallel.map_shards` (each partial is a picklable
    single-shard :class:`CdxIndex`); ``workers=0`` sweeps serially.
    Either way the merge is deterministic in shard order.

    ``fused`` selects the single-sweep digest+signature path (the
    batched :mod:`repro.kernels.digest_sig` kernel) over the two-pass
    host path; the two produce bit-identical columns. Default (None):
    fused for serial builds, host in worker processes — pool workers
    may fork before/without JAX and must not drag a fresh runtime up
    per shard. On an accelerator, ``fused=True`` with ``workers > 0``
    raises ``ValueError``: one process holds the chip, so the kernel
    cannot be dispatched from pool workers (``derive`` keeps it in the
    parent). Geometries the kernel does not cover (non-power-of-two
    ``sig_bits``, n-grams past ``MAX_NGRAM``) silently use the host
    path.

    The signature geometry (``sig_bits``/``sig_ngram``/``sig_hashes``)
    is a **per-index build parameter**: it is persisted in the CDX
    header, validated on load, and every query against the index adapts
    to it — the module constants are only defaults. ``sig_bits`` must be
    a positive multiple of 64.

    ``readahead`` (default auto) runs member decompression on a decoder
    thread inside each sweep — serial builds overlap inflate with column
    assembly and fused kernel flushes; worker builds overlap it with the
    per-process sweep on top of the shard fan-out.

    ``tolerant`` sweeps each shard in recovery mode: damaged records are
    skipped (resynced past) instead of aborting the build, and every
    skipped byte range is reported on the returned index's ``errors``
    list (:class:`~repro.core.warc.errors.LedgerEntry` rows).
    ``supervise`` (with ``workers > 0``) retries worker deaths; a shard
    that keeps killing workers is dropped from the merge and reported as
    one ``shard_quarantined`` ledger entry covering the whole file.

    The returned index carries the build's merged observability
    snapshot on ``index.obs`` (:class:`~repro.obs.ObsSnapshot`): parent
    registry counters (kernel dispatches, pad waste, serial-sweep
    ingest stats) plus, for worker builds, pool transport/supervisor
    counters and every worker's published ``ingest.*`` counters.
    """
    import functools

    from repro.core.parallel import map_shards
    from repro.core.warc.errors import LedgerEntry

    if sig_bits <= 0 or sig_bits % 64:
        raise ValueError(f"sig_bits must be a positive multiple of 64, "
                         f"got {sig_bits}")
    if sig_ngram < 1 or sig_hashes < 1:
        raise ValueError("sig_ngram and sig_hashes must be >= 1")
    if fused is None:
        fused = workers == 0
    if fused and workers > 0:
        import jax

        if jax.default_backend() != "cpu":
            raise ValueError(
                "fused=True with workers > 0 would dispatch the digest "
                "kernel from pool workers, but one process holds the "
                f"{jax.default_backend()} device: build with workers=0, "
                "or fused=False, or derive() a columnar store")
    sweep = functools.partial(_index_shard, sig_bits=sig_bits,
                              sig_ngram=sig_ngram, sig_hashes=sig_hashes,
                              fused=fused, readahead=readahead,
                              tolerant=tolerant)
    paths = [str(p) for p in paths]
    partials, obs_snap = map_shards(sweep, paths, workers=workers,
                                    supervise=supervise, with_obs=True)
    live: list[CdxIndex] = []
    dropped: list[LedgerEntry] = []
    for path, part in zip(paths, partials):
        if part is None:  # quarantined by the pool supervisor
            try:
                size = os.path.getsize(path)
            except OSError:
                size = 0
            dropped.append(LedgerEntry(
                shard=path, offset=0, error_class="shard_quarantined",
                bytes_skipped=size,
                message="shard repeatedly killed indexing workers"))
            continue
        live.append(part)
    merged = CdxIndex.merge(live)
    merged.errors.extend(dropped)
    merged.obs = obs_snap
    return merged


# --------------------------------------------------------------------------
# Random access
# --------------------------------------------------------------------------

class RandomAccessReader:
    """Fetch single records from one shard by CDX offset.

    The shard is opened once; every :meth:`read` is one seek + one member
    decode + one record parse — cost independent of archive size (the
    benchmark harness measures this against sequential scan-to-offset).
    zstd shards have no compressed-domain member boundaries; when the
    caller supplies a ``frame`` hint (the v2 CDX stores one per record,
    see :meth:`CdxIndex.frame_hint`), the reader seeks straight to the
    containing compressed frame and decompresses only from there —
    without a hint it falls back to decompressing the stream once on
    first access (legacy v1 behaviour; reads then become in-memory
    seeks).
    """

    def __init__(self, path: str, *, parse_http: bool = True,
                 verify_digests: bool = False) -> None:
        self.path = path
        self._f = open(path, "rb")
        self.kind = detect_compression(self._f.read(8))
        self._f.seek(0)
        self._parse_http = parse_http
        self._verify = verify_digests
        self._zbuf: bytes | None = None

    def read(self, offset: int,
             frame: tuple[int, int] | None = None) -> WarcRecord | None:
        """Parse exactly the record starting at ``offset``.

        ``frame`` — optional ``(frame_off, frame_base)`` pair for zstd
        shards: the compressed offset of the frame containing the record
        and that frame's decompressed base. Ignored for member formats
        (their offsets already address the compressed stream).
        """
        if self.kind == "zstd":
            if frame is not None and self._zbuf is None:
                frame_off, frame_base = frame
                self._f.seek(int(frame_off))
                window = ForwardWindow(ZstdStream(self._f),
                                       base=int(frame_base))
                return read_record_at(window, int(offset),
                                      parse_http=self._parse_http,
                                      verify_digests=self._verify,
                                      shard=self.path)
            if self._zbuf is None:
                self._f.seek(0)
                self._zbuf = ZstdStream(self._f).read()
            return read_record_at(io.BytesIO(self._zbuf), int(offset),
                                  parse_http=self._parse_http,
                                  verify_digests=self._verify,
                                  shard=self.path)
        return read_record_at(self._f, int(offset),
                              parse_http=self._parse_http,
                              verify_digests=self._verify,
                              shard=self.path)

    def read_entry(self, entry: CdxEntry) -> WarcRecord | None:
        return self.read(entry.offset)

    # -- lifecycle -------------------------------------------------------
    def close(self) -> None:
        self._f.close()
        self._zbuf = None

    def __enter__(self) -> "RandomAccessReader":
        return self

    def __exit__(self, *exc_info) -> None:
        self.close()


def verify_index(index: CdxIndex, *, limit: int | None = None,
                 use_kernel: bool = True,
                 check_signatures: bool = False) -> list[bool]:
    """Bulk-verify indexed adler32 digests against re-read record content.

    Every checked record is fetched through :class:`RandomAccessReader`
    and the whole batch is verified in batched kernel dispatches — one
    per width bucket, never one device call per record. Digest-only
    verification (the default) goes through ``verify_digests_bulk``;
    ``check_signatures=True`` routes the batch through the **fused**
    :func:`repro.kernels.digest_sig.digest_signature_batch` sweep — the
    same single-pass path the fused build uses — and additionally
    requires each re-computed n-gram signature to equal the stored
    signature row (both come out of the one sweep for free; computing
    the signature matrix just to discard it would make the digest-only
    case pay the full n-gram sweep). ``use_kernel=False`` runs
    everything on the host; a geometry the fused kernel does not cover
    keeps digest verification on the batched adler32 kernel and only
    the signature re-check falls back to the host.
    """
    from repro.core.warc.checksum import verify_digests_bulk

    n = len(index) if limit is None else min(limit, len(index))
    datas: list[bytes] = []
    readers: dict[int, RandomAccessReader] = {}
    try:
        for i in range(n):
            sid = int(index.shard_id[i])
            reader = readers.get(sid)
            if reader is None:
                reader = readers[sid] = RandomAccessReader(
                    index.shard_paths[sid], parse_http=False)
            record = reader.read(int(index.offset[i]),
                                 frame=index.frame_hint(i))
            datas.append(record.content if record is not None else b"")
    finally:
        for reader in readers.values():
            reader.close()
    expected = index.digest[:n].astype(np.uint32)
    if use_kernel and check_signatures and \
            _fused_supported(index.sig_bits, index.sig_ngram):
        from repro.kernels.digest_sig import digest_signature_batch

        # chunked exactly like the build's pending/flush loop: one
        # unbounded sweep would pad the whole corpus into int32 hash
        # matrices (~5-10x payload bytes resident) and OOM on big indexes
        ok = np.empty(n, bool)
        start = 0
        while start < n:
            end = start + 1
            nbytes = len(datas[start])
            while end < n and end - start < _FUSED_BATCH and \
                    nbytes < _FUSED_BATCH_BYTES:
                nbytes += len(datas[end])
                end += 1
            digests, sigs = digest_signature_batch(
                datas[start:end], bits=index.sig_bits, n=index.sig_ngram,
                k=index.sig_hashes)
            ok[start:end] = ((digests == expected[start:end])
                             & (sigs == index.signatures[start:end])
                             .all(axis=1))
            start = end
        return [bool(b) for b in ok]
    headers = [f"adler32:{int(d):08x}" for d in expected]
    results = verify_digests_bulk(datas, headers, use_kernel=use_kernel)
    if check_signatures:
        for i, data in enumerate(datas):
            sig = signature_of(data, bits=index.sig_bits,
                               n=index.sig_ngram, k=index.sig_hashes)
            results[i] = results[i] and bool(
                (sig == index.signatures[i]).all())
    return results
