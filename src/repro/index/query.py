"""Indexed archive query engine: header predicates + batched pattern scan.

The filter-first pipeline over a :class:`repro.index.cdx.CdxIndex`
(DESIGN.md §7). A query narrows the corpus in three strictly cheaper-
to-more-expensive stages:

1. **header predicates** — record type / HTTP status / MIME prefix /
   URL prefix evaluate as vector compares over the columnar index; no
   archive byte is touched.
2. **signature pre-filter** — the per-record n-gram bitmap
   (:mod:`repro.index.signature`) eliminates records that *cannot*
   contain the pattern; eliminated records are never decompressed.
3. **batched payload scan** — surviving candidates are fetched through
   per-shard :class:`~repro.index.cdx.RandomAccessReader`\\ s (offsets
   sorted for locality), gathered into ragged batches, and each batch
   goes through **one** :func:`repro.kernels.find_pattern_mask_batch`
   dispatch — the bulk consumer of the batched pattern kernel; the
   power-of-two width bucketing keeps repeated ragged batches on a
   bounded set of compiled shapes.

Stages 1–2 are reified as a :class:`QueryPlan` (``engine.plan`` /
``engine.plan_regex``): the candidate row set plus everything stage 3
needs to scan and verify one record. ``engine.execute`` runs a plan to
hits; the serve-layer gateway (:mod:`repro.serve.archive`) instead
*merges* the plans of concurrent queries and scans their candidates
through shared multi-pattern kernel dispatches — same verification
helpers, byte-identical hits.

**Regex queries** (``search_regex``) compile to this same shape: the
regex's required literal runs (extracted from the parsed pattern) drive
the signature pre-filter and the kernel scan, and surviving candidates
are host-verified with ``re`` — closing the pattern-literal-only gap
(the WarcSearcher workload). A regex with no usable literal degrades to
host ``re`` over the header-filtered candidates, still correct.

**Columnar path** (DESIGN.md §13): with a derived
:class:`repro.columnar.ColumnStore` attached (``attach_store`` /
``from_store``), stage 3 becomes ``execute_columnar`` — candidates are
grouped by the row-group that already holds their payload in the
kernels' packed layout, and each group is **one**
:func:`repro.kernels.find_pattern_mask_rowgroup` dispatch straight over
the mmapped matrix. No per-record seek, decompression, HTTP parse, or
ragged re-bucketing on the query path; payload bytes are materialized
only for candidates whose scan stage actually hit. Hits are
byte-identical to the CDX+seek path (the columnar bench gates on it).

``engine.stats`` records how much work each stage avoided (candidate
counts, records scanned, kernel dispatches) so the benchmarks can report
indexed-query vs full-scan speedups honestly.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import TYPE_CHECKING

import numpy as np

from repro.core.warc.record import WarcRecordType
from .cdx import CdxIndex, RandomAccessReader
from .signature import candidate_mask

if TYPE_CHECKING:  # pragma: no cover - annotation only (no import cycle)
    from repro.columnar.store import ColumnStore

try:  # renamed in 3.11+; both expose the same parse tree
    from re import _parser as _sre_parse  # type: ignore[attr-defined]
except ImportError:  # pragma: no cover - Python < 3.11
    import sre_parse as _sre_parse  # type: ignore[no-redef]

__all__ = ["HeaderFilter", "PatternHit", "QueryEngine", "QueryPlan",
           "full_scan_search", "full_scan_regex", "required_literals"]

_DEFAULT_BATCH_RECORDS = 64
_DEFAULT_BATCH_BYTES = 4 << 20
_DEFAULT_SCAN_BLOCK = 8192  # kernel tile: few-KiB records pad ≤2×, not to
                            # the 64 KiB DEFAULT_BLOCK sized for whole shards
_COLUMNAR_DENSITY = 0.25  # candidate share above which scanning the whole
                          # row-group beats gathering candidates into a
                          # compact matrix (gather copies; whole-group reads
                          # the mapping in place)


@dataclass
class HeaderFilter:
    """Columnar header predicates (all optional, AND-combined).

    ``time_range`` — ``(lo, hi)`` epoch seconds, half-open — evaluates
    against the derived store's WARC-Date timestamp column and therefore
    needs a store-attached engine (the CDX index does not carry
    timestamps).
    """

    record_type: WarcRecordType | None = None
    status: int | None = None
    mime_prefix: bytes | None = None
    url_prefix: bytes | None = None
    time_range: tuple[int, int] | None = None

    def key(self) -> tuple:
        """Hashable identity (dataclass __hash__ is suppressed by eq)."""
        return (None if self.record_type is None else int(self.record_type),
                self.status, self.mime_prefix, self.url_prefix,
                self.time_range)


@dataclass
class PatternHit:
    """One matching record with its in-content match positions."""

    index_row: int
    shard: str
    offset: int
    uri: bytes
    n_matches: int
    positions: np.ndarray = field(repr=False)
    excerpt: bytes = b""


@dataclass
class QueryPlan:
    """Stages 1–2 of one query, reified: what to scan and how to verify.

    ``rows`` is the candidate set in fetch order (shard-grouped,
    offset-sorted). Stage 3 scans each candidate for ``kernel_pattern``
    on the device (``None`` → host-only scan), then
    :meth:`verify` maps a candidate's literal hits to its final match
    positions — full-literal compare for patterns longer than the kernel
    window, ``re`` for regex queries. Plans from *different* queries can
    be scanned through one shared kernel dispatch (the serve gateway
    does), because verification is per-plan.
    """

    pattern: bytes               # the query as submitted (literal / source)
    rows: np.ndarray             # candidate index rows, fetch order
    kernel_pattern: bytes | None  # device-scannable literal prefix
    literal: bytes | None        # full required literal (None: regex w/o one)
    regex: "re.Pattern | None" = None

    def verify(self, buf: bytes,
               literal_positions: np.ndarray) -> tuple[np.ndarray, int]:
        """Final match positions in ``buf`` + first-match byte length.

        ``literal_positions`` are the scan stage's hits for
        ``kernel_pattern`` (or for ``literal`` on the host path). The
        length is what excerpting needs — fixed for literal queries,
        the first match's span for regex.
        """
        if self.regex is not None:
            if self.literal is not None and literal_positions.size == 0:
                return np.empty(0, np.int64), 0
            matches = list(self.regex.finditer(buf))
            if not matches:
                return np.empty(0, np.int64), 0
            first = matches[0]
            return (np.asarray([m.start() for m in matches], np.int64),
                    max(first.end() - first.start(), 1))
        lit = self.literal if self.literal is not None else self.pattern
        positions = literal_positions
        if self.kernel_pattern is not None and len(lit) > len(
                self.kernel_pattern):
            # kernel scanned a prefix; confirm the (few) survivors
            positions = np.asarray(
                [p for p in positions if buf[p:p + len(lit)] == lit],
                np.int64)
        return positions, len(lit)

    @property
    def needs_host_scan(self) -> bool:
        """True when the scan stage itself must run on the host (no
        device-safe literal: all-zero prefix, or a literal-free regex)."""
        return self.kernel_pattern is None

    def host_scan(self, buf: bytes) -> np.ndarray:
        """Host-side scan-stage positions for one candidate payload —
        the ``literal_positions`` input :meth:`verify` expects. A
        literal-free regex has nothing to pre-scan for: a non-empty
        sentinel makes verify() run the regex on every candidate."""
        if self.regex is not None and self.literal is None:
            return np.zeros(1, np.int64)
        return host_positions(
            buf, self.literal if self.literal is not None else self.pattern)


def host_positions(buf: bytes, pattern: bytes) -> np.ndarray:
    """All (overlapping) occurrences of ``pattern`` — host scan path."""
    pos, i = [], buf.find(pattern)
    while i >= 0:
        pos.append(i)
        i = buf.find(pattern, i + 1)
    return np.asarray(pos, np.int64)


def required_literals(pattern: bytes, flags: int = 0) -> list[bytes]:
    """Literal byte runs every match of ``pattern`` must contain.

    Conservative walk of the parsed regex: top-level concatenation
    literals form runs; a group or a repeat with ``min >= 1`` is entered
    (its own requirements hold at least once); branches, classes,
    optional parts contribute nothing. Case-insensitive patterns return
    no literals (the bytes are not required as written). Soundness is
    what matters — every returned literal occurs in every match — since
    literals only *pre-filter*; ``re`` always confirms.
    """
    if flags & re.IGNORECASE:
        return []
    try:
        parsed = _sre_parse.parse(pattern, flags)
    except re.error:
        return []
    # inline flags ((?i)...) surface only after the parse
    if getattr(parsed.state, "flags", 0) & re.IGNORECASE:
        return []
    literals: list[bytes] = []

    def walk(ops) -> None:
        run = bytearray()

        def flush() -> None:
            if run:
                literals.append(bytes(run))
                run.clear()

        for op, args in ops:
            name = str(op)
            if name == "LITERAL" and args <= 0xFF:
                run.append(args)
                continue
            flush()
            if name in ("MAX_REPEAT", "MIN_REPEAT"):
                lo, _hi, sub = args
                if lo >= 1:
                    walk(sub)
            elif name == "SUBPATTERN":
                # scoped inline flags ((?i:...)) make the group's bytes
                # not-required-as-written: contribute nothing from it
                if not args[1] & re.IGNORECASE:
                    walk(args[3])
            elif name == "ATOMIC_GROUP":
                walk(args)
            # BRANCH / IN / ANY / AT / NOT_LITERAL / ...: no requirement
        flush()

    walk(parsed)
    return [lit for lit in literals if lit]


class QueryEngine:
    """Run header + pattern queries against an indexed corpus."""

    def __init__(self, index: CdxIndex, *,
                 store: "ColumnStore | None" = None,
                 batch_records: int = _DEFAULT_BATCH_RECORDS,
                 batch_bytes: int = _DEFAULT_BATCH_BYTES,
                 use_kernel: bool = True,
                 scan_block: int = _DEFAULT_SCAN_BLOCK,
                 excerpt_bytes: int = 80) -> None:
        self.index = index
        self.batch_records = max(1, batch_records)
        self.batch_bytes = max(1, batch_bytes)
        self.scan_block = scan_block
        self.use_kernel = use_kernel
        self.excerpt_bytes = excerpt_bytes
        self._readers: dict[int, RandomAccessReader] = {}
        self._store: "ColumnStore | None" = None
        self.stats = {"queries": 0, "header_candidates": 0,
                      "sig_candidates": 0, "records_scanned": 0,
                      "bytes_scanned": 0, "kernel_dispatches": 0,
                      "batches": 0, "store_fetches": 0}
        if store is not None:
            self.attach_store(store)

    @classmethod
    def from_store(cls, store: "ColumnStore", **kwargs) -> "QueryEngine":
        """An engine running standalone on a derived store — planner
        stages over :meth:`~repro.columnar.ColumnStore.as_index`'s
        columns, scan stage over the store's row-groups. No CDX file
        and no archive readers involved."""
        engine = cls(store.as_index(), **kwargs)
        engine.attach_store(store, validate=False)
        return engine

    def attach_store(self, store: "ColumnStore",
                     validate: bool = True) -> None:
        """Attach a derived columnar store covering this engine's corpus.

        Attached, the engine routes ``execute`` through
        :meth:`execute_columnar` and serves ``_fetch`` from the store's
        row-groups (no seek/decompress) — the serve gateway inherits
        both for free. ``validate`` checks the store rows are 1:1 with
        the index rows (derive and CDX build share row order by
        construction; a store derived from a *different* corpus is
        rejected here rather than silently mis-scanned).
        """
        if validate:
            if len(store) != len(self.index):
                raise ValueError(
                    f"store has {len(store)} rows, index has "
                    f"{len(self.index)} — not the same corpus")
            if list(store.shard_paths) != list(self.index.shard_paths):
                raise ValueError("store and index cover different shards")
            if not np.array_equal(np.asarray(store.offset),
                                  np.asarray(self.index.offset)):
                raise ValueError("store row order does not match the "
                                 "index (offset columns differ)")
        self._store = store

    @property
    def store(self) -> "ColumnStore | None":
        return self._store

    # -- stage 1: header predicates (pure columnar) ----------------------
    def header_mask(self, flt: HeaderFilter | None) -> np.ndarray:
        """Boolean row mask from the metadata columns alone."""
        idx = self.index
        mask = np.ones(len(idx), dtype=bool)
        if flt is None:
            return mask
        if flt.record_type is not None:
            mask &= (idx.rtype.astype(np.int64)
                     & np.int64(int(flt.record_type))) != 0
        if flt.status is not None:
            # int64 compare: a bad user-supplied status (out of int16
            # range) selects nothing instead of raising OverflowError
            mask &= idx.status.astype(np.int64) == int(flt.status)
        if flt.mime_prefix is not None:
            mask &= np.char.startswith(idx.mimes(), bytes(flt.mime_prefix))
        if flt.url_prefix is not None:
            mask &= np.char.startswith(idx.uris(), bytes(flt.url_prefix))
        if flt.time_range is not None:
            if self._store is None:
                raise ValueError(
                    "time_range filters read the derived store's "
                    "timestamp column — attach_store() first (the CDX "
                    "index carries no WARC-Date)")
            lo, hi = flt.time_range
            ts = self._store.timestamp.astype(np.int64)
            mask &= (ts >= int(lo)) & (ts < int(hi))
        return mask

    def select(self, flt: HeaderFilter | None = None) -> np.ndarray:
        """Index rows satisfying the header predicates (sorted)."""
        return np.flatnonzero(self.header_mask(flt))

    # -- stages 1+2: plan construction -----------------------------------
    def _finish_plan(self, mask: np.ndarray, literals: list[bytes],
                     prefilter: bool) -> np.ndarray:
        """Apply the signature pre-filter and fix the fetch order."""
        self.stats["queries"] += 1
        self.stats["header_candidates"] += int(mask.sum())
        if prefilter:
            for lit in literals:
                mask &= candidate_mask(self.index.signatures, lit,
                                       n=self.index.sig_ngram,
                                       k=self.index.sig_hashes)
        rows = np.flatnonzero(mask)
        self.stats["sig_candidates"] += int(rows.size)
        # shard-grouped, offset-sorted fetch order for read locality
        order = np.lexsort((self.index.offset[rows],
                            self.index.shard_id[rows]))
        return rows[order]

    @staticmethod
    def _kernel_literal(literal: bytes) -> bytes | None:
        """Device-scannable prefix of a literal, or None (host scan)."""
        from repro.kernels.pattern_scan.pattern_scan import MAX_PATTERN

        kpat = literal[:MAX_PATTERN]
        # all-zero prefix: the kernel wrapper rejects it (zero padding
        # could false-positive); those rare queries scan on the host
        return kpat if any(kpat) else None

    def plan(self, pattern: bytes, flt: HeaderFilter | None = None, *,
             prefilter: bool = True) -> QueryPlan:
        """Stages 1+2 for a literal pattern query."""
        pattern = bytes(pattern)
        if not pattern:
            raise ValueError("empty pattern")
        rows = self._finish_plan(self.header_mask(flt), [pattern], prefilter)
        return QueryPlan(pattern=pattern, rows=rows,
                         kernel_pattern=self._kernel_literal(pattern),
                         literal=pattern)

    def plan_regex(self, regex: "bytes | re.Pattern",
                   flt: HeaderFilter | None = None, *,
                   prefilter: bool = True) -> QueryPlan:
        """Stages 1+2 for a regex query: required literals drive the
        pre-filter and the kernel scan; ``re`` verifies survivors."""
        compiled = regex if isinstance(regex, re.Pattern) else re.compile(
            regex)
        if not isinstance(compiled.pattern, bytes):
            raise TypeError("content scans need a bytes regex")
        literals = required_literals(compiled.pattern, compiled.flags
                                     & ~re.UNICODE)
        rows = self._finish_plan(self.header_mask(flt), literals, prefilter)
        scan_literal = max(literals, key=len) if literals else None
        return QueryPlan(
            pattern=compiled.pattern, rows=rows,
            kernel_pattern=(self._kernel_literal(scan_literal)
                            if scan_literal else None),
            literal=scan_literal, regex=compiled)

    # -- stage 3: execution ----------------------------------------------
    def search(self, pattern: bytes, flt: HeaderFilter | None = None, *,
               prefilter: bool = True) -> list[PatternHit]:
        """All records whose content block contains ``pattern``.

        Results are in index order. Candidates are fetched shard-by-shard
        in ascending offset order and scanned in ragged batches of at
        most ``batch_records`` records / ``batch_bytes`` bytes — each
        batch is one (bucketed) kernel dispatch, never one per record.
        """
        return self.execute(self.plan(pattern, flt, prefilter=prefilter))

    def search_regex(self, regex: "bytes | re.Pattern",
                     flt: HeaderFilter | None = None, *,
                     prefilter: bool = True) -> list[PatternHit]:
        """All records whose content block matches ``regex`` (bytes).

        ``n_matches``/``positions`` follow ``re.finditer`` semantics
        (non-overlapping matches).
        """
        return self.execute(self.plan_regex(regex, flt, prefilter=prefilter))

    def execute(self, plan: QueryPlan, *,
                columnar: bool | None = None) -> list[PatternHit]:
        """Run a plan's scan stage: fetch, batch, dispatch, verify.

        With a store attached the scan routes through
        :meth:`execute_columnar` (byte-identical hits); pass
        ``columnar=False`` to force the fetch-and-batch path, or
        ``columnar=True`` to require the store (raises if absent).
        """
        if columnar is None:
            columnar = self._store is not None
        if columnar:
            return self.execute_columnar(plan)
        hits: list[PatternHit] = []
        batch_rows: list[int] = []
        batch_bufs: list[bytes] = []
        pending = 0
        for r in plan.rows:
            content = self._fetch(int(r))
            batch_rows.append(int(r))
            batch_bufs.append(content)
            pending += len(content)
            if (len(batch_rows) >= self.batch_records
                    or pending >= self.batch_bytes):
                hits.extend(self._scan_batch(batch_rows, batch_bufs, plan))
                batch_rows, batch_bufs, pending = [], [], 0
        if batch_rows:
            hits.extend(self._scan_batch(batch_rows, batch_bufs, plan))
        hits.sort(key=lambda h: h.index_row)
        return hits

    # -- stage 3, columnar: kernels over mmapped row-groups ---------------
    def execute_columnar(self, plan: QueryPlan) -> list[PatternHit]:
        """Run a plan's scan stage against the attached derived store.

        Candidates are grouped by row-group; each group is one
        row-group kernel dispatch over its packed matrix — **dense**
        groups (candidate share ≥ ``_COLUMNAR_DENSITY`` of the group's
        live rows) scan the mmapped matrix in place, **sparse** groups
        gather just the candidate rows into a compact matrix first.
        Payload bytes are copied out only for candidates whose scan
        stage hit (verification / excerpting); everything else never
        leaves the mapping. Hits are byte-identical to :meth:`execute`.
        """
        store = self._store
        if store is None:
            raise ValueError("no columnar store attached — attach_store() "
                             "or QueryEngine.from_store()")
        hits: list[PatternHit] = []
        if plan.rows.size == 0:
            return hits
        from repro.kernels.bucketing import quantize_count
        from repro.kernels.pattern_scan import find_pattern_mask_rowgroup

        gids = store.rg_id[plan.rows].astype(np.int64)
        order = np.argsort(gids, kind="stable")
        ordered = plan.rows[order]
        bounds = np.flatnonzero(np.diff(gids[order])) + 1
        use_kernel = self.use_kernel and not plan.needs_host_scan
        # short-literal plans need no per-candidate verification: the
        # kernel positions are final and the excerpt window slices
        # straight out of the row-group matrix — no payload copy at all
        lit = plan.literal if plan.literal is not None else plan.pattern
        fast_literal = (plan.regex is None and plan.kernel_pattern is not None
                        and len(lit) <= len(plan.kernel_pattern))
        for chunk in np.split(ordered, bounds):
            g = int(store.rg_id[chunk[0]])
            lengths = store.length[chunk].astype(np.int64)
            self.stats["batches"] += 1
            self.stats["records_scanned"] += int(chunk.size)
            self.stats["bytes_scanned"] += int(lengths.sum())
            if not use_kernel:  # host scan: materialize each candidate
                for r in chunk:
                    buf = store.payload(int(r))
                    positions, first_len = plan.verify(buf,
                                                       plan.host_scan(buf))
                    if positions.size:
                        hits.append(self.make_hit(int(r), buf, positions,
                                                  first_len))
                continue
            live = int(store.rg_rows[g])
            if chunk.size >= _COLUMNAR_DENSITY * live:
                # dense: one dispatch over the whole mmapped matrix
                source, _, all_lens = store.rowgroup(g)
                masks = find_pattern_mask_rowgroup(
                    source, all_lens, plan.kernel_pattern, trim=False)
                mask_rows = store.rg_row[chunk].astype(np.int64)
                mask_lens = all_lens
            else:
                # sparse: gather candidates into a compact matrix
                matrix, _, _ = store.rowgroup(g)
                sel = store.rg_row[chunk].astype(np.int64)
                source = np.zeros(
                    (quantize_count(chunk.size), matrix.shape[1]), np.uint8)
                source[:chunk.size] = matrix[sel]
                masks = find_pattern_mask_rowgroup(
                    source, lengths, plan.kernel_pattern, trim=False)
                mask_rows = np.arange(chunk.size)
                mask_lens = lengths
            self.stats["kernel_dispatches"] += 1
            # one pass over the whole group mask instead of a
            # flatnonzero per candidate; the flat bool scan is ~10x
            # cheaper than a 2-D nonzero, and row-major order means each
            # candidate's positions stay one contiguous hit_cols run
            flat = np.flatnonzero(masks.view(bool))
            hit_rows, hit_cols = np.divmod(flat, masks.shape[1])
            # trim=False left windows past each row's true end in the
            # mask; drop them here on the compact hit list instead of
            # paying a full-matrix where-copy up front
            plen_k = len(plan.kernel_pattern)
            valid = hit_cols < np.maximum(
                mask_lens - plen_k + 1, 0)[hit_rows]
            hit_rows = hit_rows[valid]
            hit_cols = hit_cols[valid]
            starts = np.searchsorted(hit_rows, mask_rows, side="left")
            ends = np.searchsorted(hit_rows, mask_rows, side="right")
            for i in np.flatnonzero(ends > starts):
                r = int(chunk[i])
                lpos = hit_cols[starts[i]:ends[i]].astype(np.int64)
                if fast_literal:  # positions final; excerpt off the row
                    row = source[int(mask_rows[i])][:int(lengths[i])]
                    hits.append(self.make_hit(r, row, lpos, len(lit)))
                    continue
                buf = store.payload(r)
                positions, first_len = plan.verify(buf, lpos)
                if positions.size:
                    hits.append(self.make_hit(r, buf, positions,
                                              first_len))
        hits.sort(key=lambda h: h.index_row)
        return hits

    # -- internals -------------------------------------------------------
    def _fetch(self, row: int) -> bytes:
        if self._store is not None:  # row-group copy-out: no seek/inflate
            self.stats["store_fetches"] += 1
            return self._store.payload(row)
        sid = int(self.index.shard_id[row])
        reader = self._readers.get(sid)
        if reader is None:
            reader = self._readers[sid] = RandomAccessReader(
                self.index.shard_paths[sid], parse_http=False)
        record = reader.read(int(self.index.offset[row]),
                             frame=self.index.frame_hint(row))
        return record.content if record is not None else b""

    def make_hit(self, row: int, buf: bytes, positions: np.ndarray,
                 first_len: int) -> PatternHit:
        """Assemble one hit (shared with the serve gateway)."""
        first = int(positions[0])
        excerpt = bytes(buf[max(0, first - 16):
                            first + first_len + self.excerpt_bytes])
        sid = int(self.index.shard_id[row])
        return PatternHit(
            index_row=row, shard=self.index.shard_paths[sid],
            offset=int(self.index.offset[row]), uri=self.index.uri(row),
            n_matches=int(positions.size), positions=positions,
            excerpt=excerpt)

    def _scan_batch(self, rows: list[int], bufs: list[bytes],
                    plan: QueryPlan) -> list[PatternHit]:
        self.stats["batches"] += 1
        self.stats["records_scanned"] += len(rows)
        self.stats["bytes_scanned"] += sum(len(b) for b in bufs)
        if self.use_kernel and not plan.needs_host_scan:
            from repro.kernels.bucketing import dispatch_count
            from repro.kernels.pattern_scan import find_pattern_mask_batch

            masks = find_pattern_mask_batch(bufs, plan.kernel_pattern,
                                            block=self.scan_block)
            lit_positions = [np.flatnonzero(m) for m in masks]
            self.stats["kernel_dispatches"] += dispatch_count(
                [len(b) for b in bufs], self.scan_block)
        else:  # host fallback: plain bytes.find sweep (or regex verify-all)
            lit_positions = [plan.host_scan(buf) for buf in bufs]
        hits = []
        for row, buf, lpos in zip(rows, bufs, lit_positions):
            positions, first_len = plan.verify(buf, lpos)
            if positions.size:
                hits.append(self.make_hit(row, buf, positions, first_len))
        return hits

    # -- lifecycle -------------------------------------------------------
    def close(self) -> None:
        for reader in self._readers.values():
            reader.close()
        self._readers.clear()

    def __enter__(self) -> "QueryEngine":
        return self

    def __exit__(self, *exc_info) -> None:
        self.close()


def full_scan_search(paths, pattern: bytes) -> dict[tuple[str, int], int]:
    """Naive baseline: decompress + scan **every** record of every shard.

    Returns ``{(shard, offset): n_matches}`` for records containing the
    pattern — the oracle the property tests compare the indexed path
    against, and the benchmark's un-indexed comparison point.
    """
    from repro.core.warc.fastwarc import FastWARCIterator

    pattern = bytes(pattern)
    out: dict[tuple[str, int], int] = {}
    for path in paths:
        for record in FastWARCIterator(str(path), parse_http=False):
            content = record.content
            n, i = 0, content.find(pattern)
            while i >= 0:
                n += 1
                i = content.find(pattern, i + 1)
            if n:
                out[(str(path), record.stream_offset)] = n
    return out


def full_scan_regex(paths, regex: "bytes | re.Pattern"
                    ) -> dict[tuple[str, int], int]:
    """Regex oracle: ``re.finditer`` over every record of every shard."""
    from repro.core.warc.fastwarc import FastWARCIterator

    compiled = regex if isinstance(regex, re.Pattern) else re.compile(regex)
    out: dict[tuple[str, int], int] = {}
    for path in paths:
        for record in FastWARCIterator(str(path), parse_http=False):
            n = sum(1 for _ in compiled.finditer(record.content))
            if n:
                out[(str(path), record.stream_offset)] = n
    return out
