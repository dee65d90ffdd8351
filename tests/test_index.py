"""Index subsystem tests (repro.index): CDX round-trip, merge determinism,
random access vs sequential equivalence, signature pre-filter soundness,
indexed query == naive full scan, and the serving front end.

Tier-2 selection: ``pytest -m index`` (marker registered in pytest.ini);
the whole module also runs under the tier-1 suite.
"""
import os
import re
import zlib

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.core.warc import FastWARCIterator, WarcRecordType, read_record_at
from repro.core.warc.writer import serialize_record
from repro.data.synth import CorpusSpec, write_corpus
from repro.index import (
    CdxIndex,
    HeaderFilter,
    IndexQueryService,
    QueryEngine,
    QueryRequest,
    RandomAccessReader,
    build_index,
    full_scan_regex,
    full_scan_search,
    verify_index,
)
from repro.index.signature import candidate_mask, pattern_bits, signature_of
from repro.kernels.digest_sig.digest_sig import MAX_NGRAM

pytestmark = pytest.mark.index

_COMPRESSIONS = ["none", "gzip", "lz4", "zstd"]


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """Mixed-codec sharded corpus + its merged index."""
    d = tmp_path_factory.mktemp("index_corpus")
    paths = []
    for i, comp in enumerate(_COMPRESSIONS):
        p = str(d / f"s{i}.warc.{comp}")
        write_corpus(p, CorpusSpec(n_pages=8, seed=50 + i), comp)
        paths.append(p)
    return paths, build_index(paths)


# --------------------------------------------------------------------------
# CDX build / persist / merge
# --------------------------------------------------------------------------

def test_index_counts_and_metadata(corpus):
    paths, idx = corpus
    from repro.data.synth import records_in

    assert len(idx) == len(paths) * records_in(CorpusSpec(n_pages=8))
    assert idx.shard_paths == paths
    # columnar metadata matches a full parse
    row = 0
    for p in paths:
        for record in FastWARCIterator(p, parse_http=True):
            assert int(idx.offset[row]) == record.stream_offset
            assert int(idx.uncomp_len[row]) == record.content_length
            assert int(idx.rtype[row]) == int(record.record_type)
            assert idx.uri(row) == (
                record.header_bytes(b"WARC-Target-URI:") or b"")
            assert int(idx.digest[row]) == (
                zlib.adler32(record.content) & 0xFFFFFFFF)
            http = record.http_headers
            if http is not None and http.status_code is not None:
                assert int(idx.status[row]) == http.status_code
            row += 1
    assert row == len(idx)


def test_comp_len_tiles_the_addressable_stream(corpus):
    paths, idx = corpus
    for sid, p in enumerate(paths):
        rows = np.flatnonzero(idx.shard_id == sid)
        offs = idx.offset[rows].astype(np.int64)
        comps = idx.comp_len[rows].astype(np.int64)
        # records tile the stream: each ends where the next begins
        np.testing.assert_array_equal(offs[:-1] + comps[:-1], offs[1:])
        if idx.shard_kinds[sid] != "zstd":  # compressed-domain offsets
            assert int(offs[-1] + comps[-1]) == os.path.getsize(p)


def test_cdx_save_load_roundtrip(corpus, tmp_path):
    _, idx = corpus
    path = str(tmp_path / "corpus.cdx")
    idx.save(path)
    loaded = CdxIndex.load(path)
    assert loaded.shard_paths == idx.shard_paths
    assert loaded.shard_kinds == idx.shard_kinds
    assert (loaded.sig_bits, loaded.sig_ngram, loaded.sig_hashes) == (
        idx.sig_bits, idx.sig_ngram, idx.sig_hashes)
    for name in ("shard_id", "offset", "comp_len", "uncomp_len", "rtype",
                 "status", "digest", "signatures", "uri_off", "mime_off"):
        np.testing.assert_array_equal(getattr(loaded, name),
                                      getattr(idx, name))
    assert loaded.uri_heap == idx.uri_heap
    assert loaded.mime_heap == idx.mime_heap
    for i in (0, len(idx) // 2, len(idx) - 1):
        assert loaded.entry(i) == idx.entry(i)


def test_merge_deterministic_and_parallel_equal(corpus, tmp_path):
    paths, idx = corpus
    again = build_index(paths)
    a, b = str(tmp_path / "a.cdx"), str(tmp_path / "b.cdx")
    idx.save(a)
    again.save(b)
    with open(a, "rb") as fa, open(b, "rb") as fb:
        assert fa.read() == fb.read()  # bit-identical rebuild
    parallel = build_index(paths, workers=2)
    np.testing.assert_array_equal(parallel.offset, idx.offset)
    np.testing.assert_array_equal(parallel.signatures, idx.signatures)
    assert parallel.uri_heap == idx.uri_heap
    assert parallel.shard_paths == idx.shard_paths


def test_load_rejects_garbage(tmp_path):
    p = str(tmp_path / "bad.cdx")
    with open(p, "wb") as f:
        f.write(b"NOTANIDX" + b"\x00" * 64)
    with pytest.raises(ValueError, match="bad magic"):
        CdxIndex.load(p)


# --------------------------------------------------------------------------
# Random access
# --------------------------------------------------------------------------

@pytest.mark.parametrize("compression", _COMPRESSIONS)
def test_random_access_equals_sequential(tmp_path, compression):
    p = str(tmp_path / f"x.warc.{compression}")
    write_corpus(p, CorpusSpec(n_pages=6, seed=7), compression)
    idx = build_index([p])
    sequential = list(FastWARCIterator(p, parse_http=False))
    assert len(sequential) == len(idx)
    with RandomAccessReader(p, parse_http=False) as reader:
        for i, want in enumerate(sequential):
            got = reader.read(int(idx.offset[i]))
            assert got is not None
            assert got.content == want.content
            assert got.record_type == want.record_type
            assert got.headers.items_bytes() == want.headers.items_bytes()
            assert got.stream_offset == int(idx.offset[i])


def test_index_offsets_absolute_past_compact_rebase(tmp_path):
    """PR 1's `stream_offset` fix, guarded at the CDX consumer.

    An uncompressed shard large enough to cross the parser's 8 MiB
    buffer-compaction threshold must index *absolute* offsets: every
    entry re-read through `RandomAccessReader` (one seek + one parse)
    must reproduce the sequentially-iterated record, digest included.
    """
    payload = b"HTTP/1.1 200 OK\r\n\r\n" + b"x" * (1536 * 1024)
    blob = bytearray()
    for i in range(8):  # ~12 MiB, crosses the threshold mid-file
        blob += serialize_record("response", payload,
                                 {"Content-Type": "application/http",
                                  "WARC-Target-URI": f"https://t/{i}"})
    assert len(blob) > 10 * 1024 * 1024
    p = str(tmp_path / "big.warc")
    with open(p, "wb") as f:
        f.write(blob)
    idx = build_index([p])
    assert len(idx) == 8
    with RandomAccessReader(p) as reader:
        for i in range(len(idx)):
            rec = reader.read(int(idx.offset[i]))
            assert rec.target_uri == f"https://t/{i}"
            assert (zlib.adler32(rec.content) & 0xFFFFFFFF) == int(
                idx.digest[i])
    assert all(verify_index(idx, use_kernel=False))


def test_read_record_at_rebases_offset(tmp_path):
    p = str(tmp_path / "two.warc")
    first = serialize_record("resource", b"one")
    with open(p, "wb") as f:
        f.write(first + serialize_record("resource", b"two"))
    with open(p, "rb") as f:
        rec = read_record_at(f, len(first))
        assert rec.content == b"two"
        assert rec.stream_offset == len(first)


# --------------------------------------------------------------------------
# Signature pre-filter
# --------------------------------------------------------------------------

def test_signature_never_excludes_true_match():
    rng = np.random.default_rng(3)
    bufs = [rng.integers(0, 256, rng.integers(10, 400), np.uint8).tobytes()
            for _ in range(64)]
    sigs = np.stack([signature_of(b) for b in bufs])
    for pattern in (b"abcd", bufs[0][5:13], bufs[17][:4], b"longer-pattern"):
        mask = candidate_mask(sigs, pattern)
        for i, buf in enumerate(bufs):
            if pattern in buf:
                assert mask[i], (i, pattern)


def test_signature_short_pattern_inapplicable():
    sigs = np.stack([signature_of(b"some record content here")])
    assert pattern_bits(b"abc") is None  # < n-gram length
    assert candidate_mask(sigs, b"ab").all()


def test_signature_filters_most_nonmatches():
    bufs = [f"record number {i} with plain text".encode() * 4
            for i in range(200)]
    sigs = np.stack([signature_of(b) for b in bufs])
    mask = candidate_mask(sigs, b"\x01\x02\x03\x04\x05\x06\x07\x08")
    assert mask.sum() < len(bufs) // 4  # Bloom FP rate, not a proof


# --------------------------------------------------------------------------
# Query engine
# --------------------------------------------------------------------------

def test_header_filters_match_bruteforce(corpus):
    paths, idx = corpus
    with QueryEngine(idx) as engine:
        sel = engine.select(HeaderFilter(
            record_type=WarcRecordType.response, status=200,
            mime_prefix=b"text/html", url_prefix=b"https://"))
        want = []
        row = 0
        for p in paths:
            for record in FastWARCIterator(p, parse_http=True):
                http = record.http_headers
                if (record.record_type == WarcRecordType.response
                        and http is not None and http.status_code == 200
                        and http.get_bytes(b"Content-Type", b"").startswith(
                            b"text/html")
                        and (record.header_bytes(b"WARC-Target-URI:")
                             or b"").startswith(b"https://")):
                    want.append(row)
                row += 1
        assert sel.tolist() == want
        assert len(want) > 0


@pytest.mark.parametrize("use_kernel", [True, False])
def test_indexed_query_equals_full_scan(corpus, use_kernel):
    paths, idx = corpus
    with QueryEngine(idx, use_kernel=use_kernel, batch_records=16) as engine:
        for pattern in (b"archive", b"nginx", b"absent-from-corpus",
                        b"\r\n\r\n", b"q", b"longer than sixteen bytes!"):
            hits = engine.search(pattern)
            naive = full_scan_search(paths, pattern)
            assert {(h.shard, h.offset): h.n_matches
                    for h in hits} == naive, pattern
        # batched, not per-record: far fewer dispatches than records
        if use_kernel:
            assert 0 < engine.stats["kernel_dispatches"] \
                < engine.stats["records_scanned"]
            assert engine.stats["batches"] < engine.stats["records_scanned"]


def test_prefilter_skips_fetches(corpus):
    _, idx = corpus
    with QueryEngine(idx) as engine:
        engine.search(b"pattern-that-matches-nothing")
        assert engine.stats["records_scanned"] < len(idx)


def test_match_positions_and_excerpt(corpus):
    paths, idx = corpus
    with QueryEngine(idx) as engine:
        hits = engine.search(b"nginx")
        assert hits
        with RandomAccessReader(hits[0].shard, parse_http=False) as reader:
            content = reader.read(hits[0].offset).content
        for pos in hits[0].positions:
            assert content[pos:pos + 5] == b"nginx"
        assert b"nginx" in hits[0].excerpt


_PROPERTY_CORPUS: tuple | None = None


@pytest.fixture(scope="module", autouse=True)
def _property_corpus(tmp_path_factory):
    # module-global rather than a requested fixture: @given-wrapped tests
    # cannot take function arguments when the hypothesis stub is active
    global _PROPERTY_CORPUS
    p = str(tmp_path_factory.mktemp("cdx_prop") / "prop.warc.gz")
    write_corpus(p, CorpusSpec(n_pages=5, seed=99), "gzip")
    _PROPERTY_CORPUS = ([p], build_index([p]))
    yield
    _PROPERTY_CORPUS = None


@settings(max_examples=25, deadline=None)
@given(st.sampled_from(
    [b"archive", b"crawl", b"HTTP/1.1", b"</html>", b"xyzzy-missing",
     b"text/html", b"GET /", b"research.edu"])
    | st.binary(min_size=1, max_size=12))
def test_property_indexed_query_equals_re_search(pattern):
    """Indexed pattern query == naive full-scan search, any pattern."""
    if not any(pattern):
        pattern = b"\x01" + pattern[1:]  # all-zero kernel guard, still random
    paths, idx = _PROPERTY_CORPUS
    with QueryEngine(idx) as engine:
        hits = engine.search(pattern)
        assert {(h.shard, h.offset): h.n_matches
                for h in hits} == full_scan_search(paths, pattern)


def test_malformed_http_status_does_not_kill_build(tmp_path):
    """Hostile status lines index as the no-status sentinel, and an
    out-of-int16-range status filter selects nothing instead of raising."""
    body = (b"HTTP/1.1 99999 Weird\r\nContent-Type: text/html\r\n\r\n"
            b"<html>x</html>")
    p = str(tmp_path / "bad.warc")
    with open(p, "wb") as f:
        f.write(serialize_record(
            "response", body,
            {"Content-Type": "application/http; msgtype=response"}))
    idx = build_index([p])
    assert int(idx.status[0]) == -1
    with QueryEngine(idx) as engine:
        assert engine.select(HeaderFilter(status=99999)).size == 0


# --------------------------------------------------------------------------
# Digest verification + service
# --------------------------------------------------------------------------

def test_verify_index_bulk(corpus):
    _, idx = corpus
    results = verify_index(idx, limit=12)
    assert results == [True] * 12
    # corrupt one digest: exactly that row must fail
    broken = CdxIndex(idx.shard_paths, idx.shard_kinds, {
        "shard_id": idx.shard_id, "offset": idx.offset,
        "comp_len": idx.comp_len, "uncomp_len": idx.uncomp_len,
        "rtype": idx.rtype, "status": idx.status,
        "digest": idx.digest.copy(), "signatures": idx.signatures,
        "uri_off": idx.uri_off, "mime_off": idx.mime_off},
        idx.uri_heap, idx.mime_heap)
    broken.digest[3] ^= np.uint32(0xDEAD)
    results = verify_index(broken, limit=6, use_kernel=False)
    assert results == [True, True, True, False, True, True]
    results = verify_index(broken, limit=6)  # kernel digest path agrees
    assert results == [True, True, True, False, True, True]
    # fused path (signatures too): the corrupted digest still fails, the
    # intact rows' stored signatures round-trip through the fused sweep
    results = verify_index(broken, limit=6, check_signatures=True)
    assert results == [True, True, True, False, True, True]


def test_fused_build_bit_identical_to_two_pass(corpus, tmp_path):
    """ISSUE 4 acceptance: the fused digest+signature build and the
    two-pass host build must produce byte-identical indexes (and hence
    byte-identical query results)."""
    paths, _ = corpus
    fused = build_index(paths, fused=True)
    host = build_index(paths, fused=False)
    a, b = str(tmp_path / "fused.cdx"), str(tmp_path / "host.cdx")
    fused.save(a)
    host.save(b)
    with open(a, "rb") as fa, open(b, "rb") as fb:
        assert fa.read() == fb.read()


def test_fused_build_nondefault_geometry(corpus):
    paths, _ = corpus
    fused = build_index(paths, sig_bits=1024, sig_ngram=3, sig_hashes=3,
                        fused=True)
    host = build_index(paths, sig_bits=1024, sig_ngram=3, sig_hashes=3,
                       fused=False)
    np.testing.assert_array_equal(fused.digest, host.digest)
    np.testing.assert_array_equal(fused.signatures, host.signatures)


def test_fused_worker_build_rejected_on_accelerator(corpus, monkeypatch):
    """One process holds the chip: a fused build must not dispatch the
    digest kernel from pool workers there (it raises before any fork)."""
    import jax

    paths, _ = corpus
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="one process holds"):
        build_index(paths, workers=2, fused=True)


def test_non_power_of_two_bits_fall_back_to_host(corpus):
    paths, _ = corpus
    # 192 = 3·64: a legal CDX geometry the kernel cannot cover — the
    # fused flag must silently take the host path, not crash
    idx = build_index(paths, sig_bits=192, fused=True)
    ref = build_index(paths, sig_bits=192, fused=False)
    np.testing.assert_array_equal(idx.signatures, ref.signatures)


@pytest.mark.parametrize("ngram", [MAX_NGRAM, MAX_NGRAM + 1])
def test_fused_build_ngram_limit(corpus, ngram):
    """n-grams up to MAX_NGRAM take the fused kernel; longer ones take the
    host two-pass. Both builds, and verify_index, agree with the host."""
    paths, _ = corpus

    def swept() -> int:
        return int(obs.registry().snapshot().counters.get(
            "kernel.digest_signature_batch.dispatches", 0))

    before = swept()
    fused = build_index(paths, sig_ngram=ngram, fused=True)
    assert (swept() > before) == (ngram <= MAX_NGRAM)
    host = build_index(paths, sig_ngram=ngram, fused=False)
    np.testing.assert_array_equal(fused.digest, host.digest)
    np.testing.assert_array_equal(fused.signatures, host.signatures)
    assert verify_index(fused, limit=6, check_signatures=True) == [True] * 6


def test_verify_index_checks_signatures(corpus):
    _, idx = corpus
    results = verify_index(idx, limit=8, check_signatures=True)
    assert results == [True] * 8
    broken = CdxIndex(idx.shard_paths, idx.shard_kinds, {
        "shard_id": idx.shard_id, "offset": idx.offset,
        "comp_len": idx.comp_len, "uncomp_len": idx.uncomp_len,
        "rtype": idx.rtype, "status": idx.status,
        "digest": idx.digest, "signatures": idx.signatures.copy(),
        "uri_off": idx.uri_off, "mime_off": idx.mime_off},
        idx.uri_heap, idx.mime_heap)
    broken.signatures[2] ^= np.uint64(1)  # one flipped signature bit
    for use_kernel in (True, False):
        results = verify_index(broken, limit=4, check_signatures=True,
                               use_kernel=use_kernel)
        assert results == [True, True, False, True]
        # digests alone still pass: the signature check caught it
        assert verify_index(broken, limit=4,
                            use_kernel=use_kernel) == [True] * 4


def test_service_ranks_and_truncates(corpus):
    _, idx = corpus
    with IndexQueryService(idx, batch_size=2) as service:
        responses = service.serve([
            QueryRequest(b"archive", top_k=3),
            QueryRequest(b"absent-from-corpus"),
            QueryRequest(b"nginx", filters=HeaderFilter(
                record_type=WarcRecordType.response), top_k=5),
        ])
        assert len(responses) == 3
        first = responses[0]
        assert len(first.hits) == 3 and first.total_matches >= 3
        counts = [h.n_matches for h in first.hits]
        assert counts == sorted(counts, reverse=True)
        assert responses[1].hits == [] and responses[1].total_matches == 0
        assert all(int(idx.rtype[h.index_row])
                   == int(WarcRecordType.response)
                   for h in responses[2].hits)
        assert service.stats["requests"] == 3
        assert service.stats["batches"] == 2  # batch_size=2 → 2 batches
        assert all(r.latency_s > 0 for r in responses)


# --------------------------------------------------------------------------
# Regex queries (literal extraction + kernel pre-scan + host verify)
# --------------------------------------------------------------------------

def test_required_literals_extraction():
    from repro.index import required_literals

    assert required_literals(rb"nginx/1\.1[67]") == [b"nginx/1.1"]
    assert required_literals(rb"(GET|POST) /index") == [b" /index"]
    assert required_literals(rb"https?://[a-z]+\.edu") == [
        b"http", b"://", b".edu"]
    assert required_literals(rb"(abc)+xyz") == [b"abc", b"xyz"]
    # no usable literal → empty (host fallback, still correct)
    assert required_literals(rb"[a-z]{4,}") == []
    # case-insensitive bytes are not required as written — unsound to use
    assert required_literals(rb"(?i)hello") == []
    assert required_literals(rb"hello", re.IGNORECASE) == []
    # scoped inline flags: only the group's bytes become non-required
    assert required_literals(rb"(?i:NGINX)") == []
    assert required_literals(rb"foo(?i:BAR)baz") == [b"foo", b"baz"]


@pytest.mark.parametrize("use_kernel", [True, False])
def test_regex_query_equals_full_scan(corpus, use_kernel):
    paths, idx = corpus
    with QueryEngine(idx, use_kernel=use_kernel, batch_records=16) as engine:
        for rx in (rb"nginx/1\.1[0-9]", rb"[Aa]rchive", rb"</(html|body)>",
                   rb"xyzzy-missing", rb"crawl-[0-9]+",
                   rb"(?i)NGINX", rb"serv(?i:ER: NGINX)/",
                   rb"[a-z]+@[a-z]+",
                   rb"this-literal-is-longer-than-sixteen-bytes.*x?"):
            hits = engine.search_regex(rx)
            naive = full_scan_regex(paths, rx)
            assert {(h.shard, h.offset): h.n_matches
                    for h in hits} == naive, rx


def test_regex_literal_prefilter_skips_fetches(corpus):
    _, idx = corpus
    with QueryEngine(idx) as engine:
        engine.search_regex(rb"absent-needle-[0-9]{4}!")
        # the required literal drove the signature pre-filter: almost
        # nothing was fetched for a miss pattern
        assert engine.stats["records_scanned"] < len(idx)


def test_regex_requires_bytes_pattern(corpus):
    _, idx = corpus
    with QueryEngine(idx) as engine:
        with pytest.raises(TypeError, match="bytes regex"):
            engine.search_regex("str-regex-[0-9]+")


def test_service_serves_regex_requests(corpus):
    paths, idx = corpus
    rx = rb"nginx/1\.1[0-9]"
    with IndexQueryService(idx) as service:
        resp = service.serve([QueryRequest(rx, regex=True, top_k=100)])[0]
    assert {(h.shard, h.offset): h.n_matches
            for h in resp.hits} == full_scan_regex(paths, rx)


# --------------------------------------------------------------------------
# Per-index signature geometry (build parameter, persisted + validated)
# --------------------------------------------------------------------------

def test_signature_width_is_a_build_parameter(corpus, tmp_path):
    paths, _ = corpus
    idx = build_index(paths, sig_bits=512, sig_ngram=3, sig_hashes=1)
    assert (idx.sig_bits, idx.sig_ngram, idx.sig_hashes) == (512, 3, 1)
    assert idx.signatures.shape == (len(idx), 512 // 64)
    p = str(tmp_path / "narrow.cdx")
    idx.save(p)
    loaded = CdxIndex.load(p)
    assert (loaded.sig_bits, loaded.sig_ngram, loaded.sig_hashes) == (
        512, 3, 1)
    # queries adapt to the stored geometry and stay exact
    with QueryEngine(loaded) as engine:
        for pattern in (b"archive", b"absent-from-corpus"):
            hits = engine.search(pattern)
            assert {(h.shard, h.offset): h.n_matches
                    for h in hits} == full_scan_search(paths, pattern)


def test_build_index_rejects_bad_signature_geometry(corpus):
    paths, _ = corpus
    with pytest.raises(ValueError, match="multiple of 64"):
        build_index(paths, sig_bits=100)
    with pytest.raises(ValueError, match=">= 1"):
        build_index(paths, sig_hashes=0)


def test_load_rejects_corrupt_signature_header(corpus, tmp_path):
    _, idx = corpus
    p = str(tmp_path / "c.cdx")
    idx.save(p)
    blob = bytearray(open(p, "rb").read())
    import struct as _struct
    _struct.pack_into("<I", blob, 12, 100)  # sig_bits: not a multiple of 64
    bad = str(tmp_path / "bad_bits.cdx")
    open(bad, "wb").write(bytes(blob))
    with pytest.raises(ValueError, match="signature width"):
        CdxIndex.load(bad)


# --------------------------------------------------------------------------
# zstd frame table (compressed-domain random access)
# --------------------------------------------------------------------------

def _raw_zstd_frame(payload: bytes, checksum: bool = False) -> bytes:
    """Hand-built store-only zstd frame (raw blocks): lets the walker be
    tested without the zstandard module."""
    import struct as _struct
    out = bytearray(b"\x28\xb5\x2f\xfd")
    out.append(0x20 | (0x04 if checksum else 0))  # single-segment, FCS=1B
    out.append(len(payload))
    half = len(payload) // 2
    for part, last in ((payload[:half], 0), (payload[half:], 1)):
        out += _struct.pack("<I", (len(part) << 3) | last)[:3]
        out += part
    if checksum:
        out += b"\x00" * 4
    return bytes(out)


def test_zstd_frame_walker_pure():
    import struct as _struct

    from repro.core.warc.zstd_frames import frame_table, walk_frames

    blob = (_raw_zstd_frame(b"A" * 40)
            + b"\x52\x2a\x4d\x18" + _struct.pack("<I", 5) + b"skip!"
            + _raw_zstd_frame(b"B" * 30, checksum=True))
    frames = walk_frames(blob)
    assert [f.skippable for f in frames] == [False, True, False]
    assert [f.content_size for f in frames] == [40, 0, 30]
    assert sum(f.comp_len for f in frames) == len(blob)
    offs, bases = frame_table(blob)  # data frames only
    assert bases.tolist() == [0, 40]
    assert offs.tolist() == [0, frames[2].comp_off]


def test_zstd_frame_walker_rejects_garbage():
    from repro.core.warc.zstd_frames import walk_frames

    with pytest.raises(ValueError, match="magic"):
        walk_frames(b"NOTZSTD!")
    with pytest.raises(ValueError, match="truncated"):
        walk_frames(_raw_zstd_frame(b"A" * 40)[:-3])


def test_zstd_frame_hints_seek_without_full_decompress(tmp_path):
    """v2 CDX stores the containing frame per zstd record; a hinted read
    must parse the record without inflating the whole shard."""
    p = str(tmp_path / "z.warc.zstd")
    write_corpus(p, CorpusSpec(n_pages=6, seed=9), "zstd")
    idx = build_index([p])
    from repro.index.cdx import NO_FRAME
    assert not np.any(idx.frame_off == NO_FRAME)
    sequential = list(FastWARCIterator(p, parse_http=False))
    with RandomAccessReader(p, parse_http=False) as reader:
        for i, want in enumerate(sequential):
            got = reader.read(int(idx.offset[i]), frame=idx.frame_hint(i))
            assert got is not None and got.content == want.content
            assert got.stream_offset == int(idx.offset[i])
            # the whole-shard decompress fallback never ran
            assert reader._zbuf is None


def test_zstd_v1_index_compat_falls_back(tmp_path):
    """A CDX saved before the frame columns existed (v1) must load and
    serve zstd shards through the legacy full-decompress path."""
    import struct as _struct

    p = str(tmp_path / "z.warc.zstd")
    write_corpus(p, CorpusSpec(n_pages=4, seed=10), "zstd")
    idx = build_index([p])
    v2 = str(tmp_path / "v2.cdx")
    idx.save(v2)
    blob = bytearray(open(v2, "rb").read())
    _struct.pack_into("<I", blob, 8, 1)  # version = 1
    # splice out the two 8-byte-per-row frame columns
    pos = 8 + _struct.calcsize("<IIIIIQ")
    for _ in range(len(idx.shard_paths)):
        (plen,) = _struct.unpack_from("<I", blob, pos)
        pos += _struct.calcsize("<IB") + plen
    n = len(idx)
    fixed = (4 + 8 + 8 + 8 + 2 + 2 + 4 + 8 * (idx.sig_bits // 64)) * n
    frame_start = pos + fixed
    del blob[frame_start:frame_start + 16 * n]
    v1 = str(tmp_path / "v1.cdx")
    open(v1, "wb").write(bytes(blob))
    legacy = CdxIndex.load(v1)
    assert all(legacy.frame_hint(i) is None for i in range(len(legacy)))
    with RandomAccessReader(p, parse_http=False) as reader:
        rec = reader.read(int(legacy.offset[1]), frame=legacy.frame_hint(1))
        assert rec is not None
        assert reader._zbuf is not None  # fallback decompressed the shard
