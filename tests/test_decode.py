"""ISSUE 5 decompression hot path: decode-into-arena members, pipelined
readahead decoder, LZ4 decode-into variants.

Covers the tentpole contracts — arena-decoded gzip/LZ4/zstd iteration is
byte-identical to the legacy member-``bytes`` path and to the WARCIO
baseline; the ``CopyStats`` member ledger splits legacy materialization
(``member_bytes_copied``) from arena decode (``decode_into_arena``) —
and the satellite ones: decoder-thread lifecycle (``close()`` joins, no
fd/thread leaks, loader teardown), error paths (truncated gzip members,
corrupt LZ4 frames) raising through the pipeline instead of hanging the
decoder thread.
"""
import io
import os
import threading
import time
import zlib

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.warc import (
    FastWARCIterator,
    WARCIOArchiveIterator,
    WarcRecordType,
    lz4,
)
from repro.core.warc.streams import (
    CopyStats,
    GZipStream,
    LZ4Stream,
    MemberArena,
    ReadaheadDecoder,
)
from repro.data.synth import CorpusSpec, generate_warc, records_in

_MEMBER_CODECS = ["gzip", "lz4"]


def _readahead_threads() -> list[threading.Thread]:
    return [t for t in threading.enumerate()
            if t.name.startswith("warc-readahead")]


def _readahead_stages() -> list:
    import multiprocessing as mp

    return _readahead_threads() + [p for p in mp.active_children()
                                   if p.name.startswith("warc-readahead")]


def _assert_no_decoder_threads(deadline_s: float = 2.0) -> None:
    deadline = time.monotonic() + deadline_s
    while _readahead_stages() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not _readahead_stages(), "readahead decoder stage leaked"


def _snapshot(records) -> list[tuple]:
    # bytes() immediately: arena views must be read before slot recycling
    return [(r.record_id, r.record_type, r.stream_offset,
             bytes(r.content_view())) for r in records]


# --------------------------------------------------------------------------
# identity: arena member decode == legacy member bytes == WARCIO baseline
# --------------------------------------------------------------------------

@pytest.mark.parametrize("compression",
                         ["none", "gzip", "lz4", "zstd"])
@pytest.mark.parametrize("readahead", [False, True, None])
def test_arena_decode_matches_legacy_and_baseline(compression, readahead):
    spec = CorpusSpec(n_pages=30, seed=13)
    data = generate_warc(spec, compression)
    legacy = _snapshot(FastWARCIterator(data, parse_http=True,
                                        zero_copy=False))
    arena = _snapshot(FastWARCIterator(data, parse_http=True,
                                       readahead=readahead))
    assert arena == legacy
    if compression in ("none", "gzip"):  # the baseline reads only these
        baseline = [(r.record_id, r.content)
                    for r in WARCIOArchiveIterator(data)]
        assert [(i, c) for i, _, _, c in arena] == baseline
    _assert_no_decoder_threads()


@pytest.mark.parametrize("compression", _MEMBER_CODECS)
@pytest.mark.parametrize("readahead", [False, True, None])
def test_filtered_arena_decode_matches_legacy(compression, readahead):
    spec = CorpusSpec(n_pages=25, seed=5)
    data = generate_warc(spec, compression)
    kw = dict(parse_http=False, record_types=WarcRecordType.response)
    legacy_it = FastWARCIterator(data, zero_copy=False, **kw)
    legacy = _snapshot(legacy_it)
    arena_it = FastWARCIterator(data, readahead=readahead, **kw)
    arena = _snapshot(arena_it)
    assert arena == legacy and len(arena) == 25
    assert arena_it.records_skipped == legacy_it.records_skipped \
        == records_in(spec) - 25
    _assert_no_decoder_threads()


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 12), st.integers(0, 2 ** 16),
       st.sampled_from(_MEMBER_CODECS))
def test_property_arena_decode_identity(n_pages, seed, compression):
    """Any synthetic corpus decodes identically through the member-arena
    readahead path and the legacy member-``bytes`` path."""
    data = generate_warc(CorpusSpec(n_pages=n_pages, seed=seed),
                         compression)
    legacy = _snapshot(FastWARCIterator(data, zero_copy=False))
    arena = _snapshot(FastWARCIterator(data, readahead=True))
    assert arena == legacy


# --------------------------------------------------------------------------
# CopyStats ledger: member decode split, legacy path unchanged
# --------------------------------------------------------------------------

def test_member_ledger_collapses_on_arena_path():
    spec = CorpusSpec(n_pages=40, seed=9)
    data = generate_warc(spec, "gzip")
    legacy = FastWARCIterator(data, parse_http=True, zero_copy=False)
    n = sum(1 for _ in legacy)
    arena = FastWARCIterator(data, parse_http=True)
    assert sum(1 for _ in arena) == n
    # legacy: every member materialized as bytes, tallied separately
    assert legacy.copy_stats.member_bytes_copied > 1000 * 40
    assert legacy.copy_stats.decode_into_arena == 0
    # arena: zero member bytes; the same volume went straight into slots
    assert arena.copy_stats.member_bytes_copied == 0
    assert arena.copy_stats.decode_into_arena \
        == legacy.copy_stats.member_bytes_copied
    # both paths still copy exactly the (small) header blocks
    assert arena.copy_stats.bytes_copied == legacy.copy_stats.bytes_copied
    assert arena.copy_stats.bytes_copied / n < 1024


def test_gzip_copy_budget_within_2x_of_uncompressed():
    """Acceptance: gzip-path bytes-copied/record ~ uncompressed path
    (vs ~full-member-size on the legacy ledger)."""
    spec = CorpusSpec(n_pages=40, seed=9)
    plain = FastWARCIterator(generate_warc(spec, "none"), parse_http=True)
    n = sum(1 for _ in plain)
    gz = FastWARCIterator(generate_warc(spec, "gzip"), parse_http=True)
    assert sum(1 for _ in gz) == n

    def copied_per_record(stats: CopyStats) -> float:
        return (stats.bytes_copied + stats.member_bytes_copied) / n

    assert copied_per_record(gz.copy_stats) \
        <= 2 * copied_per_record(plain.copy_stats)


def test_legacy_ledger_untouched_by_new_counters():
    """zero_copy=False keeps its PR 4 accounting: the new member counters
    stay zero off the member paths and never leak into bytes_copied."""
    data = generate_warc(CorpusSpec(n_pages=10, seed=2), "none")
    it = FastWARCIterator(data, parse_http=True, zero_copy=False)
    list(it)
    assert it.copy_stats.member_bytes_copied == 0
    assert it.copy_stats.decode_into_arena == 0
    assert it.copy_stats.bytes_copied > 0  # the legacy join/header copies


# --------------------------------------------------------------------------
# decoder-thread lifecycle: close() joins, no fd/thread leaks
# --------------------------------------------------------------------------

def _decoder_processes():
    import multiprocessing as mp

    return [p for p in mp.active_children()
            if p.name.startswith("warc-readahead")]


def test_close_joins_decoder_process_and_releases_fd(tmp_path):
    """Path/bytes sources get the true-parallel decoder *process*; close()
    mid-iteration must terminate it (and close the fd)."""
    path = tmp_path / "shard.warc.gz"
    path.write_bytes(generate_warc(CorpusSpec(n_pages=50, seed=1), "gzip"))
    # tiny watermark + depth-1 ring: many slot batches ahead of the
    # parser, so the decoder is deterministically still alive (blocked
    # on the ring) when close() lands mid-iteration
    it = FastWARCIterator(str(path), readahead=True, readahead_depth=1,
                          arena_bytes=2048)
    gen = iter(it)
    first = next(gen)
    assert first.record_id is not None
    assert _decoder_processes(), "decoder process should be running"
    it.close()  # mid-iteration: must reap the child and close the fd
    deadline = time.monotonic() + 6.0
    while _decoder_processes() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not _decoder_processes(), "decoder process leaked"
    assert it.closed


def test_decoder_child_drops_inherited_signal_handlers(tmp_path):
    """The decoder child is forked from a parent that may hold a TPU,
    whose runtime hooks SIGTERM; terminating the child must not run the
    parent's handler in it."""
    import signal

    marker = tmp_path / "handler-ran"

    def handler(signum, frame):
        marker.write_text(str(os.getpid()))
        os._exit(0)

    path = tmp_path / "shard.warc.gz"
    path.write_bytes(generate_warc(CorpusSpec(n_pages=50, seed=1), "gzip"))
    previous = signal.signal(signal.SIGTERM, handler)
    try:
        it = FastWARCIterator(str(path), readahead=True, readahead_depth=1,
                              arena_bytes=2048)
        gen = iter(it)
        assert next(gen).record_id is not None
        assert _decoder_processes(), "decoder process should be running"
        it.close()  # mid-iteration: terminates the child
    finally:
        signal.signal(signal.SIGTERM, previous)
    assert not _decoder_processes()
    assert not marker.exists(), "the parent's SIGTERM handler ran"


def test_close_joins_decoder_thread_for_fileobj_sources(tmp_path):
    """File-object sources cannot be re-opened by a child process, so
    they use the decoder thread — close() must join it."""
    path = tmp_path / "shard.warc.gz"
    path.write_bytes(generate_warc(CorpusSpec(n_pages=50, seed=1), "gzip"))
    with open(path, "rb") as f:
        it = FastWARCIterator(f, readahead=True, readahead_depth=1,
                              arena_bytes=2048)
        gen = iter(it)
        assert next(gen).record_id is not None
        assert _readahead_threads(), "decoder thread should be running"
        it.close()  # mid-iteration: must join the thread
        _assert_no_decoder_threads(deadline_s=6.0)


def test_exhausted_iteration_leaves_no_thread():
    data = generate_warc(CorpusSpec(n_pages=10, seed=4), "gzip")
    it = FastWARCIterator(data, readahead=True)
    assert len(list(it)) == records_in(CorpusSpec(n_pages=10, seed=4))
    _assert_no_decoder_threads()


def test_loader_close_joins_decoder_threads(tmp_path):
    """Regression modeled on the PR 1 prefetch-join fix: closing the token
    loader mid-epoch must tear down the per-shard readahead decoder too
    (prefetch thread → iter_documents teardown → FastWARCIterator.close)."""
    from repro.data.loader import WarcTokenLoader

    paths = []
    for i in range(2):
        p = tmp_path / f"s{i}.warc.gz"
        p.write_bytes(generate_warc(CorpusSpec(n_pages=25, seed=i), "gzip"))
        paths.append(str(p))
    loader = WarcTokenLoader(paths, batch=2, seq_len=128, prefetch=2,
                             readahead=True)
    batches = iter(loader)
    assert next(batches) is not None
    loader.close()
    _assert_no_decoder_threads(deadline_s=11.0)


# --------------------------------------------------------------------------
# error paths: raise through the pipeline, decoder thread never hangs
# --------------------------------------------------------------------------

@pytest.mark.parametrize("readahead", [False, True])
def test_truncated_gzip_member_raises_and_joins(readahead):
    spec = CorpusSpec(n_pages=20, seed=6)
    data = generate_warc(spec, "gzip")
    truncated = data[:int(len(data) * 0.7)]
    expected = []
    legacy = FastWARCIterator(truncated, zero_copy=False)
    with pytest.raises(zlib.error):
        for r in legacy:
            expected.append(r.record_id)
    got = []
    it = FastWARCIterator(truncated, readahead=readahead)
    with pytest.raises(zlib.error):
        for r in it:
            got.append(r.record_id)
    # same records surface before the error as on the synchronous path
    assert got == expected
    _assert_no_decoder_threads()


@pytest.mark.parametrize("readahead", [False, True])
def test_corrupt_lz4_frame_raises_and_joins(readahead):
    spec = CorpusSpec(n_pages=8, seed=8)
    data = bytearray(generate_warc(spec, "lz4"))
    # corrupt the second frame's first data block (past its 7-byte header)
    second = data.index(b"\x04\x22\x4d\x18", 4)
    data[second + 15] ^= 0xFF
    it = FastWARCIterator(bytes(data), readahead=readahead)
    with pytest.raises(lz4.LZ4Error):
        list(it)
    _assert_no_decoder_threads()


def test_decoder_error_does_not_hang_on_full_ring():
    """A decode error behind a backed-up ring still surfaces: close() from
    the consumer side drains and joins even if get() is never called."""
    members = [zlib.compress(b"x" * 2000, 6) for _ in range(4)]

    def bad_decode(slot: bytearray):
        raise RuntimeError("boom")

    arena = MemberArena(stats=CopyStats())
    dec = ReadaheadDecoder(bad_decode, arena, depth=1)
    with pytest.raises(RuntimeError):
        dec.get()
    dec.close()
    assert not dec.thread.is_alive()
    # and close() without any get() must not deadlock either
    st2 = GZipStream(io.BytesIO(b"".join(
        zlib.compress(m, 6) for m in [b"y" * 100] * 3)))
    dec2 = ReadaheadDecoder(
        lambda slot: (lambda n, o: None if n is None else (n, o))(
            st2.next_member_into(slot), st2.tell_compressed()), arena,
        depth=1)
    time.sleep(0.05)
    dec2.close()
    assert not dec2.thread.is_alive()


# --------------------------------------------------------------------------
# streaming-member API + LZ4 decode-into units
# --------------------------------------------------------------------------

def _gzip_members(members):
    buf = io.BytesIO()
    for m in members:
        co = zlib.compressobj(6, zlib.DEFLATED, 31)
        buf.write(co.compress(m) + co.flush())
    buf.seek(0)
    return buf


def test_next_member_into_packs_slot():
    members = [b"alpha", b"beta " * 5000, b"", b"gamma"]
    for stream in (GZipStream(_gzip_members(members)),
                   LZ4Stream(io.BytesIO(b"".join(
                       lz4.compress_frame(m) for m in members)))):
        stats = CopyStats()
        slot = bytearray()
        spans = []
        while True:
            n = stream.next_member_into(slot, stats)
            if n is None:
                break
            spans.append(n)
        assert spans == [len(m) for m in members]
        assert bytes(slot) == b"".join(members)
        assert stats.decode_into_arena == len(slot)
        assert stats.bytes_copied == 0  # true decode-into, not copy-into


def test_lz4_begin_member_into_skip_rolls_back():
    frames = [lz4.compress_frame(b"AAAA" * 100),
              lz4.compress_frame(b"BBBB" * 100)]
    stream = LZ4Stream(io.BytesIO(b"".join(frames)))
    slot = bytearray()
    first = stream.begin_member_into(slot)
    assert bytes(slot[:first.prefix_len]).startswith(b"AAAA")
    first.skip()
    assert slot == bytearray()  # prefix rolled back off the slot
    assert stream.next_member_into(slot) == 400
    assert bytes(slot) == b"BBBB" * 100
    assert stream.begin_member_into(slot) is None


def test_lz4_frame_into_appends_after_existing_content():
    data = b"the quick brown fox " * 3000
    frame = lz4.compress_frame(data, block_size_code=4,
                               content_checksum=True)
    out = bytearray(b"prior-member")
    n, end = lz4.decompress_frame_into(frame, 0, out)
    assert (n, end) == (len(data), len(frame))
    assert bytes(out) == b"prior-member" + data


def test_lz4_frame_into_checksum_and_truncation_errors():
    data = b"payload " * 500
    frame = bytearray(lz4.compress_frame(data, content_checksum=True))
    frame[-2] ^= 0x55  # flip a checksum byte
    with pytest.raises(lz4.LZ4Error):
        lz4.decompress_frame_into(bytes(frame), 0, bytearray())
    good = lz4.compress_frame(data)
    with pytest.raises(lz4.LZ4Error):
        lz4.decompress_frame_into(good[:len(good) // 2], 0, bytearray())


def test_lz4_block_into_matches_block_api():
    for payload in (b"", b"ab" * 4000, b"A" * 10000,
                    bytes(range(256)) * 37, b"xyz"):
        comp = lz4.compress_block(payload)
        out = bytearray(b"seed")
        assert lz4.decompress_block_into(comp, out) == len(payload)
        assert bytes(out[4:]) == payload == lz4.decompress_block(comp)


def test_lz4_block_into_max_size_guard():
    comp = lz4.compress_block(b"Z" * 4096)
    with pytest.raises(lz4.LZ4Error):
        lz4.decompress_block_into(comp, bytearray(), max_size=100)


def test_member_arena_recycles_only_free_slots():
    arena = MemberArena(stats=CopyStats())
    slot = arena.acquire()
    slot += b"held content"
    view = memoryview(slot)
    arena.release(slot)
    other = arena.acquire()  # pinned by `view`: must be a fresh slot
    assert other is not slot
    assert bytes(view) == b"held content"
    del view
    arena.release(other)
    del slot, other
    recycled = arena.acquire()
    assert recycled == bytearray() and arena.stats.arena_reuses >= 1
