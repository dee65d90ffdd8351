"""Ingest-hot-path benchmarks: the ISSUE 4 zero-copy / fused / shm claims.

Three claims measured, not asserted:

* **zero-copy parse** — records/s *and bytes-copied-per-record* of the
  pooled-arena parser (``FastWARCIterator`` default) vs the PR 1-era
  bytes-slicing loop (``zero_copy=False``), both instrumented through
  the shared :class:`~repro.core.warc.streams.CopyStats` ledger. The
  claim is not just "faster" but "the copies are *gone*": the arena
  path's per-record copy budget is a few hundred header bytes, the
  legacy path re-copies payloads multiple times.
* **fused index build** — ``build_index(fused=True)`` (one
  ``digest_signature_batch`` kernel sweep per payload batch) vs the
  two-pass host build (``zlib.adler32`` pass + n-gram signature pass
  per record). Columns are bit-identical; the fused build touches each
  payload byte once. Measured end-to-end (the production call), in
  interpret mode: the win comes from batching away per-record host
  overhead — the per-byte sweep itself is emulated on CPU here and
  only gets its vector-unit payoff on real TPU hardware.
* **pool transport** — the shared-memory ring mechanism vs the PR 1
  pickle queue mechanism, measured single-process and *paired* (each
  rep runs both back-to-back and the reported speedup is the median of
  per-pair ratios): a chunk of synthetic-corpus-sized documents is
  serialized once and then either pushed through a real ``os.pipe`` in
  64 KiB writes and reassembled (what ``mp.Queue`` does) or memcpy'd
  into a ring slot and decoded from a zero-copy view (what the shm
  transport does). Racing actual worker processes on a 2-core shared
  container is scheduler roulette — ratios swing 0.4×–5× run to run —
  so the deterministic mechanism cost is the instrument;
  tests/test_parallel.py pins multi-process correctness of both paths.
* **decode** (ISSUE 5) — per-codec member decompression: the legacy
  member-``bytes`` path (``zero_copy=False``) vs decode-into-arena
  members, with and without the readahead decoder thread. Reported per
  codec: records/s and bytes-copied/record, where the copy metric is
  ``bytes_copied + member_bytes_copied`` off the :class:`CopyStats`
  ledger — the claim is that gzip/LZ4 copy budgets collapse from
  ~full-member-size to the uncompressed path's header-copy budget, and
  that gzip rec/s gains ≥1.3× from overlapping inflate with parsing.
  Arena-decoded output is verified byte-identical to the legacy path
  in-bench before any rate is reported.
* **obs** (ISSUE 7) — the observability tax. The zero-copy uncompressed
  sweep is raced tracing-off vs tracing-on, interleaved with
  alternating order (the ``_decode_race`` best-of idiom: each mode's
  fastest quiet window is the instrument, because per-pair ratios on a
  shared container swing ±10%), and the bench *asserts* the
  best-of-ratio ≤ 1.02: span instrumentation on the hot loop must cost
  ≤2% even when enabled — the disabled default path is a strict subset
  (one ``trace.enabled()`` test per iterator), so the gate covers it a
  fortiori.
* **robustness** (ISSUE 6) — the tolerant-mode tax and the recovery
  payoff. ``tolerant=True`` on a *clean* gzip archive must ride the
  exact same hot path as strict mode (the resync machinery only runs
  after a failure), so its overhead ratio is measured paired with
  strict sweeps and expected ≤ 1.05. The same archive with ~1% of
  members deterministically corrupted is then swept tolerantly:
  reported records/s plus the ledger's accounting (entries, bytes
  quarantined) against the known damage.

Scale with REPRO_BENCH_PAGES (default 400).
"""
from __future__ import annotations

import os
import pickle
import statistics
import tempfile
import time

from repro.core.pipeline import Document
from repro.core.warc import FastWARCIterator
from repro.data.synth import CorpusSpec, generate_warc, write_corpus

_PAGES = int(os.environ.get("REPRO_BENCH_PAGES", "400"))
_N_SHARDS = 8
_DOC_BYTES = 2048        # synthetic-corpus-sized extracted documents
_CHUNK_DOCS = 128        # documents per transported chunk
_PIPE_CHUNK = 64 * 1024  # Linux pipe buffer: mp.Queue's write granularity

_BLOB = bytes(range(256)) * 64  # 16 KiB template for transport payloads


def _best_s(fn, reps: int = 3) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


# -- parse path ----------------------------------------------------------

def _parse_stats(data: bytes, zero_copy: bool) -> tuple[float, float, int]:
    """(records/s, bytes_copied_per_record, records) for one parse mode."""
    n = 0
    it = None

    def sweep():
        nonlocal n, it
        it = FastWARCIterator(data, parse_http=True, zero_copy=zero_copy)
        n = sum(1 for _ in it)

    best = _best_s(sweep)
    stats = it.copy_stats
    return n / best, stats.bytes_copied / max(n, 1), n


# -- member decode paths (ISSUE 5) ---------------------------------------

def _decode_sweep(data: bytes, reps: int = 3,
                  **kw) -> tuple[float, float, int]:
    """(records/s, copied_bytes/record, records) for one decode mode.

    Timing uses the bare-iteration metric the parse section established;
    byte-identity is checked separately (untimed) by :func:`_snapshot`.
    """
    n = 0
    it = None

    def sweep():
        nonlocal n, it
        it = FastWARCIterator(data, parse_http=True, **kw)
        n = sum(1 for _ in it)

    best = _best_s(sweep, reps=reps)
    stats = it.copy_stats
    copied = stats.bytes_copied + stats.member_bytes_copied
    return n / best, copied / max(n, 1), n


def _decode_race(data: bytes, modes: dict, reps: int = 9) -> dict:
    """Best-of rec/s per mode, sampled round-robin.

    Shared-container CPU availability swings ~1.7× minute to minute;
    interleaving the modes inside each rep gives every mode the same
    chance of a quiet window before the per-mode best is taken (the
    transport bench's paired-measurement rationale).
    """
    times = {name: float("inf") for name in modes}
    counts = {}
    for _ in range(reps):
        for name, kw in modes.items():
            it = FastWARCIterator(data, parse_http=True, **kw)
            t0 = time.perf_counter()
            counts[name] = sum(1 for _ in it)
            times[name] = min(times[name], time.perf_counter() - t0)
    return {name: counts[name] / t for name, t in times.items()}


def _snapshot(data: bytes, **kw) -> list[tuple]:
    # bytes() immediately: arena views are read before slot recycling
    return [(r.record_id, bytes(r.content_view()))
            for r in FastWARCIterator(data, parse_http=True, **kw)]


def _two_proc_scaling() -> float:
    """Aggregate CPU capacity available to two busy processes vs one —
    the hard ceiling on what pipelined (process) readahead can deliver
    on this host. Shared/throttled CI containers sit well below 2.0."""
    import multiprocessing as mp

    def burn(q):
        deadline = time.perf_counter() + 0.4
        x = n = 0
        while time.perf_counter() < deadline:
            for i in range(10000):
                x += i * i
            n += 1
        q.put(n)

    ctx = mp.get_context()
    q = ctx.Queue()
    p = ctx.Process(target=burn, args=(q,))
    p.start()
    p.join()
    single = q.get()
    procs = [ctx.Process(target=burn, args=(q,)) for _ in range(2)]
    for p in procs:
        p.start()
    for p in procs:
        p.join()
    return (q.get() + q.get()) / max(single, 1)


def _decode_rows() -> list[str]:
    rows = [f"ingest,decode,env,two_proc_scaling,"
            f"{_two_proc_scaling():.2f}"]
    spec = CorpusSpec(n_pages=_PAGES, seed=17)
    plain_rps, plain_bpr, _ = _decode_sweep(generate_warc(spec, "none"))
    rows.append(f"ingest,decode,none_arena,records_per_s,{plain_rps:.1f}")
    rows.append(f"ingest,decode,none_arena,bytes_copied_per_record,"
                f"{plain_bpr:.1f}")
    for codec in ("gzip", "lz4", "zstd"):
        # gzip gets a larger corpus: the process-readahead fork/ring
        # setup (~5-8 ms) must amortize the way it does on real
        # (100 MB+) shards, not dominate a 1 MB toy file. LZ4/zstd keep
        # the base scale (the pure-Python LZ4 *compressor* would
        # otherwise dominate bench runtime just generating the input).
        pages = max(5 * _PAGES, 3000) if codec == "gzip" else _PAGES
        data = generate_warc(CorpusSpec(n_pages=pages, seed=17), codec)
        # acceptance gate first: arena decode (± readahead) must be
        # byte-identical to the legacy member path, checked untimed
        legacy_snap = _snapshot(data, zero_copy=False)
        assert _snapshot(data, readahead=False) == legacy_snap, codec
        modes = {"legacy": dict(zero_copy=False),
                 "arena": dict(readahead=False)}
        member_codec = codec != "zstd"  # zstd: no members, no decode stage
        if member_codec:
            assert _snapshot(data, readahead=True) == legacy_snap, codec
            modes["readahead"] = dict(readahead=True)
        rates = _decode_race(data, modes)
        # copy ledgers from one untimed sweep per mode
        _, legacy_bpr, _ = _decode_sweep(data, reps=1, zero_copy=False)
        _, arena_bpr, _ = _decode_sweep(data, reps=1, readahead=False)
        rows.append(f"ingest,decode,{codec}_legacy,records_per_s,"
                    f"{rates['legacy']:.1f}")
        rows.append(f"ingest,decode,{codec}_legacy,bytes_copied_per_record,"
                    f"{legacy_bpr:.1f}")
        rows.append(f"ingest,decode,{codec}_arena,records_per_s,"
                    f"{rates['arena']:.1f}")
        rows.append(f"ingest,decode,{codec}_arena,bytes_copied_per_record,"
                    f"{arena_bpr:.1f}")
        if member_codec:
            rows.append(f"ingest,decode,{codec}_readahead,records_per_s,"
                        f"{rates['readahead']:.1f}")
            rows.append(f"ingest,decode,{codec}_readahead,"
                        f"bytes_copied_per_record,{arena_bpr:.1f}")
            rows.append(f"ingest,decode,{codec}_readahead,speedup_vs_legacy,"
                        f"{rates['readahead'] / rates['legacy']:.2f}")
        rows.append(f"ingest,decode,{codec},verified_identical,1")
        rows.append(f"ingest,decode,{codec}_arena,copy_vs_none_ratio,"
                    f"{arena_bpr / max(plain_bpr, 1e-9):.2f}")
    return rows


# -- observability tax: tracing-off vs tracing-on (ISSUE 7) --------------

def _obs_rows() -> list[str]:
    from repro import obs
    from repro.obs import trace

    data = generate_warc(CorpusSpec(n_pages=_PAGES, seed=29), "none")

    def sweep() -> int:
        return sum(1 for _ in FastWARCIterator(data, parse_http=True))

    prev = trace.enable(False)
    try:
        sweep()
        trace.enable(True)
        n = sweep()  # warm both paths (and the span reservoirs)
        best = {False: float("inf"), True: float("inf")}
        for rep in range(12):  # interleaved best-of: per-pair ratios on
            # this container swing +-10% run to run, far above the tax
            # being measured, so (the _decode_race rationale) each mode
            # takes its fastest quiet window; alternating order kills
            # any cache/GC bias favoring the second sweep of a pair
            order = (False, True) if rep % 2 == 0 else (True, False)
            for on in order:
                trace.enable(on)
                t0 = time.perf_counter()
                sweep()
                best[on] = min(best[on], time.perf_counter() - t0)
    finally:
        trace.enable(prev)
    ratio = best[True] / best[False]
    # the gate trace.py promises: spans on the zero-copy loop cost <=2%
    # even ENABLED; the disabled default is a strict subset of that work
    assert ratio <= 1.02, f"tracing overhead ratio {ratio:.3f} > 1.02"
    fill_spans = obs.snapshot().counter("span.ingest.fill.count")
    return [
        f"ingest,obs,tracing_off,records_per_s,{n / best[False]:.1f}",
        f"ingest,obs,tracing_on,records_per_s,{n / best[True]:.1f}",
        f"ingest,obs,tracing_on,overhead_ratio,{ratio:.3f}",
        f"ingest,obs,tracing_on,fill_spans_recorded,{fill_spans}",
    ]


# -- robustness: tolerant-mode tax + recovery under damage ---------------

def _robustness_rows() -> list[str]:
    from repro.testing.faults import corrupt_warc

    data = generate_warc(CorpusSpec(n_pages=_PAGES, seed=23), "gzip")
    # paired sweeps (the decode race): the clean-archive tolerant tax is
    # a few percent at most, far below this container's minute-to-minute
    # drift — only the interleaved ratio is meaningful
    rates = _decode_race(data, {"strict": {}, "tolerant": dict(tolerant=True)})
    rows = [
        f"ingest,robustness,strict_clean,records_per_s,"
        f"{rates['strict']:.1f}",
        f"ingest,robustness,tolerant_clean,records_per_s,"
        f"{rates['tolerant']:.1f}",
        f"ingest,robustness,tolerant_clean,overhead_ratio,"
        f"{rates['strict'] / rates['tolerant']:.3f}",
    ]
    bad, damage = corrupt_warc(data, fraction=0.01, seed=23)
    it = FastWARCIterator(bad, parse_http=True, tolerant=True)
    t0 = time.perf_counter()
    n = sum(1 for _ in it)
    elapsed = time.perf_counter() - t0
    entries = it.error_ledger.entries()
    rows += [
        f"ingest,robustness,tolerant_corrupted_1pct,records_per_s,"
        f"{n / elapsed:.1f}",
        f"ingest,robustness,tolerant_corrupted_1pct,records_recovered,{n}",
        f"ingest,robustness,tolerant_corrupted_1pct,damaged_members,"
        f"{len(damage)}",
        f"ingest,robustness,tolerant_corrupted_1pct,ledger_entries,"
        f"{len(entries)}",
        f"ingest,robustness,tolerant_corrupted_1pct,bytes_quarantined,"
        f"{sum(e.bytes_skipped for e in entries)}",
    ]
    return rows


# -- transport mechanism bench -------------------------------------------

def _bench_docs() -> list:
    return [Document("https://bench.example/doc",
                     _BLOB[(i * 37) % 4096 + 1:(i * 37) % 4096 + 1
                           + _DOC_BYTES], i)
            for i in range(_CHUNK_DOCS)]


def _pickle_pipe_rate(docs: list, reps: int) -> float:
    """docs/s of the queue mechanism: dumps → pipe syscalls → loads."""
    r, w = os.pipe()
    try:
        t0 = time.perf_counter()
        for _ in range(reps):
            blob = pickle.dumps(docs, protocol=pickle.HIGHEST_PROTOCOL)
            mv = memoryview(blob)
            parts = []
            sent = 0
            while sent < len(blob):
                n = os.write(w, mv[sent:sent + _PIPE_CHUNK])
                sent += n
                parts.append(os.read(r, _PIPE_CHUNK))
            pickle.loads(b"".join(parts))
        return reps * len(docs) / (time.perf_counter() - t0)
    finally:
        os.close(r)
        os.close(w)


def _shm_ring_rate(docs: list, reps: int) -> float:
    """docs/s of the ring mechanism: dumps → slot memcpy → loads(view)."""
    slot = bytearray(4 << 20)
    t0 = time.perf_counter()
    for _ in range(reps):
        blob = pickle.dumps(docs, protocol=pickle.HIGHEST_PROTOCOL)
        slot[:len(blob)] = blob
        pickle.loads(memoryview(slot)[:len(blob)])
    return reps * len(docs) / (time.perf_counter() - t0)


def _transport_rows() -> list[str]:
    docs = _bench_docs()
    _pickle_pipe_rate(docs, 20)
    _shm_ring_rate(docs, 20)  # warm both
    pipe_rates, ring_rates, ratios = [], [], []
    for _ in range(9):  # paired reps: machine drift cancels in the ratio
        p = _pickle_pipe_rate(docs, 40)
        s = _shm_ring_rate(docs, 40)
        pipe_rates.append(p)
        ring_rates.append(s)
        ratios.append(s / p)
    return [
        f"ingest,transport,pickle_pipe,docs_per_s,"
        f"{statistics.median(pipe_rates):.0f}",
        f"ingest,transport,shm_ring,docs_per_s,"
        f"{statistics.median(ring_rates):.0f}",
        f"ingest,transport,shm_ring,speedup,"
        f"{statistics.median(ratios):.2f}",
    ]


def run(quiet: bool = False) -> list[str]:
    rows = [f"ingest,env,host,cpu_count,{os.cpu_count()}"]

    from repro.data.synth import generate_warc

    spec = CorpusSpec(n_pages=_PAGES, seed=11)
    data = generate_warc(spec, "none")

    # 1) zero-copy parse vs legacy bytes-slicing loop
    for label, zero_copy in (("legacy", False), ("zero_copy", True)):
        rps, bpr, n = _parse_stats(data, zero_copy)
        rows.append(f"ingest,parse,{label},records_per_s,{rps:.1f}")
        rows.append(f"ingest,parse,{label},bytes_copied_per_record,{bpr:.1f}")
    legacy_bpr = float(rows[-3].rsplit(",", 1)[1])
    zc_bpr = float(rows[-1].rsplit(",", 1)[1])
    rows.append(f"ingest,parse,zero_copy,copy_reduction,"
                f"{legacy_bpr / max(zc_bpr, 1e-9):.1f}")

    # 2) member decode paths: legacy bytes vs decode-into-arena ± readahead
    rows.extend(_decode_rows())

    # 2b) tolerant-mode tax on clean archives + recovery under damage
    rows.extend(_robustness_rows())

    # 2c) observability tax: paired tracing-off/on race, gated <=1.02
    rows.extend(_obs_rows())

    with tempfile.TemporaryDirectory() as d:
        shard_paths = []
        for i in range(_N_SHARDS):
            p = os.path.join(d, f"s{i}.warc")
            write_corpus(p, CorpusSpec(n_pages=_PAGES // _N_SHARDS, seed=i),
                         "none")
            shard_paths.append(p)

        # 3) pool transport mechanism: pickle+pipe vs shm ring
        rows.extend(_transport_rows())

        # 4) fused vs two-pass index build (bit-identical columns)
        from repro.index import build_index

        index = build_index(shard_paths, fused=True)  # warm compile
        n_rec = len(index)
        t_fused = _best_s(lambda: build_index(shard_paths, fused=True),
                          reps=2)
        t_host = _best_s(lambda: build_index(shard_paths, fused=False),
                         reps=2)
        rows.append(f"ingest,index_build,two_pass,records_per_s,"
                    f"{n_rec / t_host:.1f}")
        rows.append(f"ingest,index_build,fused,records_per_s,"
                    f"{n_rec / t_fused:.1f}")
        rows.append(f"ingest,index_build,fused,speedup,"
                    f"{t_host / t_fused:.2f}")

        # pad-waste gate (ISSUE 10): half-step width/row quantization +
        # sub-block buckets must keep the fused sweep's padding under
        # 50% (the power-of-two ladder wasted 90%); asserted, not just
        # reported, so a bucketing regression fails the bench
        from repro import obs
        from repro.obs.kernels import pad_waste_report

        waste = pad_waste_report(obs.snapshot()).get(
            "digest_signature_batch", {}).get("pad_waste_ratio", 0.0)
        assert waste < 0.5, f"ingest kernel pad-waste {waste:.3f} >= 0.5"
        rows.append(f"ingest,index_build,fused,pad_waste_ratio,{waste:.3f}")

    if not quiet:  # pragma: no cover - CLI convenience
        for row in rows:
            print(row)
    return rows


if __name__ == "__main__":  # pragma: no cover
    run()
