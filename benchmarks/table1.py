"""Reproduction of the paper's Table 1: records/s across parser × codec × workload.

Axes (exactly as in the paper):
  * compression: None, GZip, LZ4 — plus zstd, the beyond-paper fast codec
    (real FastWARC added zstd later; in this offline Python runtime it is
    the C-speed carrier of the paper's "fast codec beats gzip" claim, since
    our from-scratch LZ4 codec runs in pure Python).
  * workload: parse-only / +HTTP / +HTTP+Checksum.
  * parser: WARCIO-faithful baseline vs FastWARC-style optimized
    (baseline supports None and GZip only — itself part of the comparison:
    WARCIO has no LZ4 support, which the paper marks with `*`).

Also measured (paper §skipping): response-only filtered iteration, reported
as *total* records processed per second (yielded + skipped).
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass

from repro.core.warc import FastWARCIterator, WARCIOArchiveIterator, WarcRecordType
from repro.data.synth import CorpusSpec, generate_warc, records_in

_PAGES = int(os.environ.get("REPRO_BENCH_PAGES", "600"))
_REPS = int(os.environ.get("REPRO_BENCH_REPS", "3"))


@dataclass
class Row:
    compression: str
    workload: str
    parser: str
    records_per_s: float
    speedup: float | None  # vs baseline on same (compression, workload)

    def csv(self) -> str:
        sp = f"{self.speedup:.2f}" if self.speedup else ""
        return (f"table1,{self.compression},{self.workload},{self.parser},"
                f"{self.records_per_s:.1f},{sp}")


def _best_of(fn, reps: int = _REPS) -> float:
    best = float("inf")
    count = 0
    for _ in range(reps):
        t0 = time.perf_counter()
        count = fn()
        best = min(best, time.perf_counter() - t0)
    return count / best


def _fast(data, **kw):
    return lambda: sum(1 for _ in FastWARCIterator(data, **kw))


def _base(data, **kw):
    return lambda: sum(1 for _ in WARCIOArchiveIterator(data, **kw))


_WORKLOADS = {
    "parse": dict(parse_http=False),
    "+http": dict(parse_http=True),
    "+http+checksum": dict(parse_http=True, verify_digests=True),
}


def run(pages: int = _PAGES, quiet: bool = False) -> list[Row]:
    spec = CorpusSpec(n_pages=pages, seed=42)
    total = records_in(spec)
    rows: list[Row] = []
    gzip_fast_parse: float | None = None

    for comp in ("none", "gzip", "lz4", "zstd"):
        data = generate_warc(spec, comp)
        for workload, kw in _WORKLOADS.items():
            fast = _best_of(_fast(data, **kw))
            base = None
            if comp in ("none", "gzip"):
                base = _best_of(_base(data, **kw))
                rows.append(Row(comp, workload, "warcio_ref", base, None))
            rows.append(Row(comp, workload, "fastwarc", fast,
                            fast / base if base else None))
            if comp == "gzip" and workload == "parse":
                gzip_fast_parse = fast
        # response-only filtered pass: report TOTAL records processed/s
        it = FastWARCIterator(data, parse_http=False,
                              record_types=WarcRecordType.response)
        n_resp = sum(1 for _ in it)
        assert n_resp == pages and it.records_skipped == total - pages
        filt = _best_of(lambda: sum(
            1 for _ in FastWARCIterator(
                data, parse_http=False,
                record_types=WarcRecordType.response)) and total)
        rows.append(Row(comp, "filter-response", "fastwarc", filt, None))

    # the paper's fast-codec claim: codec speedup over FastWARC+GZip
    if gzip_fast_parse:
        for row in rows:
            if row.compression in ("lz4", "zstd") and row.parser == "fastwarc" \
                    and row.workload == "parse":
                row.speedup = row.records_per_s / gzip_fast_parse

    if not quiet:
        print("table,compression,workload,parser,records_per_s,speedup")
        for row in rows:
            print(row.csv())
    return rows


if __name__ == "__main__":
    run()
