"""Benchmark driver: one section per paper table + framework benches.

Sections (CSV on stdout, ``section,...`` prefixed rows):
  * table1   — the paper's Table 1: records/s per parser × codec ×
               workload, with speedups (benchmarks/table1.py);
  * pipeline — end-to-end WARC→tokens ingestion + the paper's
               Common-Crawl hours-saved projections;
  * kernels  — Pallas kernel micro-benches (interpret mode on the CPU);
  * parallel — multi-worker shard fan-out scaling + batched-vs-looped
               kernel dispatch (benchmarks/parallel_bench.py);
  * index    — CDX build throughput, random-access vs sequential
               scan-to-offset, indexed-query vs full-scan speedup
               (benchmarks/index_bench.py);
  * serve    — archive-gateway vs synchronous query service under
               1/8/64 concurrent clients: throughput, dispatches per
               request, coalesce/cache rates, per-stage trace
               attribution at 8/64 clients + the request-tracing tax
               (paired off/on race, gated ≤1.05 in-bench)
               (benchmarks/serve_bench.py);
  * ingest   — zero-copy parse vs legacy (records/s + bytes copied per
               record), fused vs two-pass index build, shared-memory vs
               pickle pool transport, and the observability tax (paired
               tracing-off/on race, gated ≤1.02 in-bench)
               (benchmarks/ingest_bench.py);
  * columnar — derived-store derivation throughput, row-group pad
               waste (gated <0.5 in-bench), and column-scan vs
               CDX+seek query speedup (byte-identical hits asserted,
               broad scan gated ≥5x in-bench)
               (benchmarks/columnar_bench.py).

``--json`` additionally writes ``BENCH_pipeline.json`` (all non-index
rows as records plus a throughput summary) and — per section that ran —
``BENCH_index.json`` / ``BENCH_serve.json`` / ``BENCH_ingest.json`` /
``BENCH_columnar.json``, so
each perf trajectory is tracked machine-readably across PRs. Every
payload embeds the bench process's merged ``repro.obs`` counter snapshot
under ``"obs"`` (cumulative across the sections that ran — kernel
dispatch / pad-waste / ingest counters ride along with the timings; the
file renders with ``python -m repro.obs.dump``). ``--sections a,b``
restricts the run.

Scale with REPRO_BENCH_PAGES (default 600 for table1 / 400 elsewhere).
"""
from __future__ import annotations

import argparse
import json
import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_JSON_PATH = os.path.join(_REPO_ROOT, "BENCH_pipeline.json")
_INDEX_JSON_PATH = os.path.join(_REPO_ROOT, "BENCH_index.json")
_SERVE_JSON_PATH = os.path.join(_REPO_ROOT, "BENCH_serve.json")
_INGEST_JSON_PATH = os.path.join(_REPO_ROOT, "BENCH_ingest.json")
_COLUMNAR_JSON_PATH = os.path.join(_REPO_ROOT, "BENCH_columnar.json")


def _parse_row(line: str) -> dict:
    """One CSV row → record: section,key...,metric,value."""
    parts = line.split(",")
    try:
        value = float(parts[-1])
    except ValueError:
        value = parts[-1]
    return {"section": parts[0], "keys": parts[1:-2],
            "metric": parts[-2], "value": value}


def _summary(records: list[dict]) -> dict:
    """Headline throughput numbers, keyed stably for cross-PR diffing."""
    out: dict[str, float] = {}
    for r in records:
        if not isinstance(r["value"], float):
            continue
        if r["metric"] in ("records_per_s", "docs_per_s", "tokens_per_s",
                           "speedup", "requests_per_s",
                           "dispatches_per_request",
                           "dispatch_reduction_vs_sync",
                           "bytes_copied_per_record", "copy_reduction"):
            out[".".join([r["section"], *r["keys"], r["metric"]])] = r["value"]
    return out


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help=f"also write {os.path.basename(_JSON_PATH)}")
    # parallel runs before kernels on purpose: its worker-scaling pass
    # forks, and forking before JAX spins up its thread pools is both
    # safer and fairer on small hosts
    ap.add_argument("--sections",
                    default="table1,pipeline,parallel,ingest,index,serve,"
                            "columnar,kernels",
                    help="comma-separated subset of sections to run")
    args = ap.parse_args(argv)
    from repro.kernels import enable_compile_cache

    enable_compile_cache()
    sections = [s.strip() for s in args.sections.split(",") if s.strip()]
    known = {"table1", "pipeline", "kernels", "parallel", "index", "serve",
             "ingest", "columnar"}
    unknown = [s for s in sections if s not in known]
    if unknown:
        ap.error(f"unknown sections {unknown}; choose from {sorted(known)}")

    lines: list[str] = []
    if "table1" in sections:
        from benchmarks import table1

        print("section,compression,workload,parser,records_per_s,speedup")
        for row in table1.run(quiet=True):
            print(row.csv())
            # table1 rows end in (value, speedup); normalize for JSON
            parts = row.csv().split(",")
            lines.append(",".join(parts[:4] + ["records_per_s", parts[4]]))
            if parts[5]:
                lines.append(",".join(parts[:4] + ["speedup", parts[5]]))
        print()

    def _runner(name: str):
        # lazy per-section imports: kernel_bench imports jax at module
        # top, and the parallel section must fork its pools before jax
        # exists for the section ordering rationale above to hold
        import importlib

        return importlib.import_module(f"benchmarks.{name}_bench")

    section_mods = {"pipeline": "pipeline", "kernels": "kernel",
                    "parallel": "parallel", "index": "index",
                    "serve": "serve", "ingest": "ingest",
                    "columnar": "columnar"}
    index_lines: list[str] = []
    serve_lines: list[str] = []
    ingest_lines: list[str] = []
    columnar_lines: list[str] = []
    for name in sections:
        if name not in section_mods:
            continue
        rows = _runner(section_mods[name]).run(quiet=True)
        for line in rows:
            print(line)
        print()
        # index/serve/ingest/columnar rows track their own trajectory
        # files (BENCH_index.json / BENCH_serve.json / BENCH_ingest.json
        # / BENCH_columnar.json); mixing them into BENCH_pipeline.json
        # would let a section-only run clobber the pipeline history
        if name == "index":
            index_lines.extend(rows)
        elif name == "serve":
            serve_lines.extend(rows)
        elif name == "ingest":
            ingest_lines.extend(rows)
        elif name == "columnar":
            columnar_lines.extend(rows)
        else:
            lines.extend(rows)

    if args.json:

        from repro import obs

        obs_dict = obs.snapshot().as_dict()

        def _write(path: str, bench: str, rows: list[str],
                   ran: list[str]) -> None:
            records = [_parse_row(line) for line in rows]
            payload = {"bench": bench, "sections": ran,
                       "rows": records, "summary": _summary(records),
                       "obs": obs_dict}
            with open(path, "w") as f:
                json.dump(payload, f, indent=2)
                f.write("\n")
            print(f"wrote {path}")

        non_index = [s for s in sections
                     if s not in ("index", "serve", "ingest", "columnar")]
        if non_index:
            _write(_JSON_PATH, "pipeline", lines, non_index)
        if index_lines:
            _write(_INDEX_JSON_PATH, "index", index_lines, ["index"])
        if serve_lines:
            _write(_SERVE_JSON_PATH, "serve", serve_lines, ["serve"])
        if ingest_lines:
            _write(_INGEST_JSON_PATH, "ingest", ingest_lines, ["ingest"])
        if columnar_lines:
            _write(_COLUMNAR_JSON_PATH, "columnar", columnar_lines,
                   ["columnar"])


if __name__ == "__main__":
    main()
