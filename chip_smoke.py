#!/usr/bin/env python3
"""One-chip smoke run of the main path: derive → store scan → gateway.

Drives the library through its normal entry points, in one process:

1. generate two gzip WARC shards from ``--seed`` (``repro.data.synth``):
   the default 40,000 pages (~120k records, about the record count of
   one Common Crawl WARC file), and ``--wide-pages`` pages whose HTML
   runs from ~50 KB up to just under the 1 MiB payload at which Common
   Crawl truncates records, so that every row-group width from 64 KiB
   to 1 MiB is swept;
2. ``repro.columnar.derive`` both into one columnar store (fused
   digest/signature kernel);
3. check a seeded sample of stored payloads, digests and signatures
   against the source records, ``zlib.adler32`` and ``signature_of``;
4. ``verify_digests_bulk`` over that sample (Adler-32 kernel), with one
   corrupted payload that must fail;
5. ``QueryEngine.from_store`` on a broad literal, a selective literal and
   a regex (row-group scan kernel), each compared with
   ``full_scan_search`` / ``full_scan_regex`` over the shards;
6. an ``ArchiveGateway`` over that engine answering concurrent requests
   (multi-pattern kernel): every answer equals step 5's, with no failed,
   timed-out or quarantined request.

Earlier lines report each phase's wall time, compilations, the row-group
widths and the kernel dispatch counts; every main-path kernel must have
dispatched. Any
mismatch raises. The last line of a passing run on a TPU is
``{"ok": true, "device": {...}}``.

Without a TPU the script exits 1 at once. ``--rehearse`` runs the phases
anyway on the CPU backend (Pallas interpreter; use a small ``--pages``)
and still exits 1 without printing a result.

    python chip_smoke.py                              # on the chip
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --pages 300 \
        --wide-pages 6
"""
from __future__ import annotations

import argparse
import json
import random
import sys
import tempfile
import time
import zlib
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# main-path kernel wrappers, as named in repro.obs.kernels' counters
MAIN_KERNELS = ("digest_signature_rowgroup", "adler32_batch",
                "find_pattern_mask_rowgroup", "find_pattern_masks_multi")
MIB = 1 << 20
WIDE = 64 << 10  # narrowest row-group width the wide shard lands in
# HTML words of a wide page (~4.2 bytes each): ~50 KB, whose payload
# pads to the 64 KiB bucket, up to ~1.02 MB, under the 1 MiB truncation
WIDE_WORDS = (12_000, 245_000)
QUERIES = (  # (pattern, regex): broad literal, selective literal, regex
    (b"<title>", False),
    (b"fetchTimeMs: 421", False),
    (rb"Server: nginx/1\.2[0-4]", True),
)


class Phases:
    """Wall time and compilations of each phase, printed as it ends."""

    def __init__(self) -> None:
        import jax

        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event: str, duration: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def run(self, name: str, fn, *args):
        c0, h0, t0 = self.compiles, self.cache_hits, time.perf_counter()
        out = fn(*args)
        print(f"phase {name}: {time.perf_counter() - t0:.3f} s, "
              f"{self.compiles - c0} compilations "
              f"({self.cache_hits - h0} from the persistent cache)",
              flush=True)
        return out


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def dispatches(kernel: str) -> int:
    from repro import obs

    return int(obs.registry().snapshot().counters.get(
        f"kernel.{kernel}.dispatches", 0))


def generate(tmp: Path, pages: int, wide_pages: int, seed: int
             ) -> tuple[list[str], int]:
    """The main shard and the wide shard; returns their paths and the
    number of records they hold."""
    from repro.data.synth import CorpusSpec, records_in, write_corpus

    lo, hi = WIDE_WORDS
    specs = {"main": CorpusSpec(n_pages=pages, seed=seed)}
    if wide_pages:
        specs["wide"] = CorpusSpec(n_pages=wide_pages, seed=seed + 1,
                                   html_words_lo=lo, html_words_hi=hi)
    paths = []
    for name, spec in specs.items():
        path = str(tmp / f"{name}.warc.gz")
        size = write_corpus(path, spec, compression="gzip")
        print(f"shard {name}: {records_in(spec)} records, {size} bytes "
              f"gzip", flush=True)
        paths.append(path)
    return paths, sum(map(records_in, specs.values()))


def derive_store(shards: list[str], out: Path, n_records: int,
                 wide: bool):
    from repro.columnar import derive

    store = derive(shards, str(out), workers=0)
    check(len(store) == n_records, f"store has {len(store)} rows, "
          f"shards have {n_records} records")
    check(not store.errors, f"derive reported damage: {store.errors[:3]}")
    check(dispatches("digest_signature_rowgroup") == store.n_rowgroups,
          "derive did not sweep every row-group with the fused kernel")
    widths = np.asarray(store.rg_width)
    check(int(store.length.max()) <= MIB,
          "a payload is past the 1 MiB truncation width")
    check(not wide or int((widths >= WIDE).sum()) > 0,
          "the wide shard made no row-group of 64 KiB or more")
    ladder = {int(w): int(c) for w, c in zip(*np.unique(
        widths[widths >= WIDE], return_counts=True))}
    print(f"store: {store.n_rowgroups} row-groups, "
          f"{int(store.length.sum())} payload bytes, "
          f"pad waste {store.pad_waste_ratio():.4f}; widths "
          f"{int(widths.min())}..{int(widths.max())}, row-groups per "
          f"width from 64 KiB: {ladder}", flush=True)
    return store


def check_sample(store, shards: list[str], rng: random.Random
                 ) -> list[bytes]:
    """Stored payload, digest and signature of sampled rows against the
    source records; returns the sampled payloads."""
    from repro.core.warc.fastwarc import FastWARCIterator
    from repro.index.signature import signature_of

    n = len(store)
    widest = sorted(range(n), key=lambda r: -int(store.length[r]))[:8]
    rows = sorted(set(rng.sample(range(n), min(n, 2000)) + widest))
    by_key = {(int(store.shard_id[r]), int(store.offset[r])): r
              for r in rows}
    source: dict[int, bytes] = {}
    for sid, shard in enumerate(shards):
        for record in FastWARCIterator(shard, parse_http=False):
            row = by_key.get((sid, record.stream_offset))
            if row is not None:
                source[row] = record.content
    check(len(source) == len(rows), "sampled rows missing from the shards")
    payloads = []
    for r in rows:
        data = source[r]
        check(store.payload(r) == data, f"row {r}: stored payload differs")
        check(int(store.digest[r]) == zlib.adler32(data) & 0xFFFFFFFF,
              f"row {r}: digest differs from zlib.adler32")
        sig = signature_of(data, bits=store.sig_bits, n=store.sig_ngram,
                           k=store.sig_hashes)
        check(bool((store.signatures[r] == sig).all()),
              f"row {r}: signature differs from signature_of")
        payloads.append(data)
    print(f"sample: {len(rows)} rows, largest {max(map(len, payloads))} "
          f"bytes", flush=True)
    return payloads


def verify_sample(payloads: list[bytes]) -> None:
    from repro.core.warc.checksum import verify_digests_bulk

    headers = [f"adler32:{zlib.adler32(p) & 0xFFFFFFFF:08x}"
               for p in payloads]
    bad = max(range(len(payloads)), key=lambda i: len(payloads[i]))
    corrupt = bytearray(payloads[bad])
    corrupt[len(corrupt) // 2] ^= 0x01
    datas = payloads[:bad] + [bytes(corrupt)] + payloads[bad + 1:]
    before = dispatches("adler32_batch")
    ok = verify_digests_bulk(datas, headers, use_kernel=True)
    check(dispatches("adler32_batch") > before,
          "verify_digests_bulk did not dispatch the adler32 kernel")
    check(ok == [i != bad for i in range(len(datas))],
          "bulk digest verification disagrees with zlib.adler32")


def as_counts(hits) -> dict[tuple[str, int], int]:
    return {(h.shard, h.offset): h.n_matches for h in hits}


def run_queries(store, shards: list[str]) -> tuple[object, list[dict]]:
    from repro.index.query import (QueryEngine, full_scan_regex,
                                   full_scan_search)

    engine = QueryEngine.from_store(store)
    answers = []
    for pattern, regex in QUERIES:
        plan = (engine.plan_regex(pattern) if regex
                else engine.plan(pattern))
        check(not plan.needs_host_scan, f"{pattern!r} is not kernel-eligible")
        before = dispatches("find_pattern_mask_rowgroup")
        t0 = time.perf_counter()
        got = as_counts(engine.execute(plan))
        t1 = time.perf_counter()
        check(dispatches("find_pattern_mask_rowgroup") > before
              or not plan.rows.size,
              f"{pattern!r} was not scanned by the row-group kernel")
        ref = (full_scan_regex if regex else full_scan_search)(
            shards, pattern)
        t2 = time.perf_counter()
        check(got == ref, f"{pattern!r}: {len(got)} hits, reference "
              f"{len(ref)}")
        print(f"query {pattern!r}: {len(got)} records, "
              f"{sum(got.values())} matches; engine {t1 - t0:.3f} s, "
              f"reference {t2 - t1:.3f} s", flush=True)
        answers.append(got)
    check(all(answers[::2]), "the broad literal or the regex found nothing")
    return engine, answers


def run_gateway(engine, answers: list[dict]) -> None:
    from repro.index.service import QueryRequest
    from repro.serve.archive import ArchiveGateway

    top_k = len(engine.index)
    requests = [QueryRequest(p, regex=rx, top_k=top_k)
                for p, rx in QUERIES] * 3
    expect = answers * 3
    before = dispatches("find_pattern_masks_multi")
    with ArchiveGateway(engine.index, engine=engine) as gw:
        futures = [gw.submit(r) for r in requests]
        responses = [f.result(timeout=600) for f in futures]
        metrics = gw.metrics
        for name in ("errors", "timeouts", "quarantined_rows",
                     "read_errors", "rejected", "shard_deaths"):
            check(metrics.count(name) == 0,
                  f"gateway {name} = {metrics.count(name)}")
        check(metrics.count("responses") == len(requests),
              "gateway did not answer every request")
    check(dispatches("find_pattern_masks_multi") > before,
          "gateway did not dispatch the multi-pattern kernel")
    for req, resp, want in zip(requests, responses, expect):
        check(resp.total_matches == len(want)
              and as_counts(resp.hits) == want,
              f"gateway answer for {req.pattern!r} differs")
    print(f"gateway: {len(requests)} requests, all equal to the engine's "
          f"answers", flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--pages", type=int, default=40_000)
    ap.add_argument("--wide-pages", type=int, default=400,
                    help="pages of 50 KB - 1 MB in the second shard")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases without a TPU (still exits 1)")
    args = ap.parse_args(argv)

    import repro.kernels

    cache_dir = repro.kernels.enable_compile_cache()
    import jax

    from repro import obs

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}, compile cache: {cache_dir}", flush=True)
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        print("chip_smoke: no TPU found (pass --rehearse for a CPU run)",
              file=sys.stderr)
        return 1
    check(repro.kernels.interpret_mode() is not on_tpu,
          "kernels would not be compiled for the TPU")

    phases = Phases()
    rng = random.Random(args.seed)
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        shards, n = phases.run("generate", generate, Path(tmp), args.pages,
                               args.wide_pages, args.seed)
        store = phases.run("derive", derive_store, shards,
                           Path(tmp) / "store.repcol", n,
                           args.wide_pages > 0)
        sample = phases.run("check_sample", check_sample, store, shards,
                            rng)
        phases.run("verify_digests", verify_sample, sample)
        engine, answers = phases.run("query", run_queries, store, shards)
        phases.run("gateway", run_gateway, engine, answers)
        del engine
        store.close()
    counters = obs.registry().snapshot().counters
    counts = {k: int(counters.get(f"kernel.{k}.dispatches", 0))
              for k in MAIN_KERNELS}
    print(f"kernel dispatches: {counts}", flush=True)
    print(f"readahead decoder: {int(counters.get('decoder.members', 0))} "
          f"members decoded in the child process", flush=True)
    print(f"total: {time.perf_counter() - t0:.3f} s, {phases.compiles} "
          f"compilations ({phases.cache_hits} from the persistent cache)",
          flush=True)
    check(all(counts.values()), f"a main-path kernel never ran: {counts}")
    if not on_tpu:
        print("chip_smoke: rehearsal passed; no TPU, so no result",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
