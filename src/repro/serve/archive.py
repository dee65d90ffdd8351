"""``repro.serve.archive`` — sharded async archive query gateway
(DESIGN.md §8 and §12).

PR 2's :class:`~repro.index.service.IndexQueryService` is synchronous:
every request pays for its own scan. PR 3 added this multi-tenant layer
— admission queue, request coalescing, cross-request kernel batching, a
byte-budgeted record cache — but with **one** scheduler thread, and
BENCH_serve.json recorded the consequence: throughput collapsed ~5×
from 8 to 64 clients while PR 8's stage attribution showed 90% of
request time was ``queue_wait`` behind that single drain loop.

PR 9 makes the gateway a **supervised shard pool**:

* **router front end** (this class) — :meth:`submit` hashes the
  request's *scan identity* (``QueryRequest.scan_key``) onto one of N
  :class:`~repro.serve.shard.ShardScheduler` shards. Affinity hashing
  is what keeps coalescing intact: identical scans always route to the
  same shard, so its in-flight registry sees every duplicate, exactly
  as the single scheduler did;
* **per-shard admission budgets** — each shard bounds its own queue
  depth (``max_pending`` is per shard) and optionally its pending
  estimated scan bytes; rejections are typed, shard-tagged
  :class:`GatewayOverloaded` (``.shard``/``.reason``) instead of one
  global cliff. Overload never spills to a sibling shard — that would
  split a scan identity across two in-flight registries and silently
  un-coalesce it;
* **sharded record cache** — :class:`~repro.serve.cache.
  ShardedRecordCache` consistent-hashes payload keys over per-slice
  TinyLFU caches: shards never duplicate hot bytes, and a shard death
  evicts only its slice;
* **supervision + re-drive** — a supervisor thread watches shard
  heartbeats/liveness, reaps a dead shard's tickets (queued, serving,
  and coalesce-attached alike), respawns it with capped backoff, and
  re-drives every orphan through the router **exactly once**; a ticket
  whose re-drive also dies fails with a typed
  :class:`GatewayShardDown`. Nothing is silently dropped and no future
  resolves twice (futures are claimed with
  ``set_running_or_notify_cancel`` before every resolution, everywhere).

``shards=1`` (the default) preserves the PR 3–8 topology and behaviour
exactly; the serving machinery itself lives in
:mod:`repro.serve.shard`. Request-scoped tracing (PR 8) is unchanged
but spans now carry a ``shard`` attribute and anomaly flight dumps are
shard-tagged.

Correctness bar: responses are **byte-identical** to what an independent
synchronous :class:`~repro.index.query.QueryEngine` run would produce —
routing, coalescing, caching, shared dispatch and re-drive change *when*
and *where* work happens, never *what* is computed (the soak + chaos
tests assert exactly this).
"""
from __future__ import annotations

import hashlib
import threading
import time
from concurrent.futures import Future

from repro.index.cdx import CdxIndex
from repro.index.query import QueryEngine
from repro.index.service import QueryRequest, QueryResponse
from repro.obs import flight as obs_flight
from repro.obs import trace as obs_trace
from .cache import ShardedRecordCache
from .metrics import GatewayMetrics
from .shard import (_NULL_CM, GatewayClosed, GatewayOverloaded,
                    GatewayShardDown, GatewayTimeout, ShardScheduler,
                    _StageCM, _Ticket)

__all__ = ["ArchiveGateway", "GatewayClosed", "GatewayOverloaded",
           "GatewayShardDown", "GatewayTimeout"]


def _key_hash(key: tuple) -> int:
    """Stable 64-bit hash of a scan identity (process-independent —
    ``repr`` of the key tuple, not Python's seeded ``hash``)."""
    digest = hashlib.blake2b(repr(key).encode("utf-8", "backslashreplace"),
                             digest_size=8).digest()
    return int.from_bytes(digest, "big")


class ArchiveGateway:
    """Sharded, coalescing, cross-request-batching query front end.

    >>> with ArchiveGateway(index, shards=4) as gw:
    ...     fut = gw.submit(QueryRequest(b"nginx"))
    ...     response = fut.result()
    ...     gw.metrics.snapshot(gw.cache)["dispatches_per_request"]

    Parameters
    ----------
    index:
        the corpus CDX index the gateway serves.
    shards:
        scheduler shard count (default 1 — the pre-PR 9 topology).
        Each shard owns an engine, a drain thread and its own admission
        budget; requests route by scan-identity affinity hashing.
    engine:
        optional pre-built :class:`QueryEngine` for shard 0; owned (and
        closed) by its shard either way. Additional shards build their
        own via ``engine_factory`` / the default constructor args.
    engine_factory:
        ``callable(shard_id) -> QueryEngine`` for building per-shard
        engines (tests inject instrumented engines this way).
    max_pending:
        **per-shard** admission-queue bound — the backpressure knob.
    shard_byte_budget:
        optional per-shard bound on *pending estimated scan bytes*:
        each unique queued scan identity charges ``est_scan_bytes``
        (coalesced duplicates are free); over budget, new identities
        are rejected with ``GatewayOverloaded(reason="bytes")``.
    est_scan_bytes:
        the per-unique-scan byte charge above (default 1 MiB).
    max_batch_requests:
        how many queued requests one shard drain may aggregate.
    cache_bytes:
        byte budget of the decompressed-payload cache, split evenly
        across per-shard consistent-hash slices.
    cache_admission:
        ``"tinylfu"`` (default) or ``"lru"`` — per slice, as before.
    default_deadline_s:
        deadline applied to every request that doesn't carry its own
        ``deadline_s`` at :meth:`submit`; expired requests resolve with
        :class:`GatewayTimeout` instead of occupying scan capacity.
    trace_requests:
        request-scoped span tracing (default on; the serve bench gates
        the traced path at ≤1.05× the untraced one).
    flight_recorder:
        where finished spans and anomaly dumps go; ``None`` uses the
        process-default :func:`repro.obs.flight.recorder`. Dumps
        tripped by a shard carry a ``shard<i>`` tag.
    slo_p99_s / queue_highwater:
        anomaly-dump trips, unchanged from PR 8 (highwater is per
        shard, default ¾ of ``max_pending``).
    max_respawns:
        how many times a dying shard is respawned before it is retired
        (marked permanently down; traffic routes around it and its
        cache slice is removed from the ring).
    respawn_backoff_s:
        base of the capped exponential respawn backoff
        (``min(1s, base·2^respawns)``).
    """

    def __init__(self, index: CdxIndex, *, engine: QueryEngine | None = None,
                 shards: int = 1,
                 engine_factory=None,
                 max_pending: int = 256, max_batch_requests: int = 16,
                 cache_bytes: int = 64 << 20, cache_admission: str = "tinylfu",
                 use_kernel: bool = True, poll_interval_s: float = 0.02,
                 default_deadline_s: float | None = None,
                 trace_requests: bool = True,
                 flight_recorder: obs_flight.FlightRecorder | None = None,
                 slo_p99_s: float | None = None,
                 queue_highwater: int | None = None,
                 shard_byte_budget: int | None = None,
                 est_scan_bytes: int = 1 << 20,
                 max_respawns: int = 3,
                 respawn_backoff_s: float = 0.05,
                 ) -> None:
        n = max(1, int(shards))
        self.index = index
        self.cache = ShardedRecordCache(cache_bytes, n,
                                        admission=cache_admission)
        self.metrics = GatewayMetrics()
        self.default_deadline_s = default_deadline_s
        self._trace = bool(trace_requests)
        self._flight = flight_recorder if flight_recorder is not None \
            else obs_flight.recorder()
        self._max_respawns = max(0, int(max_respawns))
        self._backoff = max(0.0, respawn_backoff_s)
        self._closed = False
        self._reap_lock = threading.Lock()

        def _default_engine(_i: int) -> QueryEngine:
            return QueryEngine(index, use_kernel=use_kernel)

        factory = engine_factory if engine_factory is not None \
            else _default_engine
        self._shards: list[ShardScheduler] = []
        for i in range(n):
            eng = engine if (i == 0 and engine is not None) else factory(i)
            self._shards.append(ShardScheduler(
                i, engine=eng, cache=self.cache, metrics=self.metrics,
                max_pending=max_pending, byte_budget=shard_byte_budget,
                est_scan_bytes=est_scan_bytes,
                max_batch_requests=max_batch_requests,
                poll_interval_s=poll_interval_s,
                trace_requests=trace_requests,
                flight_recorder=self._flight,
                slo_p99_s=slo_p99_s, queue_highwater=queue_highwater))
        self.metrics.gauge_set("shards", n)
        for shard in self._shards:
            shard.start()
        self._sup_stop = threading.Event()
        self._sup_thread = threading.Thread(
            target=self._supervise, daemon=True, name="gw-supervisor")
        self._sup_thread.start()

    # -- public surface ---------------------------------------------------
    @property
    def shards(self) -> list[ShardScheduler]:
        return self._shards

    @property
    def engine(self) -> QueryEngine:
        """Shard 0's engine (single-shard compatibility surface)."""
        return self._shards[0].engine

    def pending(self) -> int:
        return sum(shard.pending() for shard in self._shards)

    # -- tracing plumbing -------------------------------------------------
    def _end_span(self, span: obs_trace.Span | None) -> None:
        if span is not None:
            self.metrics.observe_stage(span.name,
                                       span.finish(recorder=self._flight))

    def _stage(self, name: str, parent=None, attrs=None):
        if not self._trace:
            return _NULL_CM
        return _StageCM(self, name, parent, attrs)

    def _trip(self, reason: str, attrs: dict | None = None,
              tag: str | None = None) -> None:
        if self._flight.trip(reason, attrs, tag=tag) is not None:
            self.metrics.inc("flight_dumps")

    # -- routing ----------------------------------------------------------
    def _shard_index(self, key: tuple) -> int:
        """Affinity home of a scan identity (ignoring down shards)."""
        return _key_hash(key) % len(self._shards)

    def _candidates(self, key: tuple):
        """The affinity ring walk: owner shard first, then successors,
        skipping permanently-down shards. Affinity is what preserves
        coalescing — every candidate order for a given key is stable
        while the down-set is stable."""
        shards = self._shards
        start = _key_hash(key) % len(shards)
        for j in range(len(shards)):
            shard = shards[(start + j) % len(shards)]
            if not shard.down:
                yield shard

    def _admit(self, key: tuple, ticket: _Ticket, *, block: bool,
               timeout: float | None, force: bool = False
               ) -> tuple[str, int, ShardScheduler]:
        last: GatewayShardDown | None = None
        for shard in self._candidates(key):
            try:
                status, detail = shard.admit(ticket, block=block,
                                             timeout=timeout, force=force)
                return status, detail, shard
            except GatewayShardDown as exc:
                last = exc  # raced a retirement: next ring candidate
                continue
        raise last if last is not None else GatewayShardDown(
            "all gateway shards are down")

    # -- client side -----------------------------------------------------
    def submit(self, request: QueryRequest, *, block: bool = True,
               timeout: float | None = None,
               deadline_s: float | None = None) -> "Future[QueryResponse]":
        """Route one request to its affinity shard; returns the future.

        An identical scan already **executing** on the shard is joined
        directly (the in-flight coalescing fast path, no queue slot);
        identical requests sitting in the shard queue merge when it
        drains them into the same batch. With ``block=False`` (or on
        ``timeout``) an over-budget shard raises
        :class:`GatewayOverloaded` — typed, shard-tagged backpressure.

        ``deadline_s`` (default: the gateway's ``default_deadline_s``)
        bounds how long the request may wait end-to-end: a ticket whose
        deadline expires before its batch resolves gets
        :class:`GatewayTimeout` instead of a response — under overload
        the shards shed expired queue entries without scanning for them.
        """
        if self._closed:
            raise GatewayClosed("gateway is closed")
        ticket = _Ticket(request)
        budget = deadline_s if deadline_s is not None \
            else self.default_deadline_s
        if budget is not None:
            ticket.deadline = ticket.t_submit + budget
        adm = None
        if self._trace:
            # root span: the whole request, submit → resolution; its
            # trace id rides the ticket across the scheduler boundary
            ticket.span = obs_trace.start_span(
                "gw.request", parent=obs_trace.ROOT, t0=ticket.t_submit,
                attrs={"pattern": repr(request.pattern[:64]),
                       "regex": request.regex, "top_k": request.top_k})
            adm = obs_trace.start_span("gw.admission", ticket.span,
                                       t0=ticket.t_submit)
        key = request.scan_key()
        try:
            status, detail, shard = self._admit(key, ticket, block=block,
                                                timeout=timeout)
        except (GatewayOverloaded, GatewayShardDown) as exc:
            if adm is not None:
                adm.set_attr("rejected", True)
                if getattr(exc, "shard", None) is not None:
                    adm.set_attr("shard", exc.shard)
                self._end_span(adm)
                ticket.span.set_attr("error", type(exc).__name__)
                ticket.span.finish(recorder=self._flight)
            raise
        if adm is not None:
            adm.set_attr("shard", shard.shard_id)
            self._end_span(adm)
            if status == "attached":
                with self._stage("gw.coalesce_attach", ticket.span,
                                 attrs={"inflight_waiters": detail,
                                        "shard": shard.shard_id}):
                    pass
            else:
                ticket.wait_span = obs_trace.start_span(
                    "gw.queue_wait", ticket.span,
                    attrs={"shard": shard.shard_id})
        if status == "queued" and self._closed and not shard.alive():
            # raced close(): we passed the closed check before close()
            # flipped it, but enqueued after the drain thread exited —
            # no one will serve the queue again, so fail it now
            shard.fail_queued()
        return ticket.future

    def query(self, request: QueryRequest,
              timeout: float | None = None) -> QueryResponse:
        """Synchronous convenience: submit and wait."""
        return self.submit(request).result(timeout)

    def snapshot(self):
        """Observability hook: one merged :class:`~repro.obs.ObsSnapshot`
        — this gateway's private metrics registry + cache counters
        (source ``"gateway"``) merged with the process-default registry
        (kernel dispatch profile, ingest counters, harvested children).
        For the raw dict surface keep using ``gateway.metrics.snapshot()``.
        """
        from repro import obs

        return obs.snapshot().merged_with(
            self.metrics.obs_snapshot(self.cache))

    # -- supervision + re-drive -------------------------------------------
    def _supervise(self) -> None:
        while not self._sup_stop.wait(0.02):
            for shard in self._shards:
                if shard.dead and not shard.alive() and not shard.closed:
                    self._reap(shard)

    def _reap(self, shard: ShardScheduler, closing: bool = False) -> None:
        """Handle one shard death: collect its tickets exactly once,
        respawn (capped backoff) or retire it, re-drive the orphans."""
        with self._reap_lock:
            if shard._reaped or not shard.dead:
                return  # lost the race: someone else already reaped it
            sid = shard.shard_id
            self.metrics.inc("shard_deaths")
            self._trip("shard_down",
                       {"shard": sid, "respawns": shard.respawns},
                       tag=f"shard{sid}")
            retire = closing or shard.respawns >= self._max_respawns
            if retire:
                # retirement: route around it and drop its cache slice
                # from the ring (only *its* keys are invalidated)
                shard.mark_down()
                self.metrics.inc("shards_down")
                self.cache.remove_slice(sid)
            orphans = shard.take_orphans()
            if not retire:
                delay = min(1.0, self._backoff * (2 ** shard.respawns))
                if delay > 0:
                    time.sleep(delay)
                # a dirty death may have left mid-fill entries behind:
                # evict this shard's slice only, siblings keep their heat
                self.cache.clear_slice(sid)
                shard.respawn()
                self.metrics.inc("shard_respawns")
        for ticket in orphans:
            self._redrive(ticket, sid)

    def _redrive(self, ticket: _Ticket, from_shard: int) -> None:
        """Recover one orphaned ticket: exactly one re-route through the
        affinity ring (budgets bypassed — it was already admitted once);
        a second death fails it with :class:`GatewayShardDown`."""
        if ticket.future.done():
            return
        if ticket.redriven:
            self._fail_shard_down(ticket, from_shard)
            return
        ticket.redriven = True
        self.metrics.inc("redriven")
        if ticket.span is not None:
            with self._stage("gw.redrive", ticket.span,
                             attrs={"from_shard": from_shard}):
                pass
        try:
            self._admit(ticket.request.scan_key(), ticket,
                        block=False, timeout=None, force=True)
        except GatewayShardDown:
            self._fail_shard_down(ticket, from_shard)

    def _fail_shard_down(self, ticket: _Ticket, shard_id: int) -> None:
        """Typed terminal failure for an unrecoverable orphan (claimed
        first, so a raced resolution can never double-resolve)."""
        if not ticket.future.set_running_or_notify_cancel():
            return
        ticket.future.set_exception(GatewayShardDown(
            f"shard {shard_id} died before serving this request",
            shard=shard_id))
        self.metrics.inc("shard_down_errors")
        if ticket.span is not None:
            ticket.span.set_attr("error", "GatewayShardDown")
            ticket.span.finish(recorder=self._flight)

    # -- lifecycle -------------------------------------------------------
    def close(self, *, drain: bool = True, timeout: float = 30.0) -> None:
        """Stop the pool; by default serve everything already queued.

        Order matters for the close audit: (1) reject new submissions,
        (2) stop the supervisor (no respawns during teardown), (3) reap
        any already-dead shard — its orphans re-drive into siblings that
        are *still open* and will drain them, (4) close shards one by
        one (each serves its queue), (5) fail anything a shard that died
        *during* its own drain left behind, with :class:`GatewayShardDown`.
        A waiter attached to an in-flight batch on shard A is resolved by
        step (4) regardless of what order siblings closed in — shards
        never wait on each other, so there is no deadlock to have.

        ``drain=False`` fails queued-but-unserved requests with
        :class:`GatewayClosed` instead of serving them. Raises
        ``TimeoutError`` if any shard is still mid-scan after
        ``timeout`` — its engine is left open; call ``close`` again to
        retry teardown.
        """
        self._closed = True  # reject new submissions immediately
        self._sup_stop.set()
        if self._sup_thread.is_alive():
            self._sup_thread.join(5.0)
        for shard in self._shards:
            if shard.dead and not shard.alive():
                self._reap(shard, closing=True)
        timeout_exc: TimeoutError | None = None
        for shard in self._shards:
            try:
                shard.close(drain=drain, timeout=timeout)
            except TimeoutError as exc:
                timeout_exc = timeout_exc or exc
        for shard in self._shards:
            # a death mid-close-drain cannot re-drive (siblings are
            # closing/closed): typed failure, never a silent drop
            if shard.dead:
                for ticket in shard.take_orphans():
                    self._fail_shard_down(ticket, shard.shard_id)
        if timeout_exc is not None:
            raise timeout_exc

    def __enter__(self) -> "ArchiveGateway":
        return self

    def __exit__(self, *exc_info) -> None:
        self.close()
