"""StreamServer — continuous batching for live sDTW search traffic.

The paper's throughput story assumes fixed batches of equal-length
queries; live traffic is a ragged, bursty stream of single queries.
This is the host-side loop that turns one into the other without
giving up the repo's exactness guarantees:

  * **admission** — ``submit(query, k=..., deadline_ms=...)`` returns a
    ``concurrent.futures.Future`` immediately.  Admission is BOUNDED:
    past ``StreamConfig.max_queue`` waiting requests, submit raises
    :class:`RejectedError` carrying a retry-after — explicit
    backpressure instead of unbounded queue growth;
  * **batch formation** — admitted requests land on per-length buckets
    (the :class:`~repro.search.batcher.QueryBatcher` grid: batches are
    always SUBLANES x 2^k rows).  A bucket flushes the moment it is
    FULL (``max_batch`` rows — a zero-padding flush) or when its oldest
    request has waited ``max_wait_ms`` (bounded straggler latency),
    whichever comes first;
  * **dispatch** — formed batches go to a
    :class:`~repro.serve.pool.SessionPool` of sweep workers, each
    running an exact ``SearchService.topk`` over precompiled
    per-reference :class:`~repro.core.session.Aligner` sessions.
    Served hits are therefore bit-identical to an offline
    ``SearchService.topk`` on the same queries (asserted end-to-end by
    ``benchmarks/serve_stream.py``);
  * **robustness** — per-request deadlines produce well-formed
    ``status="timeout"`` responses (promptly while queued, and after
    the sweep if the deadline passed mid-flight); transient sweep
    failures are retried once (:mod:`repro.serve.faults`); ``drain()``
    completes all in-flight work while refusing new requests;
    ``close(drain=False)`` cancels queued work with ``"cancelled"``
    responses.  Every accepted request resolves its future exactly
    once — no hangs, no dropped futures.

Observability (``repro.obs``, names documented in the README):
counters ``serve.requests / completed / timeouts / rejected / retries /
errors / cancelled / batches / batch_rows_real / batch_rows_padded``,
gauge ``serve.queue_depth``, histograms ``serve.request_ms /
serve.batch_fill / serve.padding_waste / serve.batch_wait_ms``, spans
``serve.form`` / ``serve.sweep``.
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import threading
import time
from concurrent.futures import Future

import jax.numpy as jnp

from repro import obs
from repro.core.spec import require_univariate
from repro.search.batcher import QueryBatcher, grid_size
from repro.search.index import ReferenceIndex
from repro.search.service import Match, SearchConfig
from repro.serve.faults import FaultPolicy
from repro.serve.policy import StreamConfig, due_flushes
from repro.serve.pool import SessionPool, SweepBatch

log = logging.getLogger(__name__)


class RejectedError(RuntimeError):
    """Admission rejected under backpressure: the queue is full.  Retry
    after ``retry_after_s`` (also in the message)."""

    def __init__(self, msg: str, *, retry_after_s: float):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class ServerClosed(RuntimeError):
    """submit() on a draining or closed server."""


@dataclasses.dataclass(frozen=True)
class ServeResponse:
    """The terminal answer of one request — ALWAYS delivered (the
    future never raises for server-side conditions).

    status:     "ok" | "timeout" | "error" | "cancelled".
    hits:       the request's top-k :class:`Match`es ("ok" only).
    error:      human-readable cause ("error" only).
    latency_ms: submit-to-response wall clock.
    attempts:   sweep attempts behind this response (2 = one retry);
                0 when no sweep ran (queued timeout / cancel).
    """
    rid: object
    status: str
    hits: tuple = ()
    error: str | None = None
    latency_ms: float = 0.0
    attempts: int = 0

    @property
    def ok(self) -> bool:
        return self.status == "ok"


@dataclasses.dataclass
class _Pending:
    """Internal request record; doubles as the QueryBatcher qid."""
    rid: object
    query: jnp.ndarray
    k: int
    t_submit: float
    deadline_s: float | None                  # absolute monotonic
    future: Future
    done: bool = False

    def expired(self, now: float) -> bool:
        return self.deadline_s is not None and now >= self.deadline_s


class StreamServer:
    """One serving loop over one reference index.

    ``search`` configures the underlying ``SearchService`` workers
    (backend, spec, pruning, windows...); its ``max_slots`` is forced
    to ``config.max_batch`` so the sweep grid and the formation grid
    agree.  The server starts its loop thread immediately; use as a
    context manager (drains on exit) or call ``close()``.
    """

    def __init__(self, index: ReferenceIndex, *,
                 config: StreamConfig = StreamConfig(),
                 search: SearchConfig | None = None,
                 fault_policy: FaultPolicy | None = None,
                 metrics: obs.MetricsRegistry | None = None,
                 tracer: obs.Tracer | None = None):
        self.config = config
        search = SearchConfig() if search is None else search
        self.search = dataclasses.replace(search,
                                          max_slots=config.max_batch)
        self._metrics = obs.default_registry() if metrics is None else \
            metrics
        self._tracer = obs.default_tracer() if tracer is None else tracer
        self._pool = SessionPool(index, self.search, size=config.workers,
                                 max_retries=config.max_retries,
                                 fault_policy=fault_policy,
                                 metrics=self._metrics,
                                 tracer=self._tracer)
        self._batcher = QueryBatcher(max_slots=config.max_batch,
                                     metrics=self._metrics)
        self._cond = threading.Condition()
        self._arrivals: list[_Pending] = []
        self._pending = 0                    # admitted, not dispatched
        self._state = "running"              # draining | closing | closed
        self._rids = itertools.count()
        self._done = threading.Event()
        self._thread = threading.Thread(target=self._loop,
                                        name="repro-serve-loop",
                                        daemon=True)
        self._thread.start()

    # -------------------------------------------------------- admission
    def submit(self, query, *, k: int = 1,
               deadline_ms: float | None = None,
               rid: object = None) -> Future:
        """Admit one query; returns a future resolving to a
        :class:`ServeResponse`.  Raises :class:`RejectedError` under
        backpressure and :class:`ServerClosed` after drain/close —
        those are the only two server-side reasons a request does not
        get a future."""
        q = jnp.asarray(query)
        require_univariate(q, "query")
        if q.shape[0] == 0:
            raise ValueError("query must be a non-empty 1-D series")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if deadline_ms is None:
            deadline_ms = self.config.default_deadline_ms
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be positive, got "
                             f"{deadline_ms}")
        now = time.monotonic()
        req = _Pending(
            rid=rid if rid is not None else next(self._rids),
            query=q, k=int(k), t_submit=now,
            deadline_s=(now + deadline_ms / 1e3
                        if deadline_ms is not None else None),
            future=Future())
        with self._cond:
            if self._state != "running":
                raise ServerClosed(
                    f"server is {self._state}; not accepting requests")
            if self._pending >= self.config.max_queue:
                self._metrics.inc("serve.rejected")
                retry = self.config.retry_after_s
                raise RejectedError(
                    f"admission queue full ({self._pending} pending >= "
                    f"max_queue={self.config.max_queue}); retry after "
                    f"{retry:.3f}s", retry_after_s=retry)
            self._pending += 1
            self._arrivals.append(req)
            self._metrics.inc("serve.requests")
            self._metrics.set_gauge("serve.queue_depth", self._pending)
            self._cond.notify()
        return req.future

    @property
    def pending(self) -> int:
        """Requests admitted but not yet dispatched to the pool."""
        with self._cond:
            return self._pending

    def warmup(self, lengths, batches=None, k: int = 1) -> int:
        """Precompile sweep executables for the given query lengths
        (see :meth:`SessionPool.warmup`); call before live traffic."""
        from repro.kernels.sdtw_wavefront import SUBLANES
        batches = (SUBLANES, self.config.max_batch) if batches is None \
            else batches
        return self._pool.warmup(lengths, batches=batches, k=k)

    # --------------------------------------------------------- lifecycle
    def drain(self, timeout: float | None = None) -> bool:
        """Stop accepting, finish everything already admitted (queued
        AND in-flight), then shut the loop down.  Returns False if the
        work did not finish within ``timeout``."""
        with self._cond:
            if self._state == "running":
                self._state = "draining"
            self._cond.notify()
        return self._done.wait(timeout)

    def close(self, drain: bool = True,
              timeout: float | None = None) -> None:
        """Shut down.  ``drain=True`` finishes admitted work first;
        ``drain=False`` cancels queued requests (their futures resolve
        with ``status="cancelled"``) while in-flight sweeps still
        complete normally."""
        with self._cond:
            if self._state == "running":
                self._state = "draining" if drain else "closing"
            elif not drain and self._state == "draining":
                self._state = "closing"
            self._cond.notify()
        self._done.wait(timeout)
        self._pool.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close(drain=True)

    # ------------------------------------------------------------- loop
    def _next_wake(self, now: float) -> float | None:
        oldest = {length: req.t_submit
                  for length, req in self._batcher.oldest_ids().items()}
        due, wake = due_flushes(oldest, now, self.config.max_wait_s)
        if due:
            return now
        deadlines = [req.deadline_s for req in self._batcher.queued_ids()
                     if req.deadline_s is not None]
        candidates = ([wake] if wake is not None else []) + deadlines
        return min(candidates) if candidates else None

    def _loop(self) -> None:
        while True:
            with self._cond:
                now = time.monotonic()
                wake = self._next_wake(now)
                if not self._arrivals and self._state == "running":
                    self._cond.wait(timeout=(None if wake is None
                                             else max(wake - now, 0.0)))
                arrivals, self._arrivals = self._arrivals, []
                state = self._state
            if state == "closing":
                for req in arrivals:
                    self._leave_queue(1)
                    self._finish(req, "cancelled")
                for req, _ in self._batcher.evict(lambda r: True):
                    self._leave_queue(1)
                    self._finish(req, "cancelled")
                break
            emitted = []
            with self._tracer.span("serve.form", arrivals=len(arrivals)):
                for req in arrivals:
                    emitted += self._batcher.add(req, req.query)
                now = time.monotonic()
                expired = self._batcher.evict(lambda r: r.expired(now))
                for req, _ in expired:
                    self._leave_queue(1)
                    self._finish(req, "timeout")
                if state == "running":
                    oldest = {length: req.t_submit for length, req in
                              self._batcher.oldest_ids().items()}
                    due, _ = due_flushes(oldest, now,
                                         self.config.max_wait_s)
                    for length in due:
                        batch = self._batcher.flush_bucket(length)
                        if batch is not None:
                            emitted.append(batch)
                else:                       # draining: no reason to wait
                    emitted += self._batcher.flush()
            for batch in emitted:
                self._dispatch(batch)
            if state == "draining":
                with self._cond:
                    empty = (not self._arrivals
                             and self._batcher.pending() == 0)
                if empty:
                    break
        self._pool.join()
        with self._cond:
            self._state = "closed"
        self._done.set()
        log.info("serve loop stopped (state=closed)")

    # --------------------------------------------------------- dispatch
    def _dispatch(self, batch) -> None:
        reqs = list(batch.ids)
        self._leave_queue(len(reqs))
        now = time.monotonic()
        live = []
        for req in reqs:
            if req.expired(now):
                self._finish(req, "timeout")
            else:
                live.append(req)
        if not live:
            return
        m = self._metrics
        g = grid_size(len(live), self.config.max_batch)
        fill = len(live) / g
        m.inc("serve.batches")
        m.inc("serve.batch_rows_real", len(live))
        if g > len(live):
            m.inc("serve.batch_rows_padded", g - len(live))
        m.observe("serve.batch_fill", fill)
        m.observe("serve.padding_waste", 1.0 - fill)
        m.observe("serve.batch_wait_ms",
                  (now - min(r.t_submit for r in live)) * 1e3)
        kmax = max(req.k for req in live)

        def on_result(matches, error, attempts):
            end = time.monotonic()
            if error is not None:
                msg = str(error) or type(error).__name__
                for req in live:
                    self._finish(req, "error", error=msg,
                                 attempts=attempts)
                return
            for row, req in enumerate(live):
                if req.expired(end):
                    self._finish(req, "timeout", attempts=attempts)
                else:
                    self._finish(req, "ok", hits=matches[row][:req.k],
                                 attempts=attempts)

        self._pool.submit(SweepBatch(
            queries=[req.query for req in live], k=kmax,
            on_result=on_result, length=batch.length, rows=g))

    # ----------------------------------------------------------- finish
    def _leave_queue(self, n: int) -> None:
        with self._cond:
            self._pending -= n
            self._metrics.set_gauge("serve.queue_depth", self._pending)

    _STATUS_COUNTER = {"ok": "serve.completed",
                       "timeout": "serve.timeouts",
                       "error": "serve.errors",
                       "cancelled": "serve.cancelled"}

    def _finish(self, req: _Pending, status: str, *, hits=(),
                error: str | None = None, attempts: int = 0) -> None:
        if req.done:                       # double-complete guard
            return
        req.done = True
        latency_ms = (time.monotonic() - req.t_submit) * 1e3
        self._metrics.inc(self._STATUS_COUNTER[status])
        self._metrics.observe("serve.request_ms", latency_ms)
        req.future.set_result(ServeResponse(
            rid=req.rid, status=status, hits=tuple(hits), error=error,
            latency_ms=latency_ms, attempts=attempts))
