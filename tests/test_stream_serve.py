"""repro.serve streaming service: served hits bit-identical to offline
topk, batch-formation policy (flush-on-full / flush-on-age), deadline
timeouts, backpressure rejects, retry-once fault tolerance, graceful
drain/cancel — no hangs, no dropped futures — plus the QueryBatcher's
streaming-admission hooks and grid invariants under any interleaving."""
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.data.cbf import make_search_dataset
from repro.kernels.sdtw_wavefront import SUBLANES
from repro.search import (QueryBatcher, ReferenceIndex, SearchConfig,
                          SearchService, grid_size)
from repro.serve import (FaultPolicy, RejectedError, ServerClosed,
                         SessionPool, StreamConfig, StreamServer,
                         SweepBatch, TransientSweepError, due_flushes)

WAIT = 30.0                             # generous future timeout: a test
#                                         failure must be an assert, not
#                                         a hang


@pytest.fixture(scope="module")
def workload():
    refs, queries, labels = make_search_dataset(
        seed=5, n_refs=3, motifs_per_ref=5, motif_len=48, n_queries=12)
    # second length bucket: every third query truncated
    queries = [np.asarray(q[: (3 * len(q)) // 4]) if i % 3 == 2 else q
               for i, q in enumerate(queries)]
    index = ReferenceIndex()
    for name, series in refs.items():
        index.add(name, series)
    return index, queries, labels


@pytest.fixture(scope="module")
def offline_hits(workload):
    index, queries, _ = workload
    svc = SearchService(index, SearchConfig(),
                        metrics=obs.MetricsRegistry())
    return svc.topk(queries, k=2)


def make_server(index, *, metrics=None, fault_policy=None, **cfg):
    cfg.setdefault("max_batch", SUBLANES)
    cfg.setdefault("max_wait_ms", 5.0)
    metrics = obs.MetricsRegistry() if metrics is None else metrics
    return StreamServer(index, config=StreamConfig(**cfg),
                        metrics=metrics, tracer=obs.Tracer(),
                        fault_policy=fault_policy)


def assert_same_hits(served, want):
    assert len(served) == len(want)
    for a, b in zip(served, want):
        assert (a.reference, a.cost, a.end, a.start) == \
            (b.reference, b.cost, b.end, b.start)


# ------------------------------------------------------- served == offline
def test_served_bit_identical_to_offline(workload, offline_hits):
    index, queries, _ = workload
    metrics = obs.MetricsRegistry()
    with make_server(index, metrics=metrics) as srv:
        futs = [srv.submit(q, k=2) for q in queries]
        resps = [f.result(timeout=WAIT) for f in futs]
    for resp, want in zip(resps, offline_hits):
        assert resp.ok and resp.attempts == 1
        assert_same_hits(resp.hits, want)
    assert metrics.value("serve.completed") == len(queries)
    assert metrics.value("serve.requests") == len(queries)
    assert metrics.value("serve.timeouts") == 0
    assert metrics.value("serve.queue_depth") == 0


def test_per_request_k_heterogeneous(workload, offline_hits):
    """Requests with different k share one sweep; each response is cut
    to ITS k and still bitwise matches offline at that k."""
    index, queries, _ = workload
    with make_server(index) as srv:
        futs = [srv.submit(q, k=1 + (i % 2))
                for i, q in enumerate(queries)]
        resps = [f.result(timeout=WAIT) for f in futs]
    for i, (resp, want) in enumerate(zip(resps, offline_hits)):
        assert resp.ok
        assert len(resp.hits) == 1 + (i % 2)
        assert_same_hits(resp.hits, want[: 1 + (i % 2)])


# --------------------------------------------------------- formation policy
def test_flush_on_full_and_batch_grid(workload):
    """max_batch same-length arrivals form ONE full batch (no padding);
    the wait-based flush never fires."""
    index, queries, _ = workload
    q = queries[0]
    metrics = obs.MetricsRegistry()
    with make_server(index, metrics=metrics, max_batch=SUBLANES,
                     max_wait_ms=10_000.0) as srv:
        futs = [srv.submit(q, k=1) for _ in range(SUBLANES)]
        resps = [f.result(timeout=WAIT) for f in futs]
    assert all(r.ok for r in resps)
    assert metrics.value("serve.batches") == 1
    assert metrics.value("serve.batch_rows_real") == SUBLANES
    assert metrics.value("serve.batch_rows_padded") == 0


def test_flush_on_max_wait(workload):
    """A lone straggler must come back in ~max_wait, not hang until the
    bucket fills."""
    index, queries, _ = workload
    metrics = obs.MetricsRegistry()
    with make_server(index, metrics=metrics, max_batch=64,
                     max_wait_ms=20.0) as srv:
        t0 = time.monotonic()
        resp = srv.submit(queries[0], k=1).result(timeout=WAIT)
        waited = time.monotonic() - t0
    assert resp.ok
    assert waited >= 0.015                # the policy really did wait
    assert metrics.value("serve.batches") == 1
    assert metrics.value("serve.batch_rows_padded") == SUBLANES - 1


# ------------------------------------------------------------- deadlines
def test_queued_deadline_timeout(workload):
    """A deadline expiring in the bucket produces a prompt, well-formed
    timeout response — and no sweep ever runs."""
    index, queries, _ = workload
    metrics = obs.MetricsRegistry()
    with make_server(index, metrics=metrics, max_batch=64,
                     max_wait_ms=10_000.0) as srv:
        t0 = time.monotonic()
        resp = srv.submit(queries[0], k=1,
                          deadline_ms=30.0).result(timeout=WAIT)
        waited = time.monotonic() - t0
    assert resp.status == "timeout" and not resp.ok
    assert resp.attempts == 0             # never reached a sweep
    assert resp.hits == ()
    assert waited < 5.0                   # prompt, not the 10s flush
    assert metrics.value("serve.timeouts") == 1
    assert metrics.value("serve.batches") == 0


def test_deadline_expired_during_sweep(workload):
    """A deadline that passes while the sweep is in flight still yields
    a timeout response (never stale 'ok' data after the deadline)."""
    index, queries, _ = workload
    metrics = obs.MetricsRegistry()
    with make_server(index, metrics=metrics, max_wait_ms=1.0,
                     fault_policy=FaultPolicy(latency_s=0.2)) as srv:
        resp = srv.submit(queries[0], k=1,
                          deadline_ms=60.0).result(timeout=WAIT)
    assert resp.status == "timeout"
    assert resp.attempts == 1             # the sweep DID run
    assert metrics.value("serve.timeouts") == 1


def test_default_deadline_applies(workload):
    index, queries, _ = workload
    with make_server(index, max_batch=64, max_wait_ms=10_000.0,
                     default_deadline_ms=30.0) as srv:
        resp = srv.submit(queries[0], k=1).result(timeout=WAIT)
    assert resp.status == "timeout"


# ---------------------------------------------------------- backpressure
def test_admission_rejects_when_full(workload):
    """Past max_queue waiting requests submit() raises RejectedError
    with a positive retry-after; earlier requests still complete."""
    index, queries, _ = workload
    metrics = obs.MetricsRegistry()
    with make_server(index, metrics=metrics, max_queue=4,
                     max_wait_ms=10_000.0, max_batch=64,
                     fault_policy=FaultPolicy(latency_s=0.3)) as srv:
        admitted = []
        rejected = 0
        for q in queries:
            try:
                admitted.append(srv.submit(q, k=1))
            except RejectedError as e:
                rejected += 1
                assert e.retry_after_s > 0
        assert rejected == len(queries) - 4
        assert metrics.value("serve.rejected") == rejected
        resps = [f.result(timeout=WAIT) for f in admitted]
    assert all(r.ok for r in resps)


# -------------------------------------------------------- fault tolerance
def test_retry_once_recovers(workload, offline_hits):
    index, queries, _ = workload
    metrics = obs.MetricsRegistry()
    policy = FaultPolicy(fail_first=1)    # first sweep attempt fails
    with make_server(index, metrics=metrics,
                     fault_policy=policy) as srv:
        resp = srv.submit(queries[0], k=2).result(timeout=WAIT)
    assert resp.ok and resp.attempts == 2
    assert_same_hits(resp.hits, offline_hits[0])
    assert metrics.value("serve.retries") == 1
    assert metrics.value("serve.errors") == 0


def test_retry_budget_exhausted_is_error(workload):
    """Two consecutive transient failures beat a retry budget of one:
    a well-formed error response, not a hang or a crashed worker."""
    index, queries, _ = workload
    metrics = obs.MetricsRegistry()
    policy = FaultPolicy(fail_first=2)
    with make_server(index, metrics=metrics, max_retries=1,
                     fault_policy=policy) as srv:
        resp = srv.submit(queries[0], k=1).result(timeout=WAIT)
        # the pool worker survived: a second request succeeds
        resp2 = srv.submit(queries[1], k=1).result(timeout=WAIT)
    assert resp.status == "error" and resp.error
    assert resp.attempts == 2
    assert resp2.ok
    assert metrics.value("serve.errors") == 1
    assert metrics.value("serve.retries") == 1


def test_fatal_fault_never_retried(workload):
    index, queries, _ = workload
    metrics = obs.MetricsRegistry()
    policy = FaultPolicy(fail_first=1, fatal=True)
    with make_server(index, metrics=metrics,
                     fault_policy=policy) as srv:
        resp = srv.submit(queries[0], k=1).result(timeout=WAIT)
    assert resp.status == "error"
    assert resp.attempts == 1
    assert metrics.value("serve.retries") == 0


# ------------------------------------------------------------- lifecycle
def test_drain_completes_admitted_work(workload, offline_hits):
    index, queries, _ = workload
    srv = make_server(index, max_batch=64, max_wait_ms=10_000.0)
    futs = [srv.submit(q, k=2) for q in queries]
    assert srv.drain(timeout=WAIT)        # flushes + finishes everything
    for fut, want in zip(futs, offline_hits):
        resp = fut.result(timeout=0)      # already resolved
        assert resp.ok
        assert_same_hits(resp.hits, want)
    with pytest.raises(ServerClosed):
        srv.submit(queries[0], k=1)
    srv.close()


def test_close_without_drain_cancels_queued(workload):
    index, queries, _ = workload
    metrics = obs.MetricsRegistry()
    srv = make_server(index, metrics=metrics, max_batch=64,
                      max_wait_ms=10_000.0)
    futs = [srv.submit(q, k=1) for q in queries]
    srv.close(drain=False, timeout=WAIT)
    resps = [f.result(timeout=WAIT) for f in futs]
    assert all(r.status == "cancelled" for r in resps)
    assert metrics.value("serve.cancelled") == len(queries)
    assert metrics.value("serve.queue_depth") == 0


def test_no_dropped_futures_under_concurrent_submit(workload):
    """Hammer submit from several threads while the loop flushes on a
    short wait: every admitted future resolves to a terminal status."""
    index, queries, _ = workload
    results, errs = [], []
    with make_server(index, max_wait_ms=2.0, workers=2,
                     max_queue=10_000) as srv:
        def client(seed):
            rng = np.random.default_rng(seed)
            for _ in range(10):
                q = queries[int(rng.integers(len(queries)))]
                results.append(srv.submit(q, k=1))
                time.sleep(float(rng.uniform(0, 0.002)))
        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    statuses = {f.result(timeout=WAIT).status for f in results}
    assert len(results) == 40
    assert statuses <= {"ok", "timeout", "cancelled"}
    assert "ok" in statuses


def test_submit_validation(workload):
    index, queries, _ = workload
    with make_server(index) as srv:
        with pytest.raises(ValueError):
            srv.submit(np.zeros((4, 4)), k=1)        # not 1-D
        with pytest.raises(ValueError):
            srv.submit(np.zeros((0,)), k=1)          # empty
        with pytest.raises(ValueError):
            srv.submit(queries[0], k=0)
        with pytest.raises(ValueError):
            srv.submit(queries[0], k=1, deadline_ms=0)


def test_stream_config_validation():
    with pytest.raises(ValueError):
        StreamConfig(max_batch=SUBLANES + 1)
    with pytest.raises(ValueError):
        StreamConfig(max_batch=0)
    with pytest.raises(ValueError):
        StreamConfig(max_wait_ms=-1.0)
    with pytest.raises(ValueError):
        StreamConfig(max_queue=0)
    with pytest.raises(ValueError):
        StreamConfig(workers=0)


def test_due_flushes_policy():
    oldest = {10: 0.0, 20: 5.0}
    due, wake = due_flushes(oldest, now=6.0, max_wait_s=2.0)
    assert due == [10]
    assert wake == 7.0                    # 5.0 + 2.0
    due, wake = due_flushes(oldest, now=8.0, max_wait_s=2.0)
    assert due == [10, 20] and wake is None
    due, wake = due_flushes({}, now=0.0, max_wait_s=2.0)
    assert due == [] and wake is None


@pytest.mark.parametrize("bad", [
    {"max_retries": -1},
    {"default_deadline_ms": 0.0},
    {"retry_after_ms": -5.0},
])
def test_stream_config_rejects(bad):
    with pytest.raises(ValueError, match=next(iter(bad))):
        StreamConfig(**bad)


def test_stream_config_seconds():
    """retry-after defaults to one batch-formation period."""
    cfg = StreamConfig(max_wait_ms=25.0)
    assert (cfg.max_wait_s, cfg.retry_after_s) == (0.025, 0.025)
    assert StreamConfig(max_wait_ms=25.0,
                        retry_after_ms=400.0).retry_after_s == 0.4


def test_due_flushes_at_the_deadline():
    """A bucket whose oldest request has waited exactly max_wait is
    due; with max_wait 0 every bucket is due at once."""
    due, wake = due_flushes({10: 0.0, 20: 1.0}, now=2.0, max_wait_s=2.0)
    assert due == [10] and wake == 3.0
    due, wake = due_flushes({30: 5.0, 10: 5.0}, now=5.0, max_wait_s=0.0)
    assert due == [10, 30] and wake is None


# ------------------------------------------------------------ session pool
def test_session_pool_exactly_once_callbacks(workload):
    index, queries, _ = workload
    pool = SessionPool(index, SearchConfig(max_slots=SUBLANES), size=2,
                       metrics=obs.MetricsRegistry(),
                       tracer=obs.Tracer())
    calls = []
    lock = threading.Lock()

    def cb(matches, error, attempts):
        with lock:
            calls.append((matches is not None, error, attempts))

    for _ in range(6):
        pool.submit(SweepBatch(queries=[queries[0]], k=1, on_result=cb))
    assert pool.join(timeout=WAIT)
    pool.close()
    assert len(calls) == 6
    assert all(ok and err is None and n == 1 for ok, err, n in calls)


def test_fault_policy_counts_attempts():
    policy = FaultPolicy(fail_first=2)
    with pytest.raises(TransientSweepError):
        policy.on_dispatch()
    with pytest.raises(TransientSweepError):
        policy.on_dispatch()
    policy.on_dispatch()                  # third attempt passes
    assert policy.attempts == 3


@pytest.mark.parametrize("bad", [{"fail_first": -1},
                                 {"latency_s": -0.1}])
def test_fault_policy_validation(bad):
    with pytest.raises(ValueError, match=next(iter(bad))):
        FaultPolicy(**bad)


def test_fault_policy_fail_when_schedule():
    """fail_when sees every attempt index, retries included."""
    policy = FaultPolicy(fail_when=lambda i: i % 2 == 1)
    outcomes = []
    for _ in range(5):
        try:
            policy.on_dispatch()
            outcomes.append("ok")
        except TransientSweepError:
            outcomes.append("fail")
    assert outcomes == ["ok", "fail", "ok", "fail", "ok"]
    assert policy.attempts == 5


def test_fault_policy_fatal_is_not_transient():
    policy = FaultPolicy(fail_first=1, fatal=True)
    with pytest.raises(RuntimeError) as err:
        policy.on_dispatch()
    assert not isinstance(err.value, TransientSweepError)
    policy.on_dispatch()


def test_fault_policy_stalls_every_attempt():
    policy = FaultPolicy(latency_s=0.05)
    t0 = time.perf_counter()
    policy.on_dispatch()
    policy.on_dispatch()
    assert time.perf_counter() - t0 >= 0.1


# ----------------------------------------- batcher streaming-admission hooks
def test_batcher_flush_bucket_and_inspection(workload):
    _, queries, _ = workload
    b = QueryBatcher(max_slots=SUBLANES)
    b.add("a", queries[0])
    b.add("b", queries[0])
    b.add("c", queries[2])                # different length bucket
    lengths = sorted({len(queries[0]), len(queries[2])})
    assert sorted(b.oldest_ids()) == lengths
    assert b.oldest_ids()[len(queries[0])] == "a"
    assert set(b.queued_ids()) == {"a", "b", "c"}
    batch = b.flush_bucket(len(queries[0]))
    assert batch is not None and batch.ids == ("a", "b")
    assert batch.queries.shape == (SUBLANES, len(queries[0]))
    assert b.pending() == 1               # "c" untouched
    assert b.flush_bucket(len(queries[0])) is None
    assert b.flush_bucket(999_999) is None


def test_batcher_evict(workload):
    _, queries, _ = workload
    b = QueryBatcher(max_slots=SUBLANES)
    for name in ("a", "b", "c"):
        b.add(name, queries[0])
    gone = b.evict(lambda qid: qid == "b")
    assert [qid for qid, _ in gone] == ["b"]
    assert b.queued_ids() == ["a", "c"]   # survivor order kept
    gone = b.evict(lambda qid: True)
    assert {qid for qid, _ in gone} == {"a", "c"}
    assert b.pending() == 0 and b.oldest_ids() == {}


def _reference_rows(ops, max_slots):
    """Oracle for the interleaving property: per-qid rows and batch
    grid shapes under the same op sequence, computed independently."""
    b = QueryBatcher(max_slots=max_slots)
    emitted = []
    for op in ops:
        if op[0] == "add":
            emitted += b.add(op[1], op[2])
        elif op[0] == "flush_bucket":
            batch = b.flush_bucket(op[1])
            emitted += [batch] if batch is not None else []
        else:
            emitted += b.flush()
    emitted += b.flush()
    return emitted


def _check_stream_invariants(ops, max_slots):
    """Any interleaving of add/flush_bucket/flush: every qid emitted
    exactly once, its row bitwise equal to its input, every batch on
    the SUBLANES x 2^k grid."""
    emitted = _reference_rows(ops, max_slots)
    adds = {op[1]: op[2] for op in ops if op[0] == "add"}
    seen = []
    for batch in emitted:
        g = batch.queries.shape[0]
        assert g == grid_size(batch.n_real, max_slots)
        assert g % SUBLANES == 0 and g >= SUBLANES
        # g is SUBLANES * 2**k
        assert (g // SUBLANES) & (g // SUBLANES - 1) == 0
        for row, qid in enumerate(batch.ids):
            np.testing.assert_array_equal(
                np.asarray(batch.queries[row]), np.asarray(adds[qid]))
            assert len(adds[qid]) == batch.length
        np.testing.assert_array_equal(
            np.asarray(batch.queries[batch.n_real:]), 0.0)
        seen += list(batch.ids)
    assert sorted(seen) == sorted(adds)   # exactly once, none dropped


def test_batcher_streaming_interleavings_seeded():
    """Deterministic fallback for the hypothesis property below: 200
    random interleavings of arrivals and flushes."""
    rng = np.random.default_rng(42)
    lengths = [12, 20]
    for trial in range(200):
        n_ops = int(rng.integers(1, 25))
        ops, qid = [], 0
        for _ in range(n_ops):
            r = rng.random()
            if r < 0.7:
                m = lengths[int(rng.integers(len(lengths)))]
                ops.append(("add", qid,
                            rng.standard_normal(m).astype(np.float32)))
                qid += 1
            elif r < 0.85:
                ops.append(("flush_bucket",
                            lengths[int(rng.integers(len(lengths)))]))
            else:
                ops.append(("flush",))
        _check_stream_invariants(ops, max_slots=SUBLANES * 2)


def test_batcher_streaming_property_hypothesis():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    lengths = [12, 20]
    op = st.one_of(
        st.tuples(st.just("add"), st.sampled_from(lengths),
                  st.integers(0, 2 ** 31 - 1)),
        st.tuples(st.just("flush_bucket"), st.sampled_from(lengths)),
        st.tuples(st.just("flush")))

    @settings(max_examples=60, deadline=None)
    @given(st.lists(op, max_size=30))
    def run(raw_ops):
        ops, qid = [], 0
        for o in raw_ops:
            if o[0] == "add":
                rng = np.random.default_rng(o[2])
                ops.append(("add", qid,
                            rng.standard_normal(o[1])
                               .astype(np.float32)))
                qid += 1
            else:
                ops.append(o)
        _check_stream_invariants(ops, max_slots=SUBLANES * 2)

    run()
