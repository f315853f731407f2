"""repro.serve — the streaming search service.

The live-traffic layer over the repo's fast primitives: a
:class:`StreamServer` admits ragged query arrivals onto the
SUBLANES x 2^k bucket grid (flush on bucket-full OR max-wait, whichever
first), dispatches formed batches through a fault-tolerant
:class:`SessionPool` of precompiled ``SearchService`` workers, and
resolves per-request futures with :class:`ServeResponse`\\ s whose hits
are bit-identical to offline ``SearchService.topk``.  Robustness —
per-request deadlines, bounded admission with retry-after backpressure,
retry-once on transient sweep failure, graceful drain — is part of the
contract and under test (``tests/test_stream_serve.py``); the load
profile is benchmarked closed-loop under seeded Poisson arrivals
(``benchmarks/serve_stream.py``).

    from repro.search import ReferenceIndex
    from repro.serve import StreamServer, StreamConfig

    index = ReferenceIndex()
    index.add("track0", series)
    with StreamServer(index, config=StreamConfig(max_wait_ms=5)) as srv:
        fut = srv.submit(query, k=3, deadline_ms=100)
        resp = fut.result()          # ServeResponse(status="ok", hits=...)
"""

from repro.serve.faults import FaultPolicy, TransientSweepError
from repro.serve.policy import StreamConfig, due_flushes
from repro.serve.pool import SessionPool, SweepBatch
from repro.serve.stream import (RejectedError, ServeResponse,
                                ServerClosed, StreamServer)

__all__ = [
    "FaultPolicy",
    "RejectedError",
    "ServeResponse",
    "ServerClosed",
    "SessionPool",
    "StreamConfig",
    "StreamServer",
    "SweepBatch",
    "TransientSweepError",
    "due_flushes",
]
