"""QueryBatcher — pack variable-count, variable-length query streams
into the paper's fixed kernel shapes.

The wavefront kernel (and the jit cache in front of every backend)
wants static shapes: a (B, M) block with B a SUBLANES multiple and one
compiled executable per distinct shape. Real search traffic is neither:
queries arrive one at a time with arbitrary lengths. The packer keeps
one open bucket per query length; a bucket emits a full batch the
moment all ``max_slots`` slots fill, and ``flush()`` drains
stragglers. Emitted
batches are zero-padded up to a small shape grid (SUBLANES x powers of
two, capped at ``max_slots``) so a long-running service compiles each
backend for only O(log(max_slots / SUBLANES)) batch shapes per length.

Padding is batch-dim only — query *rows* are never padded, because
sDTW aligns the whole query and extending it would change the cost.
Distinct lengths stay in distinct buckets; the ``[:n_real]`` trim drops
pad rows on the way out (a packing invariant under test).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core.spec import require_univariate
from repro.kernels.sdtw_wavefront import SUBLANES


def grid_size(n: int, max_slots: int) -> int:
    """Smallest SUBLANES * 2**k >= n, capped at max_slots."""
    if n > max_slots:
        raise ValueError(f"batch of {n} exceeds max_slots={max_slots}")
    g = SUBLANES
    while g < n:
        g *= 2
    return min(g, max_slots)


@dataclasses.dataclass
class QueryBatch:
    """One fixed-shape unit of kernel work."""
    length: int                 # M — query length of every real row
    ids: tuple                  # caller ids of the n_real leading rows
    queries: jnp.ndarray        # (B_grid, M); rows >= n_real are zeros

    @property
    def n_real(self) -> int:
        return len(self.ids)


class QueryBatcher:
    """Length-bucketed slot packer for a stream of 1-D queries.

    ``metrics``: optional :class:`repro.obs.MetricsRegistry` — every
    emitted batch records ``batcher.batches`` / ``batcher.rows_real`` /
    ``batcher.rows_padded`` counters and a ``batcher.fill`` histogram
    (real rows / grid rows), so bucket occupancy and padding waste are
    observable across a serving run instead of vanishing with the
    batcher object."""

    def __init__(self, *, max_slots: int = 64, metrics=None):
        if max_slots < SUBLANES or max_slots % SUBLANES:
            raise ValueError(
                f"max_slots must be a positive multiple of SUBLANES="
                f"{SUBLANES}, got {max_slots}")
        self.max_slots = max_slots
        self.metrics = metrics
        self._buckets: dict[int, list] = {}     # length -> [(id, series)]

    def add(self, qid, series) -> list[QueryBatch]:
        """Queue one query; returns the batches this fill completed
        (empty list until a bucket reaches max_slots)."""
        series = jnp.asarray(series)
        require_univariate(series, f"query {qid!r}")
        if series.shape[0] == 0:
            raise ValueError(f"query {qid!r} is empty")
        length = int(series.shape[0])
        bucket = self._buckets.setdefault(length, [])
        bucket.append((qid, series))
        if len(bucket) >= self.max_slots:
            self._buckets[length] = []
            return [self._emit(length, bucket)]
        return []

    def flush(self) -> list[QueryBatch]:
        """Emit every partially-filled bucket (grid-padded)."""
        out = [self._emit(length, bucket)
               for length, bucket in sorted(self._buckets.items()) if bucket]
        self._buckets = {}
        return out

    # ------------------------------------------------ streaming admission
    # Hooks for the streaming server (repro.serve.stream): the batcher
    # is its bucket store, so the server needs to flush ONE aged bucket
    # (not all of them), drop expired requests, and inspect bucket
    # heads to compute the next flush deadline.

    def flush_bucket(self, length: int) -> QueryBatch | None:
        """Emit one length's partially-filled bucket (grid-padded);
        None when that bucket is empty or unknown — the age-based
        flush of the streaming batch-formation policy."""
        bucket = self._buckets.pop(length, None)
        if not bucket:
            return None
        return self._emit(length, bucket)

    def evict(self, predicate) -> list[tuple]:
        """Remove (and return, as ``(qid, series)`` pairs) every queued
        entry whose ``predicate(qid)`` is true — how the streaming
        server strips deadline-expired requests out of open buckets
        without emitting them.  Arrival order of survivors is kept."""
        out = []
        for length in list(self._buckets):
            bucket = self._buckets[length]
            kept = [(qid, s) for qid, s in bucket if not predicate(qid)]
            if len(kept) != len(bucket):
                out += [(qid, s) for qid, s in bucket if predicate(qid)]
                if kept:
                    self._buckets[length] = kept
                else:
                    del self._buckets[length]
        return out

    def oldest_ids(self) -> dict[int, object]:
        """{length: qid of that bucket's oldest entry} — the inputs of
        the age-based flush decision (serve.policy.due_flushes)."""
        return {length: bucket[0][0]
                for length, bucket in self._buckets.items() if bucket}

    def queued_ids(self) -> list:
        """Every queued qid, bucket by bucket in arrival order."""
        return [qid for _, bucket in sorted(self._buckets.items())
                for qid, _ in bucket]

    def pack(self, queries, ids=None) -> list[QueryBatch]:
        """One-shot convenience: add all then flush."""
        out = []
        for i, q in enumerate(queries):
            out += self.add(ids[i] if ids is not None else i, q)
        return out + self.flush()

    def pending(self) -> int:
        return sum(len(b) for b in self._buckets.values())

    def _emit(self, length: int, bucket: list) -> QueryBatch:
        ids = tuple(qid for qid, _ in bucket)
        q = jnp.stack([s for _, s in bucket])
        g = grid_size(q.shape[0], self.max_slots)
        n_real = int(q.shape[0])
        q = jnp.pad(q, ((0, g - n_real), (0, 0)))
        if self.metrics is not None:
            self.metrics.inc("batcher.batches")
            self.metrics.inc("batcher.rows_real", n_real)
            if g > n_real:
                self.metrics.inc("batcher.rows_padded", g - n_real)
            self.metrics.observe("batcher.fill", n_real / g)
        return QueryBatch(length=length, ids=ids, queries=q)
