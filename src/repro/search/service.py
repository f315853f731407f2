"""SearchService — exact top-k subsequence search over many references.

Per query batch the service runs a three-layer cascade:

  1. **bound** — admissible lower bounds (prune.py) of every
     (query, reference) pair from cached reference envelopes: the query
     stays full-resolution (coarsening it collapses the bound — the
     noise accumulation that dominates real sweep costs lives in the
     per-row terms) while the reference is PAA-coarsened, so a bound at
     ref_chunk c costs roughly 1/c of a full sweep;
  2. **order** — per query, references are visited best-bound-first, so
     the running top-k threshold tightens as early as possible, and
     progressively tighter (costlier) bound stages run only on pairs
     the coarse stage failed to prune;
  3. **sweep** — surviving pairs reach a full DP sweep, packed into
     fixed kernel shapes by the QueryBatcher and dispatched through the
     selected backend (the kernel path reuses the index's cached
     swizzled layouts).

Skipping is *exact*: a pair is discarded only when a true lower bound
strictly exceeds the k-th best true cost found so far, so ``topk``
returns results identical to a brute-force ``repro.sdtw`` loop over
every registered reference (same costs and end indices, any backend).
Ties break by registration order, matching the brute-force iteration.

The recurrence itself is a ``DPSpec`` (``config.spec``, falling back to
the index's default): top-k search runs banded and under any distance /
reduction the chosen backend supports.  The pruning cascade only
engages for specs whose bounds are admissible
(:func:`repro.search.prune.prune_admissible` — hard-min with a
gap-monotone distance, or cosine via the angular envelope bound); for
soft-min specs the service transparently falls back to full sweeps,
still exact for the spec'd recurrence.

``SearchConfig.windows`` returns the matched (start, end) window with
every hit — the start pointers ride the sweeps' existing carries
(``repro.align``), so windowed search costs one extra int lane, not a
second pass.  ``SearchConfig.options`` forwards backend extras into
every dispatch; ``{"mesh": Mesh(...)}`` fans the full sweeps across a
device mesh through the distributed backend.

Since the request/result front door, the service is a consumer of the
typed API: every shared-reference sweep goes through a precompiled
:class:`repro.Aligner` session (one per registered reference — the
reference stays pre-normalized, kernel layouts come from the index's
cache, and each (batch shape, outputs) pair compiles exactly once
across all topk() calls), every dispatch yields an
:class:`~repro.core.result.SDTWResult`, and ``brute_force_topk``
mirrors the same sessions so "identical to brute force" stays
bit-for-bit by construction.
"""

from __future__ import annotations

import bisect
import dataclasses
import logging
import time

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.backends import registry
from repro.core.api import sdtw
from repro.core.normalize import normalize_batch
from repro.core.result import SDTWResult, sweep_outputs
from repro.core.session import Aligner
from repro.core.spec import NO_WINDOW, DPSpec, validate_query_list
from repro.kernels import ops as _ops
from repro.kernels.ops import ceil_to
from repro.kernels.sdtw_wavefront import SUBLANES
from repro.search.batcher import QueryBatcher, grid_size
from repro.search.index import ReferenceIndex
from repro.search.prune import (lb_keogh_sdtw, lb_keogh_sdtw_multi,
                                prune_admissible)

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    backend: str = "engine"          # any registry backend or alias
    spec: DPSpec | None = None       # recurrence; None = the index's spec
    segment_width: int | str = 8     # kernel backend only; "auto" defers
    #                                  to repro.tune per reference — the
    #                                  per-reference Aligner sessions tune
    #                                  (or hit the persistent cache) on
    #                                  first sweep and every session
    #                                  shares the index's layout dicts
    interpret: bool | None = None    # kernel backend only (None = auto)
    normalize: bool = True           # must match the index's setting
    windows: bool = False            # return matched (start, end) windows
    #                                  with every hit (window-capable
    #                                  backends + hard-min specs only;
    #                                  validated at construction)
    options: dict | None = None      # backend extras forwarded into every
    #                                  ExecutionPlan — {"mesh": Mesh(...)}
    #                                  routes sweeps through the
    #                                  distributed backend's shard_map
    #                                  pipeline (plus optional
    #                                  "row_block", "batch_axes",
    #                                  "ref_axis")
    prune: bool = True
    stages: tuple = (4, 2)           # ref_chunk per cascade stage, coarse
    #                                  to fine; stage 0 runs batched over
    #                                  all pairs, later stages run per
    #                                  round just before a sweep
    probe_rounds: int = 2            # rounds that sweep ONE reference per
    #                                  query (tightening the threshold at
    #                                  minimum cost) before the remaining
    #                                  survivors are swept all at once
    prune_margin: float = 1e-4       # bounds and sweeps run in f32 with
    #                                  different summation orders; prune
    #                                  only when lb > theta + margin so
    #                                  rounding near a tie can never evict
    #                                  a pair brute force would keep
    max_slots: int = 64              # kernel-batch slot cap


@dataclasses.dataclass
class Match:
    reference: str
    cost: float
    end: int
    start: int | None = None         # matched-window start column — only
    #                                  populated when SearchConfig.windows

    @property
    def window(self) -> tuple[int, int] | None:
        """Inclusive (start, end) reference window, None without
        ``SearchConfig.windows``."""
        return None if self.start is None else (self.start, self.end)


@dataclasses.dataclass
class SearchStats:
    """Cascade accounting (benchmarked in
    benchmarks/search_throughput.py).

    ``SearchService.stats`` is CUMULATIVE over the service's lifetime —
    it is merged into, never silently replaced — and
    ``SearchService.last`` holds the per-call snapshot of the most
    recent ``topk()``.  Poking fields from outside the service is
    deprecated: every field is mirrored into the service's
    :class:`~repro.obs.MetricsRegistry` under ``search.*``, which is
    the supported way to consume (and export) these numbers.
    """
    pairs: int = 0                   # queries x references
    dp_pairs: int = 0                # pairs that reached a full sweep
    pruned_stage0: int = 0           # discarded on the coarse batched bound
    pruned_later: int = 0            # discarded on a tighter lazy stage
    dp_calls: int = 0                # backend dispatches (batched)
    kernel_blocks_run: int = 0       # kernel grid steps actually executed
    kernel_blocks_total: int = 0     # grid steps a full (unskipped) grid
    #                                  would have executed — banded specs
    #                                  pick the band-skip KernelPlan, so
    #                                  run < total for tight bands
    topk_calls: int = 0              # topk() invocations folded in here
    bound_s: float = 0.0             # wall-clock in the pruning cascade
    sweep_s: float = 0.0             # wall-clock in full DP sweeps
    sweep_rows: int = 0              # dispatched batch rows incl. padding
    sweep_rows_real: int = 0         # ... of which carried a real query

    @property
    def skipped(self) -> int:
        return self.pruned_stage0 + self.pruned_later

    @property
    def skip_fraction(self) -> float:
        return self.skipped / self.pairs if self.pairs else 0.0

    @property
    def kernel_blocks_skipped(self) -> int:
        return self.kernel_blocks_total - self.kernel_blocks_run

    @property
    def padding_waste(self) -> float:
        """Fraction of dispatched batch rows that were grid padding."""
        if not self.sweep_rows:
            return 0.0
        return 1.0 - self.sweep_rows_real / self.sweep_rows

    def merge(self, other: "SearchStats") -> "SearchStats":
        """Fold another stats block into this one (field-wise sum)."""
        for f in dataclasses.fields(self):
            setattr(self, f.name,
                    getattr(self, f.name) + getattr(other, f.name))
        return self

    def as_dict(self) -> dict:
        out = dataclasses.asdict(self)
        out.update(skipped=self.skipped, skip_fraction=self.skip_fraction,
                   padding_waste=self.padding_waste)
        return out


class SearchService:
    def __init__(self, index: ReferenceIndex,
                 config: SearchConfig = SearchConfig(), *,
                 metrics: obs.MetricsRegistry | None = None,
                 tracer: obs.Tracer | None = None):
        if index.normalize != config.normalize:
            raise ValueError(
                f"index.normalize={index.normalize} != "
                f"config.normalize={config.normalize}: bounds and sweeps "
                f"must run on identically-prepared series")
        if config.prune and not config.stages:
            raise ValueError("prune=True needs at least one cascade stage")
        self.index = index
        self.config = config
        # resolve the recurrence + backend ONCE: alias expansion and
        # capability validation (windows included) fail fast here, not
        # mid-search
        spec = config.spec if config.spec is not None else index.spec
        self._outputs = sweep_outputs(
            ("cost", "start", "end") if config.windows
            else ("cost", "end"))
        self.backend, self.spec = registry.resolve(
            config.backend, spec, outputs=self._outputs)
        # one precompiled Aligner session per reference for the
        # shared-reference sweeps (kernel / quantized / distributed):
        # pre-normalized series, index-cached kernel layouts, and
        # per-(batch shape, outputs) executables that persist across
        # topk() calls
        self._aligners: dict[str, Aligner] = {}
        if self.backend.name == "distributed" and \
                (config.options or {}).get("mesh") is None:
            raise ValueError(
                "the distributed backend needs a mesh: pass "
                "SearchConfig(options={'mesh': Mesh(...)}) (plus "
                "optional 'row_block', 'batch_axes', 'ref_axis')")
        # the cascade's bounds are lower bounds of the EXACT spec'd
        # sweep, and only for hard-min, gap-monotone specs; approximate
        # backends (quantized) or other specs fall back to full sweeps
        self.prune_active = (config.prune and prune_admissible(self.spec)
                             and self.backend.capabilities.exact)
        # ``stats`` accumulates for the life of the service; ``last``
        # is the per-call snapshot of the most recent topk()
        self.stats = SearchStats()
        self.last = SearchStats()
        self._cur = self.last
        self._metrics = obs.default_registry() if metrics is None else \
            metrics
        self._tracer = obs.default_tracer() if tracer is None else tracer

    def reset_stats(self) -> None:
        """Zero the cumulative accounting (e.g. after warm-up) —
        explicit, never implicit: ``topk()`` only ever merges."""
        self.stats = SearchStats()
        self.last = SearchStats()

    def warmup(self, m: int, batch: int = SUBLANES, k: int = 1) -> None:
        """Precompile the sweep executables a (batch, m) query workload
        would use: one seeded synthetic ``topk`` through the real path,
        so a serving frontend (``repro.serve``) pays trace+compile
        before live traffic instead of inside a request's latency
        budget.  Results are discarded; stats/metrics tick as usual
        (call :meth:`reset_stats` afterwards for clean accounting)."""
        rng = np.random.default_rng(0)
        q = rng.standard_normal((int(batch), int(m))).astype(np.float32)
        self.topk(list(q), k=k)

    # ------------------------------------------------------------ topk
    def topk(self, queries, k: int = 1) -> list[list[Match]]:
        """queries: (B, M) array or sequence of 1-D arrays (any lengths).
        Returns, per query, the k best (reference, cost, end) matches
        ordered by (cost, registration order).

        Accounting: the call's own numbers land in ``self.last`` and are
        merged into the cumulative ``self.stats``; both are mirrored
        into obs counters/gauges (``search.*``) plus a ``search.topk_ms``
        latency histogram, and the whole call runs inside a
        ``search.topk`` span with per-stage child spans."""
        refs = self.index.references()
        if not refs:
            raise ValueError("no references registered")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        qlist = self._as_query_list(queries)
        B, R = len(qlist), len(refs)
        st = self._cur = SearchStats(pairs=B * R, topk_calls=1)
        t0 = time.perf_counter()
        with self._tracer.span("search.topk", queries=B, refs=R, k=k,
                               backend=self.backend.name):
            out = self._topk_impl(qlist, refs, k)
        self.last = st
        self.stats.merge(st)
        self._publish(st, time.perf_counter() - t0)
        return out

    def _topk_impl(self, qlist, refs, k: int) -> list[list[Match]]:
        cfg = self.config
        st = self._cur
        B, R = len(qlist), len(refs)

        # --- stage 0: batched coarse bounds for every (query, ref) pair,
        # queries packed into the sweeps' fixed shapes and equal-length
        # reference envelopes stacked into one fan-out dispatch
        lb0 = np.zeros((B, R))
        if self.prune_active:
            tb = time.perf_counter()
            with self._tracer.span("search.bound0", pairs=B * R):
                by_nc: dict[int, list[int]] = {}
                envs = {}
                for j, e in enumerate(refs):
                    envs[j] = self.index.envelopes(e.name, cfg.stages[0])
                    by_nc.setdefault(int(envs[j][0].shape[0]),
                                     []).append(j)
                stacked = {nc: (jnp.stack([envs[j][0] for j in refidx]),
                                jnp.stack([envs[j][1] for j in refidx]))
                           for nc, refidx in by_nc.items()}
                batcher = QueryBatcher(max_slots=cfg.max_slots)
                for batch in batcher.pack(qlist):
                    for nc, refidx in by_nc.items():
                        rlo, rhi = stacked[nc]
                        vals = np.asarray(lb_keogh_sdtw_multi(
                            batch.queries, rlo, rhi, spec=self.spec))
                        lb0[np.ix_(list(batch.ids), refidx)] = \
                            vals[:batch.n_real]
            st.bound_s += time.perf_counter() - tb

        # --- per-query pending references, best-bound-first
        if self.prune_active:
            pending = [list(np.argsort(lb0[i], kind="stable"))
                       for i in range(B)]
        else:
            pending = [list(range(R)) for _ in range(B)]
        # found[i]: (cost, order, end, name) tuples kept SORTED via
        # bisect.insort so the k-th best is an O(1) read — brute-force-
        # equal tie-breaking falls out of the (cost, order) tuple order
        found: list[list[tuple]] = [[] for _ in range(B)]

        def threshold(i: int) -> float:
            if len(found[i]) < k:
                return np.inf
            return found[i][k - 1][0]

        rounds = 0
        while True:
            # each round: every unfinished query nominates its next
            # (best-bound-first) reference — one per query in the probe
            # rounds (a full sweep has a large flat dispatch cost, so the
            # threshold is tightened on as few dispatches as possible),
            # then everything still unpruned at once.  Nominations are
            # pruned by the tighter cascade stages, then swept grouped so
            # the backend stays saturated with batched fixed-shape work.
            nominations: dict[int, list[int]] = {}   # ref idx -> query ids
            for i in range(B):
                while pending[i]:
                    j = pending[i][0]
                    if self.prune_active and lb0[i, j] > threshold(i) + \
                            cfg.prune_margin:
                        # pending is sorted by lb0: everything left prunes
                        st.pruned_stage0 += len(pending[i])
                        pending[i] = []
                        break
                    pending[i].pop(0)
                    nominations.setdefault(j, []).append(i)
                    if rounds < cfg.probe_rounds:
                        break
            rounds += 1
            if not nominations:
                break
            if self.prune_active:
                nominations = self._later_stages(nominations, refs, qlist,
                                                 threshold)
            if not self.backend.capabilities.per_query_reference:
                # backends whose semantics need ONE reference per
                # dispatch (kernel: one shared pre-swizzled layout;
                # quantized: the codebook is built from the reference;
                # distributed: the reference is sharded over the mesh)
                # — each runs through its reference's Aligner session
                for j, qids in sorted(nominations.items()):
                    self._sweep_session(refs[j], j, qids, qlist, found)
            else:
                self._sweep_pairs(nominations, refs, qlist, found)

        out = []
        for i in range(B):
            out.append([Match(reference=name, cost=cost, end=end,
                              start=(start if cfg.windows else None))
                        for cost, _, end, name, start in found[i][:k]])
        return out

    def _publish(self, st: SearchStats, seconds: float) -> None:
        """Mirror one call's stats into the obs registry: counters
        accumulate, gauges hold the latest ratios, and the latency
        histogram feeds p50/p99 (``search.topk_ms``)."""
        m = self._metrics
        m.inc("search.topk_calls")
        for name in ("pairs", "dp_pairs", "pruned_stage0", "pruned_later",
                     "dp_calls", "kernel_blocks_run", "kernel_blocks_total",
                     "sweep_rows", "sweep_rows_real"):
            n = getattr(st, name)
            if n:
                m.inc(f"search.{name}", n)
        m.set_gauge("search.skip_fraction", st.skip_fraction)
        m.set_gauge("search.padding_waste", st.padding_waste)
        m.set_gauge("search.bound_vs_sweep",
                    st.bound_s / st.sweep_s if st.sweep_s else 0.0)
        m.observe("search.topk_ms", seconds * 1e3)
        m.observe("search.bound_ms", st.bound_s * 1e3)
        m.observe("search.sweep_ms", st.sweep_s * 1e3)
        log.debug("topk: %.1fms  pairs=%d swept=%d skipped=%d (%.0f%%)  "
                  "bound/sweep=%.3fs/%.3fs  padding=%.0f%%",
                  seconds * 1e3, st.pairs, st.dp_pairs, st.skipped,
                  100 * st.skip_fraction, st.bound_s, st.sweep_s,
                  100 * st.padding_waste)

    # ---------------------------------------------------------- cascade
    def _later_stages(self, nominations, refs, qlist, threshold):
        """Tighter (costlier) bound stages over one round's nominations,
        batched per reference through the same fixed-shape packer the
        sweeps use. A pruned query simply re-nominates next round."""
        cfg = self.config
        st = self._cur
        tb = time.perf_counter()
        with self._tracer.span("search.cascade",
                               stages=list(cfg.stages[1:])):
            for chunk in cfg.stages[1:]:
                survivors: dict[int, list[int]] = {}
                for j, qids in nominations.items():
                    qids = [i for i in qids if threshold(i) < np.inf]
                    cheap = [i for i in nominations[j] if i not in qids]
                    if cheap:   # nothing found yet: no threshold to beat
                        survivors.setdefault(j, []).extend(cheap)
                    if not qids:
                        continue
                    rlo, rhi = self.index.envelopes(refs[j].name, chunk)
                    batcher = QueryBatcher(max_slots=cfg.max_slots)
                    for batch in batcher.pack([qlist[i] for i in qids],
                                              ids=qids):
                        vals = np.asarray(lb_keogh_sdtw(
                            batch.queries, rlo, rhi, spec=self.spec))
                        for row, i in enumerate(batch.ids):
                            if vals[row] > threshold(i) + cfg.prune_margin:
                                st.pruned_later += 1
                            else:
                                survivors.setdefault(j, []).append(i)
                nominations = survivors
        st.bound_s += time.perf_counter() - tb
        return nominations

    # ----------------------------------------------------------- sweeps
    def sessions(self) -> list[Aligner]:
        """The per-reference sweep sessions built so far."""
        return list(self._aligners.values())

    def _aligner(self, entry) -> Aligner:
        """The reference's precompiled session (built on first sweep).

        ``normalize=False``: the index already normalized the series
        and ``_as_query_list`` normalizes queries, so the session's
        executables contain exactly the sweep — results stay
        bit-identical to the eager dispatch path.  ``layout_cache``
        shares the index entry's swizzled-layout dict, so the kernel's
        offline reference prep is paid once per (reference, width),
        wherever it happens first.
        """
        a = self._aligners.get(entry.name)
        if a is None:
            cfg = self.config
            a = self._aligners[entry.name] = Aligner(
                entry.series, spec=self.spec, backend=self.backend.name,
                normalize=False, segment_width=cfg.segment_width,
                interpret=cfg.interpret, options=cfg.options,
                layout_cache=entry.layouts)
        return a

    def _sweep_session(self, entry, order: int, qids: list[int], qlist,
                       found):
        """Full sweep of the nominated queries against ONE shared
        reference through its Aligner session, packed into fixed shapes
        by the QueryBatcher.  Banded kernel specs automatically execute
        the band-skip KernelPlan — trailing fully-out-of-band reference
        blocks are dropped from the pallas grid itself
        (``stats.kernel_blocks_run`` vs ``kernel_blocks_total``)."""
        cfg = self.config
        st = self._cur
        aligner = self._aligner(entry)
        batcher = QueryBatcher(max_slots=cfg.max_slots,
                               metrics=self._metrics)
        ts = time.perf_counter()
        with self._tracer.span("search.sweep", ref=entry.name,
                               queries=len(qids)) as sp:
            for batch in batcher.pack([qlist[i] for i in qids], ids=qids):
                res = aligner.align(batch.queries, outputs=self._outputs)
                sp.sync(res)
                if self.backend.name == "kernel":
                    blocked = self.spec.band is not None and \
                        batch.length - 1 - self.spec.band > entry.length - 1
                    if not blocked:   # blocked bands short-circuit in ops:
                        #             no pallas grid ran, no steps to count
                        plan = _ops.kernel_plan(
                            self.spec, m=batch.length, n=entry.length,
                            segment_width=aligner.resolved_width(
                                batch.queries.shape, self._outputs),
                            with_window=cfg.windows)
                        grid_groups = ceil_to(batch.queries.shape[0],
                                              SUBLANES) // SUBLANES
                        st.kernel_blocks_run += \
                            grid_groups * plan.grid_blocks
                        st.kernel_blocks_total += \
                            grid_groups * plan.num_ref_blocks
                self._record(res, batch.ids, order, entry.name, found)
                st.dp_pairs += batch.n_real
                st.dp_calls += 1
                st.sweep_rows += int(batch.queries.shape[0])
                st.sweep_rows_real += batch.n_real
        st.sweep_s += time.perf_counter() - ts

    def _sweep_pairs(self, nominations: dict, refs, qlist, found):
        """Full DP of one round's (query, reference) pairs for backends
        with per-row reference batching: all pairs with the same (query
        length, reference length) go in ONE stacked call, so a round
        costs O(distinct shapes) dispatches, not O(refs)."""
        cfg = self.config
        st = self._cur
        shapes: dict[tuple, list[tuple]] = {}    # (M, N) -> [(i, j)]
        for j, qids in sorted(nominations.items()):
            for i in qids:
                key = (int(qlist[i].shape[0]), refs[j].length)
                shapes.setdefault(key, []).append((i, j))
        ts = time.perf_counter()
        with self._tracer.span("search.sweep",
                               shapes=len(shapes)) as sp:
            for (m, n), pairs in shapes.items():
                qg = jnp.stack([qlist[i] for i, _ in pairs])
                rg = jnp.stack([refs[j].series for _, j in pairs])
                p = len(pairs)
                g = (grid_size(p, cfg.max_slots) if p <= cfg.max_slots
                     else ceil_to(p, SUBLANES))
                qg = jnp.pad(qg, ((0, g - p), (0, 0)))
                rg = jnp.concatenate(
                    [rg, jnp.broadcast_to(rg[:1], (g - p, n))]) \
                    if g > p else rg
                plan = registry.ExecutionPlan(
                    queries=qg, reference=rg,
                    segment_width=cfg.segment_width,
                    interpret=cfg.interpret,
                    outputs=self._outputs, options=cfg.options)
                res = self.backend.execute(self.spec, plan)
                sp.sync(res)
                self._record(res, [i for i, _ in pairs],
                             [j for _, j in pairs],
                             [refs[j].name for _, j in pairs], found)
                st.dp_pairs += p
                st.dp_calls += 1
                st.sweep_rows += g
                st.sweep_rows_real += p
        st.sweep_s += time.perf_counter() - ts

    def _record(self, res: SDTWResult, qids, order, name, found):
        """Fold one dispatch's :class:`SDTWResult` into the per-query
        top-k lists.

        ``res.start`` is populated exactly when ``SearchConfig.windows``
        asked for it; any batch-padding rows beyond ``len(qids)`` are
        ignored.  ``order``/``name`` are scalars for shared-reference
        sweeps or per-row sequences for pair sweeps.  The sort key
        stays (cost, order, end, name): the start column rides behind
        and never changes the ranking."""
        costs = np.asarray(res.cost)
        ends = np.asarray(res.end)
        starts = np.asarray(res.start) if res.start is not None else None
        scalar = not isinstance(order, (list, tuple))
        for row, i in enumerate(qids):
            bisect.insort(found[i], (
                float(costs[row]),
                order if scalar else order[row],
                int(ends[row]),
                name if scalar else name[row],
                int(starts[row]) if starts is not None else NO_WINDOW))

    # ------------------------------------------------------------ misc
    def _as_query_list(self, queries) -> list[jnp.ndarray]:
        if getattr(queries, "ndim", None) == 2:
            qs = list(jnp.asarray(queries))
        else:
            qs = [jnp.asarray(q) for q in queries]
        validate_query_list(qs)              # shared contract (core.spec)
        if self.config.normalize:
            qs = [normalize_batch(q) for q in qs]
        return qs


def brute_force_topk(index: ReferenceIndex, queries, k: int = 1, *,
                     backend: str = "engine", spec: DPSpec | None = None,
                     segment_width: int | str = 8,
                     interpret: bool | None = None,
                     windows: bool = False,
                     options: dict | None = None) -> list[list[Match]]:
    """Reference implementation: full DP of every query against every
    registered reference — what SearchService.topk must reproduce
    (windows included when ``windows=True``).

    Shared-reference backends (kernel / quantized / distributed) run
    through the same per-reference Aligner sessions the service uses,
    so the two paths execute literally the same compiled sweeps."""
    svc = SearchService(index, SearchConfig(
        backend=backend, spec=spec, normalize=index.normalize, prune=False,
        segment_width=segment_width, interpret=interpret,
        windows=windows, options=options))
    qs = svc._as_query_list(queries)
    groups: dict[int, list[int]] = {}
    for i, q in enumerate(qs):
        groups.setdefault(int(q.shape[0]), []).append(i)
    found: list[list[tuple]] = [[] for _ in qs]
    shared_ref = not svc.backend.capabilities.per_query_reference
    for length, qids in groups.items():
        qg = jnp.stack([qs[i] for i in qids])
        for order, e in enumerate(index.references()):
            if shared_ref:
                res = svc._aligner(e).align(qg, outputs=svc._outputs)
            else:
                res = sdtw(qg, e.series, outputs=svc._outputs,
                           normalize=False, backend=svc.backend.name,
                           spec=svc.spec, segment_width=segment_width,
                           interpret=interpret, options=options)
            costs, ends = np.asarray(res.cost), np.asarray(res.end)
            starts = (np.asarray(res.start) if res.start is not None
                      else None)
            for row, i in enumerate(qids):
                found[i].append((
                    float(costs[row]), order, int(ends[row]), e.name,
                    int(starts[row]) if starts is not None else NO_WINDOW))
    return [[Match(reference=name, cost=cost, end=end,
                   start=(start if windows else None))
             for cost, _, end, name, start in sorted(f)[:k]]
            for f in found]
