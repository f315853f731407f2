"""Search-service driver: stream query chunks against a registered
reference set and report throughput + cascade statistics.

CPU-scale usage (reduced workload):
  PYTHONPATH=src python -m repro.launch.search_serve --refs 8 \
      --queries 64 --chunk 16 --k 2
  PYTHONPATH=src python -m repro.launch.search_serve --backend kernel
  PYTHONPATH=src python -m repro.launch.search_serve --no-prune
  PYTHONPATH=src python -m repro.launch.search_serve --distance abs
  PYTHONPATH=src python -m repro.launch.search_serve --band 256
  PYTHONPATH=src python -m repro.launch.search_serve --no-windows
  PYTHONPATH=src python -m repro.launch.search_serve --reduction softmin \
      --gamma 1.0      # soft specs disable the (inadmissible) cascade
                       # and the (argmin-shaped) matched windows
  PYTHONPATH=src python -m repro.launch.search_serve --trace trace.json
      # Chrome trace (chrome://tracing / perfetto) of every cascade stage
  PYTHONPATH=src python -m repro.launch.search_serve --stream --rate 100
      # live-traffic mode: Poisson arrivals of SINGLE queries through
      # the StreamServer (continuous batching, deadlines, backpressure)
      # instead of pre-formed chunks; --max-wait-ms / --max-batch /
      # --workers / --deadline-ms expose the formation policy knobs

The driver builds the index once (normalized + cached layouts), then
drives the SearchService over arriving chunks the way a serving
frontend would.  Hits come back with their matched
reference window — ``track3[412..540]`` — not just a distance, unless
``--no-windows`` (or a soft-min spec) turns the start lanes off.

Per-chunk latency lands in a ``repro.obs`` histogram (reported as
p50/p95/p99 — tails matter for serving); cascade totals come from the
service's cumulative ``svc.stats`` after a post-warm-up reset.
"""

from __future__ import annotations

import argparse
import logging
import time

from repro import obs
from repro.core.spec import DISTANCES, REDUCTIONS, DPSpec
from repro.data.cbf import make_search_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.search import ReferenceIndex, SearchConfig, SearchService

log = logging.getLogger(__name__)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--refs", type=int, default=8)
    ap.add_argument("--motifs-per-ref", type=int, default=16)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--query-motifs", type=int, default=2)
    ap.add_argument("--chunk", type=int, default=16,
                    help="queries per arriving batch")
    ap.add_argument("--k", type=int, default=1)
    ap.add_argument("--backend", default="engine",
                    choices=["ref", "engine", "kernel", "soft", "quantized"])
    ap.add_argument("--distance", default="sqeuclidean", choices=DISTANCES)
    ap.add_argument("--reduction", default="hardmin", choices=REDUCTIONS)
    ap.add_argument("--gamma", type=float, default=1.0,
                    help="softmin temperature (reduction=softmin)")
    ap.add_argument("--band", type=int, default=None,
                    help="Sakoe-Chiba radius (default: unbanded)")
    ap.add_argument("--no-prune", action="store_true")
    ap.add_argument("--no-windows", action="store_true",
                    help="report distances only (matched windows are on "
                         "by default for hard-min specs)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace (.json) or JSONL (.jsonl) "
                         "of the serve loop's spans")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stream", action="store_true",
                    help="drive single-query Poisson arrivals through "
                         "the StreamServer instead of pre-formed chunks")
    ap.add_argument("--rate", type=float, default=100.0,
                    help="offered load in queries/second (--stream)")
    ap.add_argument("--max-batch", type=int, default=16,
                    help="formation grid cap, SUBLANES multiple "
                         "(--stream)")
    ap.add_argument("--max-wait-ms", type=float, default=10.0,
                    help="straggler flush deadline (--stream)")
    ap.add_argument("--workers", type=int, default=1,
                    help="session-pool sweep workers (--stream)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline; omit for none (--stream)")
    args = ap.parse_args(argv)
    obs.configure_logging()
    enable_compile_cache()

    spec = DPSpec(distance=args.distance, reduction=args.reduction,
                  gamma=args.gamma, band=args.band)
    # windows ride hard-min argmin pointers; soft-min specs (and the
    # quantized backend) fall back to distance-only hits
    from repro.backends import registry
    windows = (not args.no_windows and
               registry.supports(args.backend, spec,
                                 outputs=("cost", "start", "end")))
    refs, queries, labels = make_search_dataset(
        seed=args.seed, n_refs=args.refs,
        motifs_per_ref=args.motifs_per_ref, n_queries=args.queries,
        query_motifs=args.query_motifs)
    index = ReferenceIndex(spec=spec)
    for name, series in refs.items():
        index.add(name, series)
    search = SearchConfig(backend=args.backend,
                          prune=not args.no_prune, windows=windows)
    if args.stream:
        return _stream_main(args, index, search, queries, labels)
    svc = SearchService(index, search)

    n = len(queries)
    log.info("[search] %d refs x %d samples, %d queries arriving in "
             "chunks of %d, backend=%s, spec=%s, prune=%s, windows=%s",
             len(index), refs["track0"].shape[0], n, args.chunk,
             svc.backend.name, svc.spec.describe(), svc.prune_active,
             windows)
    svc.topk(queries[:args.chunk], k=args.k)      # warm-up compile
    svc.reset_stats()      # report steady state, not the compile chunk
    lat = obs.default_registry().histogram("serve.chunk_ms")
    hits = 0
    t0 = time.perf_counter()
    for lo in range(0, n, args.chunk):
        chunk = queries[lo:lo + args.chunk]
        t1 = time.perf_counter()
        matches = svc.topk(chunk, k=args.k)
        lat.record((time.perf_counter() - t1) * 1e3)
        hits += sum(m[0].reference == labels[lo + i]
                    for i, m in enumerate(matches))
    dt = time.perf_counter() - t0
    st = svc.stats        # cumulative across all chunks since reset
    print(f"[search] {n / dt:8.1f} q/s   top-1 hit-rate {hits / n:.0%}   "
          f"sweeps {st.dp_pairs}/{st.pairs} "
          f"(skipped {st.skipped / max(st.pairs, 1):.0%})")
    print(f"[search] chunk latency ms: p50 {lat.quantile(0.5):.2f}  "
          f"p95 {lat.quantile(0.95):.2f}  p99 {lat.quantile(0.99):.2f}  "
          f"over {lat.count} chunks   bound {st.bound_s * 1e3:.1f} ms / "
          f"sweep {st.sweep_s * 1e3:.1f} ms   "
          f"padding waste {st.padding_waste:.0%}")
    for i, m in enumerate(svc.topk(queries[:3], k=args.k)):
        best = ", ".join(
            (f"{x.reference}[{x.start}..{x.end}] cost={x.cost:.3f}"
             if x.start is not None else
             f"{x.reference}@{x.end} cost={x.cost:.3f}")
            for x in m)
        print(f"  q{i} ({labels[i]}): {best}")
    if args.trace:
        path = obs.save_trace(args.trace)
        print(f"[search] trace -> {path}")


def _stream_main(args, index, search, queries, labels):
    """--stream: single-query Poisson arrivals through the StreamServer."""
    import numpy as np

    from repro.serve import RejectedError, StreamConfig, StreamServer

    config = StreamConfig(max_batch=args.max_batch,
                          max_wait_ms=args.max_wait_ms,
                          workers=args.workers,
                          default_deadline_ms=args.deadline_ms)
    rng = np.random.default_rng(args.seed)
    gaps = rng.exponential(1.0 / args.rate, size=len(queries))
    with StreamServer(index, config=config, search=search) as srv:
        srv.warmup(sorted({len(q) for q in queries}), k=args.k)
        log.info("[stream] %d queries at %.0f q/s offered, max_batch=%d "
                 "max_wait=%.1fms workers=%d deadline=%s", len(queries),
                 args.rate, args.max_batch, args.max_wait_ms,
                 args.workers, args.deadline_ms)
        futures, rejects = [], 0
        t0 = time.perf_counter()
        for i, q in enumerate(queries):
            try:
                futures.append((i, srv.submit(q, k=args.k)))
            except RejectedError as e:
                rejects += 1
                time.sleep(e.retry_after_s)
            time.sleep(float(gaps[i]))
        responses = [(i, f.result(timeout=120.0)) for i, f in futures]
        dt = time.perf_counter() - t0
    ok = [(i, r) for i, r in responses if r.ok]
    timeouts = sum(1 for _, r in responses if r.status == "timeout")
    lat = sorted(r.latency_ms for _, r in ok)

    def pct(p):
        return lat[min(int(p * len(lat)), len(lat) - 1)] if lat else 0.0

    hits = sum(r.hits[0].reference == labels[i] for i, r in ok)
    print(f"[stream] offered {args.rate:.0f} q/s   goodput "
          f"{len(ok) / dt:8.1f} q/s   top-1 hit-rate "
          f"{hits / max(len(ok), 1):.0%}   timeouts {timeouts}   "
          f"rejects {rejects}")
    print(f"[stream] request latency ms: p50 {pct(0.50):.2f}  "
          f"p95 {pct(0.95):.2f}  p99 {pct(0.99):.2f}  over "
          f"{len(ok)} ok responses")
    for i, r in [x for x in ok[:3]]:
        best = ", ".join(
            (f"{x.reference}[{x.start}..{x.end}] cost={x.cost:.3f}"
             if x.start is not None else
             f"{x.reference}@{x.end} cost={x.cost:.3f}")
            for x in r.hits)
        print(f"  q{i} ({labels[i]}): {best}")
    if args.trace:
        path = obs.save_trace(args.trace)
        print(f"[stream] trace -> {path}")


if __name__ == "__main__":
    main()
