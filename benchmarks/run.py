"""Benchmark driver: one bench per paper table/figure + framework-level
benches.  Every bench's rows are written both to the combined
benchmarks/out/results.csv and to a schema-versioned, per-bench
``BENCH_<name>.json`` (see benchmarks/common.py) that
``launch/report.py --compare`` diffs for regressions.

  python -m benchmarks.run            # reduced CPU workloads
  python -m benchmarks.run --full     # paper's exact sizes (slow on CPU)
  python -m benchmarks.run --ci       # tiny shapes; asserts + validates
                                      # every emitted BENCH_*.json
"""

from __future__ import annotations

import argparse
import csv
import os

from benchmarks import common


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--ci", action="store_true",
                    help="tiny shapes, hard asserts, schema-validate "
                         "every BENCH_*.json")
    ap.add_argument("--kernel", action="store_true", default=True)
    ap.add_argument("--out", default="benchmarks/out")
    ap.add_argument("--history-keep", type=int, default=20,
                    help="in --ci: keep only the newest N sha entries "
                         "under benchmarks/history/ (0 = keep all)")
    args = ap.parse_args(argv)
    if args.ci and args.full:
        ap.error("--ci and --full are mutually exclusive")

    from benchmarks import table1_throughput, fig3_segment_width
    from benchmarks import sdtw_scaling
    from benchmarks import search_throughput, backend_matrix
    from benchmarks import align_throughput, band_skip, aligner_session
    from benchmarks import serve_stream, soft_backward
    from benchmarks import family_matrix

    # (name, thunk(rows)) — in --ci mode only benches with a tiny
    # asserting mode run; the paper-workload sweeps are bench-only
    full, ci = args.full, args.ci
    benches = []
    if not ci:
        benches += [
            ("table1", lambda rows: table1_throughput.run(
                full=full, kernel=args.kernel, csv=rows)),
            ("sdtw_scaling", lambda rows: sdtw_scaling.run(csv=rows)),
        ]
    benches += [
        # fig3 runs in --ci too: the tiny-budget tuner smoke asserts a
        # second run against the same cache file is a pure cache hit
        ("fig3_segment_width", lambda rows: fig3_segment_width.run(
            full=full, ci=ci, csv=rows)),
        ("search_throughput", lambda rows: search_throughput.run(
            full=full, ci=ci, csv=rows)),
        ("backend_matrix", lambda rows: backend_matrix.run(
            full=full, ci=ci, csv=rows)),
        # family_matrix runs in --ci too: every repro.dp family is
        # oracle-checked and kernel-vs-engine parity-asserted per run
        ("family_matrix", lambda rows: family_matrix.run(
            full=full, ci=ci, csv=rows)),
        ("align_throughput", lambda rows: align_throughput.run(
            full=full, ci=ci, csv=rows)),
        ("band_skip", lambda rows: band_skip.run(
            full=full, ci=ci, csv=rows)),
        ("aligner_session", lambda rows: aligner_session.run(
            full=full, ci=ci, csv=rows)),
        # serve_stream runs in --ci too: a seconds-long deterministic
        # smoke that hard-asserts zero timeouts/rejects and served
        # results bit-identical to offline SearchService.topk
        ("serve_stream", lambda rows: serve_stream.run(
            full=full, ci=ci, csv=rows)),
        # soft_backward asserts fused-vs-engine gradient parity and the
        # zero-O(M*N)-buffer memory contract in every mode
        ("soft_backward", lambda rows: soft_backward.run(
            full=full, ci=ci, csv=rows)),
    ]

    mode = "ci" if ci else "full" if full else "reduced"
    all_rows: list[dict] = []
    written: list[str] = []
    for name, thunk in benches:
        print("=" * 70)
        rows: list[dict] = []
        ret = thunk(rows)
        # a bench returning a flat numeric dict supplies its own
        # comparable metrics (e.g. fig3's tuned_vs_default); others
        # fall back to write_bench's row summarization
        metrics = ret if (
            isinstance(ret, dict) and ret
            and all(isinstance(k, str)
                    and isinstance(v, (int, float))
                    and not isinstance(v, bool)
                    for k, v in ret.items())) else None
        path = common.write_bench(name, out_dir=args.out,
                                  params={"mode": mode}, rows=rows,
                                  metrics=metrics)
        written.append(path)
        all_rows += rows

    os.makedirs(args.out, exist_ok=True)
    keys = sorted({k for r in all_rows for k in r})
    path = os.path.join(args.out, "results.csv")
    with open(path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=keys)
        w.writeheader()
        w.writerows(all_rows)
    print("=" * 70)
    print(f"wrote {len(all_rows)} rows -> {path}")

    # validate what actually landed on disk: a malformed or metric-less
    # document must fail the run (the CI contract), not sit in the
    # artifacts looking plausible
    docs = common.load_bench_dir(args.out)
    missing = [n for n, _ in benches if n not in docs]
    if missing:
        raise common.BenchSchemaError(
            f"missing BENCH_*.json for bench(es) {missing} in {args.out}")
    for name, doc in docs.items():
        print(f"  BENCH_{name}.json: {len(doc['metrics'])} metrics, "
              f"{len(doc['rows'])} rows  [schema ok]")

    # bench history: in --ci the validated BENCH_*.json set is also
    # archived under benchmarks/history/<git-sha>/ so
    # `launch/report.py --history` can flag metric trends across runs
    if args.ci:
        dest = _archive_history(written, args.out)
        if dest:
            print(f"archived {len(written)} BENCH docs -> {dest}")
        removed = prune_history(keep=args.history_keep)
        if removed:
            print(f"pruned {len(removed)} old history entr"
                  f"{'y' if len(removed) == 1 else 'ies'} "
                  f"(--history-keep {args.history_keep})")


def _archive_history(paths, out_dir,
                     root: str = "benchmarks/history") -> str | None:
    """Copy the run's BENCH_*.json files into ``<root>/<git-sha>/``;
    falls back to a timestamped entry outside a git checkout."""
    import shutil
    import subprocess
    import time
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            check=True).stdout.strip()
    except Exception:
        sha = f"nogit-{int(time.time())}"
    if not sha:
        return None
    dest = os.path.join(root, sha)
    os.makedirs(dest, exist_ok=True)
    for p in paths:
        shutil.copy2(p, dest)
    return dest


def prune_history(root: str = "benchmarks/history",
                  keep: int = 20) -> list[str]:
    """Drop all but the newest ``keep`` per-sha entries under ``root``
    (newest by directory mtime — shas don't sort chronologically).
    ``keep <= 0`` disables pruning.  Returns the removed entry names."""
    import shutil
    if keep <= 0 or not os.path.isdir(root):
        return []
    entries = [e for e in os.listdir(root)
               if os.path.isdir(os.path.join(root, e))]
    entries.sort(key=lambda e: os.path.getmtime(os.path.join(root, e)),
                 reverse=True)
    removed = []
    for e in entries[keep:]:
        shutil.rmtree(os.path.join(root, e))
        removed.append(e)
    return removed


if __name__ == "__main__":
    main()
