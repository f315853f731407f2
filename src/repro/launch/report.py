"""Reporting driver over ``BENCH_*.json`` documents, three modes:

  * ``--compare A/ B/`` — diff two directories of schema-versioned
    ``BENCH_*.json`` files (written by ``benchmarks/run.py``; schema in
    ``repro.obs.bench``) and flag regressions beyond ``--threshold``
    (default 10%).  Metric direction is inferred from the name (``ms``/
    ``*_s``/``waste`` are lower-better; ``gsps``/``qps``/``*_per_s``/
    ``speedup``/``skip_fraction`` are higher-better; anything else is
    reported but never flagged).  Exits nonzero when any regression is
    found — the CI gate for perf PRs:

      python -m repro.launch.report --compare main/ pr/ --threshold 0.1

  * ``--history DIR`` — trend view over the archive that
    ``benchmarks/run.py --ci`` grows (one ``DIR/<git-sha>/`` entry of
    BENCH docs per run).  Orders entries by the docs' ``created_unix``,
    takes the last ``--last`` (default 5), and flags any metric whose
    LATEST value worsened beyond ``--threshold`` against the median of
    the preceding window — the median, not the single previous run, so
    one noisy entry can't hide (or fake) a drift.  Same exit codes as
    ``--compare``: 1 when anything is flagged, 2 on schema errors.

      python -m repro.launch.report --history benchmarks/history --last 5

  * ``--plot DIR`` — render the same history archive as per-metric
    trend SVGs (one ``<bench>__<metric>.svg`` sparkline per directional
    metric series, dependency-free hand-rolled SVG) into ``--plot-out``
    (default ``benchmarks/out/plots``):

      python -m repro.launch.report --plot benchmarks/history
"""

from __future__ import annotations

import argparse
import logging
import os
import re
import sys

from repro import obs
from repro.obs.bench import BenchSchemaError, load_bench_dir

log = logging.getLogger(__name__)

# direction by metric-name convention (see benchmarks/*.py rows)
LOWER_BETTER = re.compile(
    r"(^|_)(ms|ns|s|sec|seconds|time|latency|waste|bound_s|sweep_s)"
    r"(_p\d+)?($|_)|_ms($|_)|ms_")
HIGHER_BETTER = re.compile(
    r"gsps|qps|per_s|throughput|speedup|calls_per_s|skip_fraction|"
    r"hit_rate|over_warm")


def metric_direction(name: str) -> int:
    """-1 lower-better, +1 higher-better, 0 unknown (never flagged)."""
    low = name.lower()
    if HIGHER_BETTER.search(low):
        return 1
    if LOWER_BETTER.search(low):
        return -1
    return 0


def compare_dirs(dir_a: str, dir_b: str, *, threshold: float = 0.10,
                 out=None) -> int:
    """Print a markdown diff table of B vs A; return the number of
    regressions beyond ``threshold`` (relative worsening)."""
    out = sys.stdout if out is None else out
    a_docs, b_docs = load_bench_dir(dir_a), load_bench_dir(dir_b)
    if not a_docs:
        raise BenchSchemaError(f"{dir_a}: no BENCH_*.json files")
    if not b_docs:
        raise BenchSchemaError(f"{dir_b}: no BENCH_*.json files")
    fp_a = next(iter(a_docs.values()))["machine"]
    fp_b = next(iter(b_docs.values()))["machine"]
    for key in ("platform", "jax_backend"):
        if fp_a.get(key) != fp_b.get(key):
            print(f"WARNING: machine.{key} differs "
                  f"({fp_a.get(key)!r} vs {fp_b.get(key)!r}) — "
                  f"deltas may reflect the machine, not the code",
                  file=out)

    regressions = []
    print(f"| bench | metric | {dir_a} | {dir_b} | delta | verdict |",
          file=out)
    print("|---|---|---|---|---|---|", file=out)
    for name in sorted(a_docs):
        if name not in b_docs:
            print(f"| {name} | - | present | MISSING | - | missing |",
                  file=out)
            regressions.append((name, "<bench missing>"))
            continue
        ma, mb = a_docs[name]["metrics"], b_docs[name]["metrics"]
        for key in sorted(ma):
            if key not in mb:
                continue
            va, vb = ma[key], mb[key]
            if va == 0:
                continue
            delta = (vb - va) / abs(va)
            direction = metric_direction(key)
            worsening = delta * -direction    # >0 means B is worse
            if direction and worsening > threshold:
                verdict = f"REGRESSION (>{threshold:.0%})"
                regressions.append((name, key))
            elif direction and -worsening > threshold:
                verdict = "improved"
            else:
                verdict = "ok" if direction else "(untracked)"
            print(f"| {name} | {key} | {fmt(va)} | {fmt(vb)} | "
                  f"{delta:+.1%} | {verdict} |", file=out)
    if regressions:
        print(f"\n{len(regressions)} regression(s) beyond "
              f"{threshold:.0%}:", file=out)
        for name, key in regressions:
            print(f"  - {name}: {key}", file=out)
    else:
        print(f"\nno regressions beyond {threshold:.0%}", file=out)
    return len(regressions)


def fmt(x):
    return f"{x:.3g}"


# ------------------------------------------------------------- history
def load_history(root: str) -> list[tuple[str, dict]]:
    """-> [(entry_name, {bench: doc})] ordered oldest -> newest by the
    docs' ``created_unix`` (directory names are git shas — unordered)."""
    entries = []
    for d in sorted(os.listdir(root)):
        path = os.path.join(root, d)
        if not os.path.isdir(path):
            continue
        docs = load_bench_dir(path)       # raises BenchSchemaError
        if docs:
            stamp = min(doc["created_unix"] for doc in docs.values())
            entries.append((stamp, d, docs))
    entries.sort(key=lambda e: e[0])
    return [(name, docs) for _, name, docs in entries]


def _median(xs):
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2


def history_trends(root: str, *, last: int = 5,
                   threshold: float = 0.10, out=None) -> int:
    """Print the metric trend table over the last ``last`` history
    entries; return the number of flagged drifts (latest vs. the median
    of the preceding entries, directional metrics only)."""
    out = sys.stdout if out is None else out
    entries = load_history(root)
    if not entries:
        raise BenchSchemaError(f"{root}: no history entries with "
                               f"BENCH_*.json files")
    entries = entries[-last:]
    names = [name for name, _ in entries]
    print(f"history: {len(entries)} entr"
          f"{'y' if len(entries) == 1 else 'ies'} "
          f"({' -> '.join(names)})", file=out)
    if len(entries) < 2:
        print("(need >= 2 entries for a trend; nothing to flag)",
              file=out)
        return 0

    latest_name, latest = entries[-1]
    window = entries[:-1]
    flagged = []
    print(f"| bench | metric | median({len(window)} prior) | "
          f"{latest_name} | delta | verdict |", file=out)
    print("|---|---|---|---|---|---|", file=out)
    for bench in sorted(latest):
        metrics = latest[bench]["metrics"]
        for key in sorted(metrics):
            prior = [docs[bench]["metrics"][key]
                     for _, docs in window
                     if bench in docs and key in docs[bench]["metrics"]]
            if not prior:
                continue
            base = _median(prior)
            if base == 0:
                continue
            vb = metrics[key]
            delta = (vb - base) / abs(base)
            direction = metric_direction(key)
            worsening = delta * -direction
            if direction and worsening > threshold:
                verdict = f"DRIFT (>{threshold:.0%})"
                flagged.append((bench, key))
            elif direction and -worsening > threshold:
                verdict = "improved"
            else:
                verdict = "ok" if direction else "(untracked)"
            print(f"| {bench} | {key} | {fmt(base)} | {fmt(vb)} | "
                  f"{delta:+.1%} | {verdict} |", file=out)
    if flagged:
        print(f"\n{len(flagged)} metric(s) drifted beyond "
              f"{threshold:.0%}:", file=out)
        for bench, key in flagged:
            print(f"  - {bench}: {key}", file=out)
    else:
        print(f"\nno drift beyond {threshold:.0%}", file=out)
    return len(flagged)


# --------------------------------------------------------------- plots
def _slug(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9._-]+", "-", name)


def _svg_sparkline(bench: str, metric: str,
                   points: list[tuple[str, float]]) -> str:
    """One metric series -> a self-contained SVG trend chart.

    Hand-rolled (no matplotlib in the toolchain): a polyline over the
    history entries oldest -> newest, per-entry dots, min/max/latest
    annotations, and the latest point tinted by the metric's direction
    (green when the latest value is on the good side of the series
    median, red when on the bad side, gray for untracked metrics).
    """
    W, H = 520, 170
    left, right, top, bottom = 56, 16, 34, 34
    pw, ph = W - left - right, H - top - bottom
    vals = [v for _, v in points]
    lo, hi = min(vals), max(vals)
    if hi == lo:                      # flat series: pad so it centers
        pad = abs(hi) * 0.05 or 1.0
        lo, hi = lo - pad, hi + pad
    n = len(points)

    def x(i):
        return left + (pw * i / (n - 1) if n > 1 else pw / 2)

    def y(v):
        return top + ph * (1 - (v - lo) / (hi - lo))

    direction = metric_direction(metric)
    med = _median(vals)
    latest = vals[-1]
    if direction == 0 or latest == med:
        tint = "#888888"
    else:
        good = (latest - med) * direction > 0
        tint = "#2e7d32" if good else "#c62828"

    pts = " ".join(f"{x(i):.1f},{y(v):.1f}"
                   for i, (_, v) in enumerate(points))
    dots = "\n  ".join(
        f'<circle cx="{x(i):.1f}" cy="{y(v):.1f}" r="2.5" '
        f'fill="{tint if i == n - 1 else "#1565c0"}">'
        f"<title>{name}: {v:.6g}</title></circle>"
        for i, (name, v) in enumerate(points))
    first, last = points[0][0], points[-1][0]
    return f"""<svg xmlns="http://www.w3.org/2000/svg" width="{W}" height="{H}" viewBox="0 0 {W} {H}">
  <rect width="{W}" height="{H}" fill="white"/>
  <text x="{left}" y="16" font-family="monospace" font-size="12" fill="#333">{bench}: {metric}</text>
  <text x="{W - right}" y="16" text-anchor="end" font-family="monospace" font-size="12" fill="{tint}">latest {latest:.6g}</text>
  <text x="{left - 6}" y="{y(hi):.1f}" text-anchor="end" dominant-baseline="middle" font-family="monospace" font-size="10" fill="#777">{hi:.4g}</text>
  <text x="{left - 6}" y="{y(lo):.1f}" text-anchor="end" dominant-baseline="middle" font-family="monospace" font-size="10" fill="#777">{lo:.4g}</text>
  <line x1="{left}" y1="{top}" x2="{left}" y2="{top + ph}" stroke="#ccc"/>
  <line x1="{left}" y1="{top + ph}" x2="{left + pw}" y2="{top + ph}" stroke="#ccc"/>
  <polyline points="{pts}" fill="none" stroke="#1565c0" stroke-width="1.5"/>
  {dots}
  <text x="{left}" y="{H - 10}" font-family="monospace" font-size="10" fill="#777">{first}</text>
  <text x="{left + pw:.0f}" y="{H - 10}" text-anchor="end" font-family="monospace" font-size="10" fill="#777">{last}</text>
</svg>
"""


def write_plots(root: str, out_dir: str, *, last: int = 20,
                out=None) -> list[str]:
    """Render every metric series in the history archive under ``root``
    (the ``benchmarks/history`` layout ``--history`` reads) to
    ``out_dir/<bench>__<metric>.svg``; returns the written paths."""
    out = sys.stdout if out is None else out
    entries = load_history(root)
    if not entries:
        raise BenchSchemaError(f"{root}: no history entries with "
                               f"BENCH_*.json files")
    entries = entries[-last:]
    series: dict[tuple[str, str], list[tuple[str, float]]] = {}
    for name, docs in entries:
        for bench, doc in sorted(docs.items()):
            for key, val in sorted(doc["metrics"].items()):
                if isinstance(val, bool) or \
                        not isinstance(val, (int, float)):
                    continue
                series.setdefault((bench, key), []).append(
                    (name, float(val)))
    os.makedirs(out_dir, exist_ok=True)
    written = []
    for (bench, key), points in sorted(series.items()):
        path = os.path.join(out_dir, f"{_slug(bench)}__{_slug(key)}.svg")
        with open(path, "w") as f:
            f.write(_svg_sparkline(bench, key, points))
        written.append(path)
    print(f"wrote {len(written)} trend SVG(s) over {len(entries)} "
          f"history entr{'y' if len(entries) == 1 else 'ies'} -> "
          f"{out_dir}", file=out)
    return written


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--compare", nargs=2, metavar=("BASE", "NEW"),
                    help="diff two directories of BENCH_*.json files")
    ap.add_argument("--history", metavar="DIR",
                    help="trend view over a benchmarks/history archive "
                         "(one <git-sha>/ entry per --ci run)")
    ap.add_argument("--plot", metavar="DIR",
                    help="render per-metric trend SVGs from a "
                         "benchmarks/history archive")
    ap.add_argument("--plot-out", default="benchmarks/out/plots",
                    help="directory the --plot SVGs land in "
                         "(default benchmarks/out/plots)")
    ap.add_argument("--last", type=int, default=None,
                    help="history entries to consider (default: 5 for "
                         "--history, 20 for --plot)")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="relative worsening that counts as a "
                         "regression (default 0.10 = 10%%)")
    args = ap.parse_args(argv)
    obs.configure_logging()
    if sum(map(bool, (args.compare, args.history, args.plot))) != 1:
        ap.error("give exactly one of --compare, --history and --plot")

    if args.plot:
        if args.last is not None and args.last < 1:
            ap.error("--last must be >= 1")
        try:
            write_plots(args.plot, args.plot_out,
                        last=args.last or 20)
        except (BenchSchemaError, OSError) as e:
            log.error("%s", e)
            return 2
        return 0

    if args.compare:
        try:
            n = compare_dirs(args.compare[0], args.compare[1],
                             threshold=args.threshold)
        except BenchSchemaError as e:
            log.error("%s", e)
            return 2
        return 1 if n else 0
    if args.history:
        if args.last is not None and args.last < 1:
            ap.error("--last must be >= 1")
        try:
            n = history_trends(args.history, last=args.last or 5,
                               threshold=args.threshold)
        except (BenchSchemaError, OSError) as e:
            log.error("%s", e)
            return 2
        return 1 if n else 0


if __name__ == "__main__":
    sys.exit(main())
