"""repro.obs: metrics math, span semantics, exporters, bench schema,
the instrumented hot paths (registry.select / Aligner / SearchService),
and the report --compare regression gate.

The quantile tests pin Histogram to numpy's default linear
interpolation; the tracing tests pin the device-sync contract (a
synced span's duration covers the block; a non-sync tracer never
blocks); the integration test pins the acceptance criterion: a traced
search + warm aligner call yields a Chrome-loadable trace with
per-stage spans, nonzero cascade/cache metrics, and ZERO added
retraces.
"""

import json
import math
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs import Counter, Gauge, Histogram, MetricsRegistry, Tracer
from repro.obs import bench as obench
from repro.obs.tracing import chrome_event, load_chrome, load_jsonl


# ---------------------------------------------------------------- metrics

def test_counter_monotonic():
    c = Counter("x")
    assert c.inc() == 1
    assert c.inc(4) == 5
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.summary() == {"type": "counter", "value": 5}


def test_gauge_set_add():
    g = Gauge("x")
    g.set(2.5)
    g.add(-1.0)
    assert g.value == 1.5
    assert g.summary()["type"] == "gauge"


@pytest.mark.parametrize("q", [0.0, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0])
@pytest.mark.parametrize("seed,n", [(0, 7), (1, 100), (2, 1000)])
def test_histogram_quantile_matches_numpy(q, seed, n):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=n) * 10
    h = Histogram("lat")
    for x in xs:
        h.record(float(x))
    assert h.quantile(q) == pytest.approx(float(np.quantile(xs, q)),
                                          rel=1e-12, abs=1e-12)


def test_histogram_moments_and_reservoir():
    h = Histogram("lat", max_samples=64)
    xs = list(range(1000))
    for x in xs:
        h.record(x)
    # count/sum/min/max/mean stay exact past the reservoir limit
    assert h.count == 1000
    assert h.sum == sum(xs)
    assert (h.min, h.max) == (0, 999)
    assert h.mean == pytest.approx(float(np.mean(xs)))
    # quantiles become estimates over 64 kept samples, still in range
    assert 0 <= h.quantile(0.5) <= 999
    with pytest.raises(ValueError):
        h.record(float("nan"))
    with pytest.raises(ValueError):
        h.quantile(1.5)
    assert math.isnan(Histogram("empty").quantile(0.5))


def test_registry_type_conflict_and_value():
    m = MetricsRegistry()
    m.inc("a.calls", 2)
    m.set_gauge("a.rate", 0.5)
    m.observe("a.ms", 3.0)
    with pytest.raises(TypeError):
        m.gauge("a.calls")
    with pytest.raises(ValueError):
        m.counter("")
    assert m.value("a.calls") == 2
    assert m.value("a.rate") == 0.5
    assert m.value("a.ms") == 1          # histograms: sample count
    assert m.value("missing", default=-1) == -1
    assert "a.calls" in m and "nope" not in m
    snap = m.snapshot()
    assert snap["a.ms"]["type"] == "histogram"
    m.reset()
    assert m.names() == []


def test_registry_cardinality_cap_error_mode():
    m = MetricsRegistry(max_names=3)
    m.inc("a")
    m.set_gauge("b", 1.0)
    m.observe("c", 2.0)
    m.inc("a", 5)                        # existing names keep working
    with pytest.raises(ValueError, match="max_names"):
        m.inc("d")
    with pytest.raises(ValueError, match="max_names"):
        m.histogram("e")
    assert sorted(m.names()) == ["a", "b", "c"]
    with pytest.raises(ValueError):
        MetricsRegistry(max_names=0)
    with pytest.raises(ValueError):
        MetricsRegistry(overflow="explode")


def test_registry_cardinality_cap_drop_mode():
    m = MetricsRegistry(max_names=3, overflow="drop")
    m.inc("a")
    m.inc("b")                           # 2 names + 1 reserved slot
    assert m.inc("overflow.1", 7) == 7   # detached metric still records
    m.observe("overflow.2", 1.0)
    m.set_gauge("overflow.3", 2.0)
    assert "overflow.1" not in m
    assert m.value("metrics.dropped_names") == 3
    assert sorted(m.names()) == ["a", "b", "metrics.dropped_names"]
    assert len(m.names()) <= 3           # exports stay bounded at the cap
    m.inc("a")                           # registered names unaffected
    assert m.value("a") == 2


def test_registry_thread_safety():
    m = MetricsRegistry()

    def work():
        for _ in range(1000):
            m.inc("hits")
            m.observe("ms", 1.0)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert m.value("hits") == 8000
    assert m.histogram("ms").count == 8000


# ---------------------------------------------------------------- tracing

def test_span_nesting_order_and_parents():
    tr = Tracer()
    with tr.span("outer", run=1):
        with tr.span("mid"):
            with tr.span("inner"):
                pass
        with tr.span("mid2"):
            pass
    # finish order: children before parents
    names = [e["name"] for e in tr.events]
    assert names == ["inner", "mid", "mid2", "outer"]
    by = {e["name"]: e for e in tr.events}
    assert by["outer"]["depth"] == 0 and by["outer"]["parent"] is None
    assert by["mid"]["parent"] == "outer" and by["mid"]["depth"] == 1
    assert by["inner"]["parent"] == "mid" and by["inner"]["depth"] == 2
    assert by["outer"]["args"] == {"run": 1}
    assert all(e["dur_ns"] >= 0 for e in tr.events)
    # outer's duration covers its children
    assert by["outer"]["dur_ns"] >= by["mid"]["dur_ns"]
    assert tr.active_depth() == 0


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation; logs enter/exit."""
    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def _nested_spans(tr):
    with tr.span("outer", run=1):
        with tr.span("mid"):
            with tr.span("inner"):
                pass
        with pytest.raises(RuntimeError):
            with tr.span("failing"):
                raise RuntimeError("boom")


@pytest.mark.parametrize("profiler", [False, True])
def test_profiler_tracer_nests_and_records_like_the_default(
        monkeypatch, profiler):
    import jax
    monkeypatch.setattr(_FakeAnnotation, "log", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    plain, tr = Tracer(), Tracer(profiler=profiler)
    _nested_spans(plain)
    _nested_spans(tr)

    def shape(events):
        return [(e["name"], e["depth"], e["parent"], e.get("args"),
                 e.get("error", False)) for e in events]
    assert shape(tr.events) == shape(plain.events)
    assert tr.active_depth() == 0
    if not profiler:
        assert _FakeAnnotation.log == []
        return
    # one annotation per span, opened and closed in span order, the
    # failing span's included
    assert _FakeAnnotation.log == [
        ("enter", "outer"), ("enter", "mid"), ("enter", "inner"),
        ("exit", "inner"), ("exit", "mid"), ("enter", "failing"),
        ("exit", "failing"), ("exit", "outer")]


@pytest.mark.parametrize("profiler", [False, True])
def test_profiler_tracer_spans_land_in_a_jax_profile(tmp_path, profiler):
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    tr = Tracer(profiler=profiler)
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("outer.span"):
            with tr.span("inner.span"):
                jnp.ones(3).block_until_ready()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("outer.span", "inner.span"):
                    found[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    if not profiler:
        assert found == {}
        return
    assert set(found) == {"outer.span", "inner.span"}
    (os_, oe), (is_, ie) = found["outer.span"], found["inner.span"]
    assert os_ <= is_ <= ie <= oe


def test_span_records_metrics_histogram():
    m = MetricsRegistry()
    tr = Tracer(metrics=m)
    for _ in range(3):
        with tr.span("step"):
            pass
    assert m.histogram("span.step.ms").count == 3


def test_device_sync_blocks_before_end_timestamp(monkeypatch):
    import repro.obs.tracing as tracing
    calls = []

    def fake_block(values):
        calls.append(values)
        import time
        time.sleep(0.02)

    monkeypatch.setattr(tracing, "_block", fake_block)
    tr = Tracer(device_sync=True)
    with tr.span("dispatch") as sp:
        sp.sync(object())
    (e,) = tr.events
    assert e["synced"] is True
    assert len(calls) == 1
    assert e["dur_ns"] >= 15e6          # the sleep is inside the span


def test_no_sync_never_blocks(monkeypatch):
    import repro.obs.tracing as tracing

    def boom(values):
        raise AssertionError("device_sync=False must not block")

    monkeypatch.setattr(tracing, "_block", boom)
    tr = Tracer(device_sync=False)
    with tr.span("dispatch") as sp:
        sp.sync(object())
    (e,) = tr.events
    assert e["synced"] is False


def test_span_error_flag_skips_sync(monkeypatch):
    import repro.obs.tracing as tracing
    monkeypatch.setattr(tracing, "_block", lambda v: (_ for _ in ()).throw(
        AssertionError("must not block on error exit")))
    tr = Tracer(device_sync=True)
    with pytest.raises(RuntimeError):
        with tr.span("bad") as sp:
            sp.sync(object())
            raise RuntimeError("boom")
    (e,) = tr.events
    assert e["error"] is True and e["synced"] is False
    assert tr.active_depth() == 0       # stack unwound


def test_trace_exports_round_trip(tmp_path):
    tr = Tracer()
    with tr.span("a", k=1):
        with tr.span("b"):
            pass
    jp = tmp_path / "t.jsonl"
    cp = tmp_path / "t.json"
    assert tr.export_jsonl(jp) == 2
    assert tr.export_chrome(cp) == 2
    back = load_jsonl(jp)
    assert back == tr.events
    ce = load_chrome(cp)
    assert [e["name"] for e in ce] == ["b", "a"]
    assert all(e["ph"] == "X" for e in ce)
    for orig, chrome in zip(tr.events, ce):
        assert chrome["ts"] == pytest.approx(orig["ts_ns"] / 1e3)
        assert chrome["dur"] == pytest.approx(orig["dur_ns"] / 1e3)
    assert chrome_event(tr.events[1])["args"]["k"] == 1
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    with pytest.raises(ValueError):
        load_chrome(bad)


def test_default_tracer_and_save_trace(tmp_path):
    obs.reset()
    with obs.trace("unit.run"):
        pass
    p = obs.save_trace(tmp_path / "d.json")
    assert [e["name"] for e in load_chrome(p)] == ["unit.run"]
    p = obs.save_trace(tmp_path / "d.jsonl")
    assert [e["name"] for e in load_jsonl(p)] == ["unit.run"]
    snap = obs.save_metrics(tmp_path / "m.json")
    assert "span.unit.run.ms" in snap
    assert json.load(open(tmp_path / "m.json")) == snap
    obs.reset()
    assert obs.default_tracer().events == []


def test_log_level_env(monkeypatch):
    import logging
    monkeypatch.setenv("REPRO_LOG", "debug")
    assert obs.log_level() == logging.DEBUG
    monkeypatch.setenv("REPRO_LOG", "15")
    assert obs.log_level() == 15
    monkeypatch.setenv("REPRO_LOG", "nope")
    with pytest.raises(ValueError):
        obs.log_level()
    monkeypatch.delenv("REPRO_LOG")
    assert obs.log_level() == logging.INFO


# ----------------------------------------------------------- bench schema

def _good_doc():
    return obench.bench_doc("unit", params={"mode": "test"},
                            rows=[{"ms": 1.0, "tag": "a"},
                                  {"ms": 3.0, "tag": "b"}])


def test_bench_doc_valid_and_summarized():
    doc = _good_doc()
    assert doc["schema"] == obench.BENCH_SCHEMA
    assert doc["metrics"] == {"ms": 2.0}          # median, strings skipped
    obench.validate_bench(doc)


@pytest.mark.parametrize("mutate", [
    lambda d: d.update(schema="repro.bench/v0"),
    lambda d: d.update(name=""),
    lambda d: d.pop("created_unix"),
    lambda d: d.pop("machine"),
    lambda d: d["machine"].pop("jax_backend"),
    lambda d: d.update(metrics={}),
    lambda d: d["metrics"].update(bad=float("inf")),
    lambda d: d["metrics"].update(bad="fast"),
    lambda d: d.update(rows=[1, 2]),
])
def test_bench_schema_rejects(mutate):
    doc = _good_doc()
    mutate(doc)
    with pytest.raises(obench.BenchSchemaError):
        obench.validate_bench(doc)


def test_write_load_bench_dir(tmp_path):
    p = obench.write_bench("unit", out_dir=str(tmp_path),
                           params={}, rows=[{"ms": 1.0}])
    assert p.endswith("BENCH_unit.json")
    docs = obench.load_bench_dir(str(tmp_path))
    assert list(docs) == ["unit"] and docs["unit"]["metrics"]["ms"] == 1.0
    (tmp_path / "BENCH_broken.json").write_text("not json")
    with pytest.raises(obench.BenchSchemaError):
        obench.load_bench_dir(str(tmp_path))


# --------------------------------------------------------- report compare

def test_metric_direction_heuristics():
    from repro.launch.report import metric_direction
    assert metric_direction("ms_warm_p99") == -1
    assert metric_direction("topk_ms_p50") == -1
    assert metric_direction("sweep_s") == -1
    assert metric_direction("padding_waste") == -1
    assert metric_direction("qps") == 1
    assert metric_direction("gsps") == 1
    assert metric_direction("warm_calls_per_s") == 1
    assert metric_direction("speedup") == 1
    assert metric_direction("B") == 0            # never flagged


def test_report_compare_flags_injected_regression(tmp_path, capsys):
    from repro.launch import report
    a, b = tmp_path / "a", tmp_path / "b"
    rows = [{"ms": 10.0, "qps": 100.0}]
    obench.write_bench("u", out_dir=str(a), rows=rows)
    obench.write_bench("u", out_dir=str(b), rows=rows)
    assert report.main(["--compare", str(a), str(b)]) == 0

    # inject a 2x latency regression into B
    doc = obench.load_bench(obench.bench_path(str(b), "u"))
    doc["metrics"]["ms"] *= 2
    json.dump(doc, open(obench.bench_path(str(b), "u"), "w"))
    assert report.main(["--compare", str(a), str(b)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out
    # higher threshold lets the same delta through
    assert report.main(["--compare", str(a), str(b),
                        "--threshold", "1.5"]) == 0
    # a throughput DROP is also a regression (higher-better metric)
    doc["metrics"]["ms"] /= 2
    doc["metrics"]["qps"] = 10.0
    json.dump(doc, open(obench.bench_path(str(b), "u"), "w"))
    assert report.main(["--compare", str(a), str(b)]) == 1
    # missing bench in B / empty dir -> hard errors
    obench.write_bench("extra", out_dir=str(a), rows=rows)
    assert report.main(["--compare", str(a), str(b)]) == 1
    assert report.main(["--compare", str(a), str(tmp_path / "nope")]) == 2


@pytest.mark.parametrize("argv", [[], ["--compare", "a", "b",
                                       "--history", "h"]])
def test_report_takes_exactly_one_mode(argv, capsys):
    from repro.launch import report
    with pytest.raises(SystemExit) as exit_:
        report.main(argv)
    assert exit_.value.code == 2
    assert "exactly one of" in capsys.readouterr().err


# ------------------------------------------------- instrumented hot paths

def test_registry_select_records_choice(monkeypatch):
    from repro.backends import registry
    from repro.core.spec import DPSpec
    obs.reset()
    m = obs.default_registry()
    backend, _ = registry.select(DPSpec())
    assert m.value("registry.select.calls") == 1
    assert m.value(f"registry.select.{backend.name}") == 1
    registry.select(DPSpec(), preferred="engine")
    assert m.value("registry.select.calls") == 2
    assert m.value("registry.select.engine") >= 1
    obs.reset()


def test_aligner_counters_and_zero_warm_retraces():
    import repro
    rng = np.random.default_rng(0)
    r = rng.normal(size=64).astype(np.float32)
    q = rng.normal(size=(2, 8)).astype(np.float32)
    m = MetricsRegistry()
    tr = Tracer(metrics=m, device_sync=True)
    aligner = repro.Aligner(r, backend="engine", metrics=m, tracer=tr)

    aligner(q)                                   # cold: trace+compile
    assert (m.value("aligner.calls"), m.value("aligner.compiles"),
            m.value("aligner.traces"), m.value("aligner.cache_hits")) \
        == (1, 1, 1, 0)
    traces_before = m.value("aligner.traces")
    for _ in range(3):                           # warm: dispatch only
        aligner(q)
    assert m.value("aligner.traces") == traces_before, "warm call retraced"
    assert m.value("aligner.cache_hits") == 3
    # the dataclass view agrees with the registry
    assert aligner.stats.as_dict() == {
        "calls": 4, "cache_hits": 3, "compiles": 1, "traces": 1,
        "evictions": 0}
    names = [e["name"] for e in tr.events]
    assert names.count("aligner.build") == 1
    assert names.count("aligner.dispatch") == 4
    by_cold = [e["args"]["cold"] for e in tr.events
               if e["name"] == "aligner.dispatch"]
    assert by_cold == [True, False, False, False]
    assert all(e["synced"] for e in tr.events
               if e["name"] == "aligner.dispatch")


def test_aligner_failed_build_ticks_nothing():
    import repro
    rng = np.random.default_rng(0)
    r = rng.normal(size=64).astype(np.float32)
    q = rng.normal(size=(2, 8)).astype(np.float32)
    m = MetricsRegistry()
    aligner = repro.Aligner(r, backend="quantized",
                            metrics=m, tracer=Tracer())
    with pytest.raises(ValueError):
        aligner(q, outputs=("cost", "start", "end"))
    # the failed build left no executable and no compile tick
    assert aligner.stats.compiles == 0 and aligner.executables() == 0
    assert m.value("aligner.compiles") == 0
    aligner(q)                                   # session still usable
    assert aligner.stats.compiles == 1


def _tiny_search_service(m, tr, prune=True):
    from repro.core.spec import DPSpec
    from repro.data.cbf import make_search_dataset
    from repro.search import ReferenceIndex, SearchConfig, SearchService
    refs, queries, labels = make_search_dataset(
        seed=0, n_refs=3, motifs_per_ref=4, n_queries=8, query_motifs=2)
    index = ReferenceIndex(spec=DPSpec())
    for name, series in refs.items():
        index.add(name, series)
    svc = SearchService(index, SearchConfig(backend="engine", prune=prune),
                        metrics=m, tracer=tr)
    return svc, queries


def test_search_service_cumulative_stats_and_metrics(tmp_path):
    m = MetricsRegistry()
    tr = Tracer(metrics=m, device_sync=True)
    svc, queries = _tiny_search_service(m, tr)

    svc.topk(queries[:4], k=1)
    first = svc.last.as_dict()
    assert svc.stats.as_dict() == first          # one call so far
    svc.topk(queries[4:8], k=1)
    assert svc.last.topk_calls == 1              # per-call snapshot
    assert svc.stats.topk_calls == 2             # cumulative
    assert svc.stats.pairs == first["pairs"] + svc.last.pairs
    assert svc.stats.dp_pairs + svc.stats.skipped == svc.stats.pairs
    assert svc.stats.bound_s > 0 and svc.stats.sweep_s > 0
    assert 0.0 <= svc.stats.padding_waste < 1.0

    # registry mirrors the cumulative view
    assert m.value("search.topk_calls") == 2
    assert m.value("search.pairs") == svc.stats.pairs
    assert m.value("search.pruned_stage0") == svc.stats.pruned_stage0
    assert m.histogram("search.topk_ms").count == 2
    assert m.histogram("search.bound_ms").count == 2

    svc.reset_stats()
    assert svc.stats.topk_calls == 0 and svc.last.topk_calls == 0

    # per-stage spans present and properly nested under search.topk
    by = {}
    for e in tr.events:
        by.setdefault(e["name"], []).append(e)
    assert set(by) >= {"search.topk", "search.bound0", "search.sweep"}
    assert all(e["parent"] == "search.topk" for e in by["search.bound0"])
    assert all(e["synced"] for e in by["search.sweep"])


def test_search_stats_merge_and_padding_waste():
    from repro.search.service import SearchStats
    a = SearchStats(pairs=4, dp_pairs=2, sweep_rows=8, sweep_rows_real=6,
                    bound_s=0.5, topk_calls=1)
    b = SearchStats(pairs=6, dp_pairs=3, sweep_rows=8, sweep_rows_real=2,
                    bound_s=0.25, topk_calls=1)
    a.merge(b)
    assert (a.pairs, a.dp_pairs, a.topk_calls) == (10, 5, 2)
    assert a.bound_s == 0.75
    assert a.padding_waste == pytest.approx(1 - 8 / 16)
    assert SearchStats().padding_waste == 0.0


def test_traced_search_and_aligner_end_to_end(tmp_path):
    """Acceptance: traced topk + warm Aligner -> Chrome-loadable trace
    with per-stage spans, nonzero cascade/cache metrics, zero added
    retraces."""
    import repro
    m = MetricsRegistry()
    tr = Tracer(metrics=m, device_sync=True)
    svc, queries = _tiny_search_service(m, tr)
    svc.topk(queries[:4], k=1)

    rng = np.random.default_rng(1)
    r = rng.normal(size=64).astype(np.float32)
    q = rng.normal(size=(2, 8)).astype(np.float32)
    aligner = repro.Aligner(r, backend="engine", metrics=m, tracer=tr)
    aligner(q)                                   # cold
    traces = m.value("aligner.traces")
    aligner(q)                                   # warm
    assert m.value("aligner.traces") == traces   # zero added retraces

    path = tmp_path / "trace.json"
    tr.export_chrome(path)
    events = load_chrome(path)                   # validates container
    names = {e["name"] for e in events}
    assert {"search.topk", "search.bound0", "search.sweep",
            "aligner.build", "aligner.dispatch"} <= names
    assert m.value("search.pruned_stage0") > 0   # cascade did something
    assert m.value("aligner.cache_hits") == 1
    assert m.histogram("span.search.topk.ms").count == 1


# ---------------------------------------------------------- report plots

def test_report_plot_writes_trend_svgs(tmp_path):
    from repro.launch import report
    root, out = tmp_path / "history", tmp_path / "plots"
    for sha, ms in (("aaa1111", 10.0), ("bbb2222", 12.0)):
        obench.write_bench("u", out_dir=str(root / sha),
                           rows=[{"ms": ms, "qps": 100.0}])
    paths = report.write_plots(str(root), str(out))
    import os
    assert sorted(os.path.basename(p) for p in paths) == \
        ["u__ms.svg", "u__qps.svg"]
    svg = (out / "u__ms.svg").read_text()
    assert svg.startswith("<svg") and "u: ms" in svg
    assert "latest 12" in svg
    # one point per history entry
    assert svg.count("<circle") == 2
    # CLI round trip, and schema errors exit 2
    assert report.main(["--plot", str(root),
                        "--plot-out", str(out)]) == 0
    empty = tmp_path / "nohistory"
    empty.mkdir()
    assert report.main(["--plot", str(empty),
                        "--plot-out", str(out)]) == 2
