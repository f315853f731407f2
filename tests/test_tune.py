"""repro.tune: the tentpole contracts.

Safety: segment width only changes the kernel's sweep schedule, never
the recurrence — the parity matrix asserts costs/ends/starts are
BIT-identical across candidate widths x outputs x band settings
(interpret mode), so no tuning verdict can ever change an answer.

Tuner: cache round-trips survive a process boundary (modeled as a
fresh TuningCache over the same file), budgets are respected, a seeded
fake timer makes the winner deterministic, corrupt caches are rejected
(treated as empty, never crash), and a warm cache answers with ZERO
timing trials — the counters prove it.
"""
import json

import numpy as np
import pytest

import repro
from repro import tune
from repro.core.spec import DPSpec
from repro.kernels import ops
from repro.obs import MetricsRegistry

WIDTHS = (2, 4, 8, 14, 16, 32)


@pytest.fixture()
def mem_cache():
    """Memory-only default cache, restored afterwards — tests must not
    touch the user's ~/.cache tuning file."""
    prev = tune.set_default_cache(tune.TuningCache(None))
    yield tune.default_cache()
    tune.set_default_cache(prev)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    q = rng.standard_normal((5, 20)).astype(np.float32)
    r = rng.standard_normal(700).astype(np.float32)
    return q, r


def fake_timer(times: dict, default: float = 9.9):
    """timer(label, make_fn) stub returning scripted seconds; records
    the call order so budget tests can count trials."""
    calls = []

    def timer(label, make_fn):
        calls.append(label)
        return times.get(label, default)

    timer.calls = calls
    return timer


# ------------------------------------------------- width parity matrix
@pytest.mark.parametrize("outputs", [("cost", "end"),
                                     ("cost", "start", "end")])
@pytest.mark.parametrize("band", [None, 12])
def test_segment_width_parity_matrix(data, outputs, band):
    """Every candidate width produces the SAME bits for every output
    and band setting: tuning is free to pick any of them."""
    q, r = data
    base = None
    for w in WIDTHS:
        res = repro.sdtw(q, r, outputs=outputs, backend="kernel",
                         segment_width=w, band=band, interpret=True)
        got = {o: np.asarray(getattr(res, o)) for o in outputs}
        if base is None:
            base = got
            continue
        for o in outputs:
            np.testing.assert_array_equal(
                got[o], base[o],
                err_msg=f"width {w} changed output {o!r} (band={band})")


def test_soft_spec_width_parity(data):
    """Soft-min sweeps stay equal across widths to float rounding: the
    width reorders the running logsumexp fold, so the last ulp can
    move — everything the hard-min matrix asserts bitwise stays
    bitwise; the soft channel is tested at tight tolerance."""
    q, r = data
    base = None
    for w in WIDTHS:
        res = repro.sdtw(q, r, backend="kernel", reduction="softmin",
                         gamma=0.5, segment_width=w, interpret=True)
        c = np.asarray(res.cost)
        if base is None:
            base = c
        else:
            np.testing.assert_allclose(c, base, rtol=1e-6, atol=1e-6)


def test_width_candidates_prune_pathological_padding():
    # a 700-sample reference pads to 4x+ its length at wide widths:
    # those candidates are dropped, the rest survive sorted + deduped
    kept = ops.width_candidates(700, WIDTHS)
    assert kept == tuple(sorted(kept))
    assert all(ops.ceil_to(700, 128 * w) <= 4 * 700 for w in kept)
    assert ops.width_candidates(10, (64,)) == (64,)   # smallest survives
    with pytest.raises(ValueError):
        ops.width_candidates(0)
    with pytest.raises(ValueError):
        ops.width_candidates(100, ())
    with pytest.raises(ValueError, match="segment_width"):
        ops.width_candidates(100, (True,))


# -------------------------------------------------------- tuning cache
def test_cache_round_trip(tmp_path, data):
    _, r = data
    path = str(tmp_path / "tuning.json")
    spec = DPSpec()
    c1 = tune.TuningCache(path)
    key = c1.key(spec=spec, m=20, n=700, batch_bucket=8,
                 outputs=("cost", "end"))
    verdict = {"backend": "kernel", "segment_width": 14, "best_ms": 1.5,
               "trials": 3, "measured": {"kernel:w14": 1.5}}
    c1.put(key, verdict)
    # a fresh object over the same file — the process boundary
    c2 = tune.TuningCache(path)
    got = c2.get(key)
    assert got is not None and got["segment_width"] == 14
    assert got["backend"] == "kernel"
    assert not c2.rejected
    # the document is schema-versioned and machine-keyed
    doc = json.loads((tmp_path / "tuning.json").read_text())
    assert doc["schema"] == tune.TUNE_SCHEMA
    assert c2.machine in doc["machines"]
    assert "fingerprint" in doc["machines"][c2.machine]


@pytest.mark.parametrize("corrupt", [
    "not json at all {",
    json.dumps({"schema": "repro.tune/v0", "machines": {}}),
    json.dumps(["wrong", "shape"]),
    json.dumps({"schema": "repro.tune/v1", "machines": "nope"}),
])
def test_corrupt_cache_rejected(tmp_path, corrupt):
    path = tmp_path / "tuning.json"
    path.write_text(corrupt)
    c = tune.TuningCache(str(path))
    assert c.rejected
    assert len(c) == 0
    # and the next put() rewrites a valid document
    key = c.key(spec=DPSpec(), m=8, n=100, batch_bucket=8,
                outputs=("cost",))
    c.put(key, {"backend": "engine", "segment_width": 8})
    assert not tune.TuningCache(str(path)).rejected


def test_malformed_entries_dropped(tmp_path):
    path = tmp_path / "tuning.json"
    mkey = tune.machine_key()
    path.write_text(json.dumps({
        "schema": tune.TUNE_SCHEMA,
        "machines": {mkey: {"entries": {
            "good": {"backend": "kernel", "segment_width": 4},
            "bad_width": {"backend": "kernel", "segment_width": 0},
            "bad_bool": {"backend": "kernel", "segment_width": True},
            "bad_type": "not a dict",
            "bad_ms": {"backend": "kernel", "segment_width": 4,
                       "best_ms": float("nan")},
        }}}}))
    c = tune.TuningCache(str(path))
    assert c.rejected
    assert list(c.entries()) == ["good"]
    with pytest.raises(ValueError, match="malformed"):
        c.put("k", {"backend": "kernel", "segment_width": -1})


def test_stale_fingerprint_entries_expire(tmp_path):
    """Entries filed under this machine's key whose STORED fingerprint
    no longer hashes back to it (e.g. a jax upgrade in place) age out
    on load — counted in ``expired`` and ``tune.cache_expired``."""
    path = tmp_path / "tuning.json"
    mkey = tune.machine_key()
    from repro.obs.bench import machine_fingerprint
    stale_fp = dict(machine_fingerprint())
    stale_fp["jax"] = "0.0.archaeology"      # drifts the machine_key
    assert tune.machine_key(stale_fp) != mkey
    path.write_text(json.dumps({
        "schema": tune.TUNE_SCHEMA,
        "machines": {mkey: {
            "fingerprint": stale_fp,
            "entries": {
                "w1": {"backend": "kernel", "segment_width": 4},
                "w2": {"backend": "engine", "segment_width": 2},
            }}}}))
    from repro import obs
    before = obs.default_registry().value("tune.cache_expired")
    c = tune.TuningCache(str(path))
    assert len(c) == 0                       # nothing trusted
    assert c.expired == 2
    assert not c.rejected                    # hygiene, not corruption
    assert obs.default_registry().value("tune.cache_expired") \
        == before + 2
    # a matching stored fingerprint is trusted as before
    path.write_text(json.dumps({
        "schema": tune.TUNE_SCHEMA,
        "machines": {mkey: {
            "fingerprint": dict(machine_fingerprint()),
            "entries": {"w1": {"backend": "kernel",
                               "segment_width": 4}}}}}))
    c2 = tune.TuningCache(str(path))
    assert c2.expired == 0 and list(c2.entries()) == ["w1"]
    # legacy documents without a stored fingerprint keep working
    path.write_text(json.dumps({
        "schema": tune.TUNE_SCHEMA,
        "machines": {mkey: {"entries": {
            "w1": {"backend": "kernel", "segment_width": 4}}}}}))
    assert list(tune.TuningCache(str(path)).entries()) == ["w1"]


def test_stale_by_age_entries_expire(tmp_path, monkeypatch):
    """max_age_s: a section whose ``updated_unix`` write stamp is older
    than the bound ages out on load — same ``expired`` /
    ``tune.cache_expired`` accounting as fingerprint drift."""
    import time as _time
    from repro.obs.bench import machine_fingerprint
    path = tmp_path / "tuning.json"
    mkey = tune.machine_key()

    def write(stamp):
        doc = {"schema": tune.TUNE_SCHEMA,
               "machines": {mkey: {
                   "fingerprint": dict(machine_fingerprint()),
                   "entries": {
                       "w1": {"backend": "kernel", "segment_width": 4},
                       "w2": {"backend": "engine", "segment_width": 2},
                   }}}}
        if stamp is not None:
            doc["machines"][mkey]["updated_unix"] = stamp
        path.write_text(json.dumps(doc))

    from repro import obs
    write(_time.time() - 3600)               # written an hour ago
    before = obs.default_registry().value("tune.cache_expired")
    stale = tune.TuningCache(str(path), max_age_s=60.0)
    assert len(stale) == 0 and stale.expired == 2
    assert not stale.rejected                # hygiene, not corruption
    assert obs.default_registry().value("tune.cache_expired") \
        == before + 2
    # a fresh-enough stamp is trusted; no bound means no expiry
    fresh = tune.TuningCache(str(path), max_age_s=7200.0)
    assert fresh.expired == 0 and len(fresh) == 2
    unbounded = tune.TuningCache(str(path))
    assert unbounded.expired == 0 and len(unbounded) == 2
    # a stamp-less section cannot prove its age: expired under a bound
    write(None)
    assert tune.TuningCache(str(path), max_age_s=60.0).expired == 2
    # a put() refreshes the stamp, so the rewritten file loads clean
    stale.put("w3", {"backend": "kernel", "segment_width": 8})
    reloaded = tune.TuningCache(str(path), max_age_s=60.0)
    assert reloaded.expired == 0 and list(reloaded.entries()) == ["w3"]
    with pytest.raises(ValueError, match="max_age_s"):
        tune.TuningCache(str(path), max_age_s=0)
    # env knob: the default cache picks the bound up from the process
    # environment (garbage is ignored, seconds are parsed)
    monkeypatch.setenv("REPRO_TUNE_CACHE_MAX_AGE", "86400")
    assert tune.cache._default_max_age() == 86400.0
    monkeypatch.setenv("REPRO_TUNE_CACHE_MAX_AGE", "soon")
    assert tune.cache._default_max_age() is None
    monkeypatch.setenv("REPRO_TUNE_CACHE_MAX_AGE", "-5")
    assert tune.cache._default_max_age() is None


def test_cache_preserves_other_machines(tmp_path):
    path = str(tmp_path / "tuning.json")
    other = tune.TuningCache(path, fingerprint={"platform": "mars"})
    other.put("alien-key", {"backend": "kernel", "segment_width": 2})
    mine = tune.TuningCache(path)
    mine.put("my-key", {"backend": "engine", "segment_width": 8})
    doc = json.loads((tmp_path / "tuning.json").read_text())
    assert len(doc["machines"]) == 2
    assert tune.TuningCache(
        path, fingerprint={"platform": "mars"}).get("alien-key")


def test_disabled_cache_path(monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", "0")
    assert tune.default_cache_path() is None
    monkeypatch.setenv("REPRO_TUNE_CACHE", "off")
    assert tune.default_cache_path() is None
    monkeypatch.setenv("REPRO_TUNE_CACHE", "/x/y.json")
    assert tune.default_cache_path() == "/x/y.json"
    monkeypatch.delenv("REPRO_TUNE_CACHE")
    assert tune.default_cache_path().endswith("tuning.json")


# -------------------------------------------------------------- tuner
def test_deterministic_winner_on_fake_timer(data):
    _, r = data
    times = {"engine": 5.0, "kernel:w8": 3.0, "kernel:w4": 2.0,
             "kernel:w2": 2.5, "kernel:w14": 4.0}
    for _ in range(2):     # same fake timings -> same winner, twice
        m = MetricsRegistry()
        res = tune.autotune(r, m=20, batch=5, candidates=WIDTHS,
                            interpret=True, cache=tune.TuningCache(None),
                            metrics=m, timer=fake_timer(times))
        assert (res.backend, res.segment_width) == ("kernel", 4)
        assert res.trials == m.value("tune.trials") > 0
        assert not res.from_cache
        # hill-climb walked 8 -> 4 -> 2 and stopped at the local min
        assert "kernel:w4" in res.measured
        assert "kernel:w2" in res.measured


@pytest.mark.parametrize("device", ["cpu", "tpu"])
def test_failing_kernel_trial(data, monkeypatch, device):
    """Off the chip a kernel trial that raises just loses to the engine;
    on a TPU it is the compiled main path failing, so it surfaces."""
    from repro.backends import registry
    monkeypatch.setattr(registry, "_device_default", lambda: device)
    _, r = data

    def timer(label, make_fn):
        if label.startswith("kernel:"):
            raise RuntimeError("Mosaic refused the kernel")
        return 1.0

    def run():
        return tune.autotune(r, m=20, batch=5, candidates=WIDTHS,
                             interpret=True, cache=tune.TuningCache(None),
                             metrics=MetricsRegistry(), timer=timer)
    if device == "tpu":
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            run()
    else:
        assert run().backend == "engine"


def test_budget_max_trials_respected(data):
    _, r = data
    timer = fake_timer({})
    m = MetricsRegistry()
    res = tune.autotune(r, m=20, batch=5, candidates=WIDTHS,
                        interpret=True, cache=tune.TuningCache(None),
                        budget=tune.TuneBudget(max_trials=2), metrics=m,
                        timer=timer)
    assert len(timer.calls) == 2 == m.value("tune.trials")
    assert res.trials == 2
    with pytest.raises(ValueError):
        tune.TuneBudget(max_trials=0)


def test_warm_cache_zero_trials(tmp_path, data):
    _, r = data
    path = str(tmp_path / "t.json")
    timer = fake_timer({"kernel:w8": 1.0})
    cold = MetricsRegistry()
    res1 = tune.autotune(r, m=20, batch=5, interpret=True,
                         cache=tune.TuningCache(path), metrics=cold,
                         timer=timer)
    assert cold.value("tune.trials") > 0
    assert cold.value("tune.cache_hits") == 0
    # "second process": fresh cache object, fresh metrics, a timer that
    # would blow up if consulted
    def exploding(label, make_fn):
        raise AssertionError("warm path must not measure")
    warm = MetricsRegistry()
    res2 = tune.autotune(r, m=20, batch=5, interpret=True,
                         cache=tune.TuningCache(path), metrics=warm,
                         timer=exploding)
    assert res2.from_cache and res2.trials == 0
    assert warm.value("tune.trials") == 0
    assert warm.value("tune.cache_hits") == 1
    assert (res2.backend, res2.segment_width) == \
        (res1.backend, res1.segment_width)


def test_tune_span_recorded(data):
    _, r = data
    from repro.obs import Tracer
    tr = Tracer()
    tune.autotune(r, m=20, batch=5, interpret=True,
                  cache=tune.TuningCache(None), metrics=MetricsRegistry(),
                  tracer=tr, timer=fake_timer({}))
    assert any(e["name"] == "tune.search" for e in tr.events)


def test_engine_winner_still_records_best_kernel_width(data):
    _, r = data
    times = {"engine": 1.0, "kernel:w8": 7.0, "kernel:w4": 6.0,
             "kernel:w2": 8.0}
    res = tune.autotune(r, m=20, batch=5, candidates=WIDTHS,
                        interpret=True, cache=tune.TuningCache(None),
                        metrics=MetricsRegistry(),
                        timer=fake_timer(times))
    assert res.backend == "engine"
    assert res.segment_width == 4     # the best kernel width measured


def test_batch_bucket():
    assert tune.batch_bucket(1) == 8
    assert tune.batch_bucket(8) == 8
    assert tune.batch_bucket(9) == 16
    assert tune.batch_bucket(100) == 128
    with pytest.raises(ValueError):
        tune.batch_bucket(0)


# -------------------------------------------- integration: auto width
def test_auto_aligner_bit_identical_to_pinned(data, mem_cache):
    q, r = data
    m = MetricsRegistry()
    auto = repro.Aligner(r, backend="kernel", segment_width="auto",
                         interpret=True, metrics=m,
                         tune_options={"budget": tune.TuneBudget(
                             max_trials=3, warmup=0, runs=1)})
    res = auto(q, outputs=("cost", "start", "end"))
    assert m.value("tune.trials") > 0
    for w in WIDTHS:
        pin = repro.Aligner(r, backend="kernel", segment_width=w,
                            interpret=True)
        ref = pin(q, outputs=("cost", "start", "end"))
        np.testing.assert_array_equal(np.asarray(res.cost),
                                      np.asarray(ref.cost))
        np.testing.assert_array_equal(np.asarray(res.end),
                                      np.asarray(ref.end))
        np.testing.assert_array_equal(np.asarray(res.start),
                                      np.asarray(ref.start))


def test_auto_aligner_warm_cache_zero_trials(tmp_path, data):
    q, r = data
    path = str(tmp_path / "t.json")
    budget = tune.TuneBudget(max_trials=2, warmup=0, runs=1)
    m1 = MetricsRegistry()
    a1 = repro.Aligner(r, backend="kernel", segment_width="auto",
                       interpret=True, metrics=m1,
                       tune_options={"budget": budget,
                                     "cache": tune.TuningCache(path)})
    r1 = a1(q)
    assert m1.value("tune.trials") > 0
    # "second process": a fresh Aligner + fresh cache object over the
    # same file performs zero timing trials
    m2 = MetricsRegistry()
    a2 = repro.Aligner(r, backend="kernel", segment_width="auto",
                       interpret=True, metrics=m2,
                       tune_options={"budget": budget,
                                     "cache": tune.TuningCache(path)})
    r2 = a2(q)
    assert m2.value("tune.trials") == 0
    assert m2.value("tune.cache_hits") == 1
    np.testing.assert_array_equal(np.asarray(r1.cost),
                                  np.asarray(r2.cost))
    # the tuned width is memoized per workload key: a second batch of
    # the same shape consults neither the tuner nor the cache again
    a2(q)
    assert m2.value("tune.cache_hits") == 1


def test_auto_sdtw_front_door(data, mem_cache):
    q, r = data
    res = repro.sdtw(q, r, segment_width="auto", interpret=True)
    ref = repro.sdtw(q, r, backend="engine")
    np.testing.assert_allclose(np.asarray(res.cost), np.asarray(ref.cost),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="auto"):
        repro.sdtw(q, r, segment_width="fastest")
    with pytest.raises(ValueError, match="auto"):
        repro.Aligner(r, segment_width="fastest")


def test_auto_width_non_kernel_backend_skips_tuning(data, mem_cache):
    q, r = data
    m = MetricsRegistry()
    a = repro.Aligner(r, backend="engine", segment_width="auto",
                      metrics=m)
    a(q)
    assert m.value("tune.trials") == 0
    assert a.resolved_width(q.shape) == ops.DEFAULT_SEGMENT_WIDTH


def test_registry_select_consults_verdict(data, mem_cache):
    """A measured verdict re-ranks auto-selection: after the tuner
    records that the kernel won this workload, backend=None lands on
    the kernel (on CPU the static priority would pick the engine)."""
    from repro.backends import registry
    _, r = data
    spec = DPSpec()
    times = {"engine": 5.0, "kernel:w8": 1.0}
    tune.autotune(r, m=20, batch=5, spec=spec, interpret=True,
                  metrics=MetricsRegistry(), timer=fake_timer(times))
    backend, _ = registry.select(spec, workload=(20, 700, 5))
    assert backend.name == "kernel"
    # an untuned workload still follows static priority
    backend, _ = registry.select(spec, workload=(21, 700, 5))
    assert backend.name == "engine"


def test_layout_requires_width_under_auto(data, mem_cache):
    _, r = data
    a = repro.Aligner(r, backend="kernel", segment_width="auto",
                      interpret=True)
    with pytest.raises(ValueError, match="auto"):
        a.layout()
    assert a.layout(segment_width=4).shape[1] == 4


# -------------------------------------- recurrence families in the key
def test_workload_key_family_component():
    """Two recurrence families over identical (m, n, bucket, outputs)
    tune independently: the family is spelled in the workload key."""
    from repro.core.spec import resolve_spec
    shapes = dict(m=512, n=2000, batch_bucket=8,
                  outputs=frozenset({"cost", "end"}))
    keys = {fam: tune.workload_key(spec=resolve_spec(None, family=fam),
                                   **shapes)
            for fam in ("sdtw", "twed", "erp", "local")}
    assert len(set(keys.values())) == 4
    # sdtw keys keep their historical (pre-family) form: existing
    # tuning caches stay warm across the upgrade
    assert "fam=" not in keys["sdtw"]
    for fam in ("twed", "erp", "local"):
        assert f"fam={fam}|" in keys[fam]


def test_family_cache_sections_distinct(data):
    """Regression: a twed tune and an sdtw tune of the SAME shapes land
    in distinct cache entries, each answering warm with its own
    verdict."""
    from repro.core.spec import resolve_spec
    _, r = data
    cache = tune.TuningCache(None)
    sdtw_spec = resolve_spec(None)
    twed_spec = resolve_spec(None, family="twed")
    tune.autotune(r, m=20, batch=5, spec=sdtw_spec, candidates=WIDTHS,
                  interpret=True, cache=cache, metrics=MetricsRegistry(),
                  timer=fake_timer({"engine": 5.0, "kernel:w8": 3.0,
                                    "kernel:w4": 1.0}))
    tune.autotune(r, m=20, batch=5, spec=twed_spec, candidates=WIDTHS,
                  interpret=True, cache=cache, metrics=MetricsRegistry(),
                  timer=fake_timer({"engine": 5.0, "kernel:w8": 3.0,
                                    "kernel:w14": 1.0}))
    assert len(cache) == 2
    req = frozenset({"cost", "end"})
    k_sdtw = cache.key(spec=sdtw_spec, m=20, n=len(r), batch_bucket=8,
                       outputs=req)
    k_twed = cache.key(spec=twed_spec, m=20, n=len(r), batch_bucket=8,
                       outputs=req)
    assert cache.get(k_sdtw)["segment_width"] == 4
    assert cache.get(k_twed)["segment_width"] == 14
    # both answer warm from their own section
    for spec, width in ((sdtw_spec, 4), (twed_spec, 14)):
        m = MetricsRegistry()
        res = tune.autotune(r, m=20, batch=5, spec=spec,
                            candidates=WIDTHS, interpret=True,
                            cache=cache, metrics=m,
                            timer=fake_timer({}))
        assert res.from_cache and res.segment_width == width
        assert m.value("tune.trials") == 0


# ------------------------------------------------- cross-shape seeding
def test_cross_shape_seeding(data):
    """A cold tune of a NEARBY shape starts the hill-climb at the
    cached winner's width (tune.seeded_starts), while the default
    width still gets measured."""
    _, r = data
    cache = tune.TuningCache(None)
    times = {"engine": 5.0, "kernel:w8": 3.0, "kernel:w4": 2.0,
             "kernel:w2": 2.5, "kernel:w14": 4.0}
    m1 = MetricsRegistry()
    res1 = tune.autotune(r, m=20, batch=5, candidates=WIDTHS,
                         interpret=True, cache=cache, metrics=m1,
                         timer=fake_timer(times))
    assert (res1.segment_width, m1.value("tune.seeded_starts")) == (4, 0)
    # same spec+outputs, nearby m: the climb starts at w=4, not w=8
    m2 = MetricsRegistry()
    timer = fake_timer(times)
    res2 = tune.autotune(r, m=24, batch=5, candidates=WIDTHS,
                         interpret=True, cache=cache, metrics=m2,
                         timer=timer)
    assert m2.value("tune.seeded_starts") == 1
    assert res2.segment_width == 4 and not res2.from_cache
    kernel_calls = [c for c in timer.calls if c.startswith("kernel:")]
    assert kernel_calls[0] == "kernel:w4"
    assert "kernel:w8" in res2.measured     # default still measured


def test_seeding_skips_other_spec_and_outputs(data):
    """Verdicts recorded for another family never seed this one: the
    reconstructed-key match must be exact."""
    from repro.core.spec import resolve_spec
    _, r = data
    times = {"engine": 5.0, "kernel:w8": 3.0, "kernel:w4": 2.0}
    cache = tune.TuningCache(None)
    tune.autotune(r, m=20, batch=5, spec=resolve_spec(None, family="erp"),
                  candidates=WIDTHS, interpret=True, cache=cache,
                  metrics=MetricsRegistry(), timer=fake_timer(times))
    m2 = MetricsRegistry()
    tune.autotune(r, m=24, batch=5, candidates=WIDTHS, interpret=True,
                  cache=cache, metrics=m2, timer=fake_timer(times))
    assert m2.value("tune.seeded_starts") == 0
