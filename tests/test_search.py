"""repro.search: envelope-bound admissibility, index caching, batcher
packing invariants, and SearchService exactness vs the brute-force loop
(including: pruning never discards a pair full sDTW would rank top-k)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.normalize import normalize_batch
from repro.core.ref import sdtw_ref
from repro import obs
from repro.core.spec import DEFAULT_SPEC, DPSpec
from repro.data.cbf import make_search_dataset
from repro.kernels.ops import swizzle_reference
from repro.kernels.sdtw_wavefront import SUBLANES
from repro.search import (QueryBatcher, ReferenceIndex, SearchConfig,
                          SearchService, brute_force_topk, grid_size,
                          lb_keogh_sdtw, lb_paa_sdtw, paa_envelopes,
                          prune_admissible, streaming_envelopes)


@pytest.fixture(scope="module")
def workload():
    refs, queries, labels = make_search_dataset(
        seed=3, n_refs=5, motifs_per_ref=8, n_queries=10, query_motifs=2)
    index = ReferenceIndex()
    for name, series in refs.items():
        index.add(name, series)
    return index, queries, labels


# ---------------------------------------------------------------- prune
def test_paa_envelopes_cover_blocks(rng):
    x = rng.normal(size=(3, 37)).astype(np.float32)     # ragged tail
    lo, hi = paa_envelopes(jnp.asarray(x), 8)
    assert lo.shape == hi.shape == (3, 5)
    for b in range(5):
        blk = x[:, b * 8:(b + 1) * 8]
        np.testing.assert_allclose(np.asarray(lo)[:, b], blk.min(axis=1))
        np.testing.assert_allclose(np.asarray(hi)[:, b], blk.max(axis=1))


@pytest.mark.parametrize("chunk", [1, 2, 7, 8, 16, 37, 64])
@pytest.mark.parametrize("shape", [(37,), (64,), (3, 37), (2, 5, 24)])
def test_streaming_envelopes_equal_paa(rng, chunk, shape):
    """The O(L) monotonic-deque build is bit-identical to the reshape
    build — ragged tails, chunk == length, and chunk > length
    included — so swapping it into ReferenceIndex changes nothing."""
    x = rng.normal(size=shape).astype(np.float32)
    lo_s, hi_s = streaming_envelopes(x, chunk)
    lo_p, hi_p = paa_envelopes(jnp.asarray(x), chunk)
    np.testing.assert_array_equal(np.asarray(lo_s), np.asarray(lo_p))
    np.testing.assert_array_equal(np.asarray(hi_s), np.asarray(hi_p))
    assert lo_s.dtype == lo_p.dtype


def test_streaming_envelopes_validation(rng):
    with pytest.raises(ValueError, match="chunk"):
        streaming_envelopes(rng.normal(size=(8,)), 0)
    with pytest.raises(ValueError, match="empty"):
        streaming_envelopes(np.zeros((0,)), 4)


def test_index_envelopes_use_streaming_build(rng):
    """ReferenceIndex's cached envelopes come from the deque build and
    match the reshape build on the test corpus."""
    r = rng.normal(size=(217,)).astype(np.float32)
    idx = ReferenceIndex(normalize=False)
    idx.add("a", r)
    lo, hi = idx.envelopes("a", 8)
    lo_p, hi_p = paa_envelopes(jnp.asarray(r), 8)
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(lo_p))
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(hi_p))


@pytest.mark.parametrize("chunks", [(1, 1), (1, 4), (2, 8), (5, 7)])
def test_lower_bounds_are_admissible(rng, chunks):
    """The cascade's soundness: every bound <= the true sDTW cost."""
    cq, cr = chunks
    q = normalize_batch(jnp.asarray(
        rng.normal(size=(6, 33)).astype(np.float32)))
    r = normalize_batch(jnp.asarray(
        rng.normal(size=(217,)).astype(np.float32)))
    true, _ = sdtw_ref(q, r)
    lb = lb_paa_sdtw(q, r, query_chunk=cq, ref_chunk=cr)
    assert (np.asarray(lb) <= np.asarray(true) + 1e-4).all()
    if cq == 1:
        rlo, rhi = paa_envelopes(r, cr)
        lb_fast = lb_keogh_sdtw(q, rlo, rhi)
        np.testing.assert_allclose(np.asarray(lb_fast), np.asarray(lb),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("chunks", [(1, 1), (1, 4), (2, 8)])
def test_cosine_lower_bound_is_admissible(rng, chunks):
    """The angular envelope bound: every cosine bound <= the true
    cosine sDTW cost (sign-aware, not gap-based)."""
    cq, cr = chunks
    spec = DPSpec(distance="cosine")
    q = normalize_batch(jnp.asarray(
        rng.normal(size=(6, 33)).astype(np.float32)))
    r = normalize_batch(jnp.asarray(
        rng.normal(size=(217,)).astype(np.float32)))
    true, _ = sdtw_ref(q, r, spec=spec)
    lb = lb_paa_sdtw(q, r, query_chunk=cq, ref_chunk=cr, spec=spec)
    assert (np.asarray(lb) <= np.asarray(true) + 1e-4).all()
    if cq == 1:
        rlo, rhi = paa_envelopes(r, cr)
        lb_fast = lb_keogh_sdtw(q, rlo, rhi, spec=spec)
        assert (np.asarray(lb_fast) <= np.asarray(true) + 1e-4).all()
    assert prune_admissible(spec)


def test_cosine_lower_bound_bites_on_sign_separated_series(rng):
    """Where the angular bound has teeth: a strictly negative query
    against a strictly positive reference costs ~2 per cell, and the
    envelope bound must see (most of) it — while staying admissible."""
    spec = DPSpec(distance="cosine")
    q = jnp.asarray(-(np.abs(rng.normal(size=(2, 16))) + 0.1)
                    .astype(np.float32))
    r = jnp.asarray((np.abs(rng.normal(size=(64,))) + 0.1)
                    .astype(np.float32))
    true, _ = sdtw_ref(q, r, spec=spec)
    rlo, rhi = paa_envelopes(r, 4)
    lb = np.asarray(lb_keogh_sdtw(q, rlo, rhi, spec=spec))
    assert (lb <= np.asarray(true) + 1e-4).all()
    assert (lb >= 16).all()          # ~1+ per query row, M = 16 rows


def test_lower_bound_exact_at_chunk_one(rng):
    """ref_chunk=1 envelopes degenerate to the series itself: the bound
    must equal the true sweep."""
    q = jnp.asarray(rng.normal(size=(4, 24)).astype(np.float32))
    r = jnp.asarray(rng.normal(size=(96,)).astype(np.float32))
    true, _ = sdtw_ref(q, r)
    rlo, rhi = paa_envelopes(r, 1)
    np.testing.assert_allclose(np.asarray(lb_keogh_sdtw(q, rlo, rhi)),
                               np.asarray(true), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------- index
def test_reference_index_caches_preps(rng):
    idx = ReferenceIndex()
    idx.add("a", rng.normal(size=(300,)).astype(np.float32))
    l1 = idx.layout("a", 4)
    assert idx.layout("a", 4) is l1                 # cached, not rebuilt
    assert idx.layout("a", 8) is not l1             # per segment_width
    e1 = idx.envelopes("a", 8)
    assert idx.envelopes("a", 8) is e1
    with pytest.raises(ValueError, match="already registered"):
        idx.add("a", rng.normal(size=(10,)))
    with pytest.raises(KeyError, match="unknown reference"):
        idx.get("zzz")
    with pytest.raises(ValueError, match="1-D"):
        idx.add("b", rng.normal(size=(3, 4)))


def test_reference_index_normalizes_once(rng):
    r = (rng.normal(size=(256,)) * 5 + 3).astype(np.float32)
    idx = ReferenceIndex(normalize=True)
    entry = idx.add("a", r)
    np.testing.assert_allclose(np.asarray(entry.series),
                               np.asarray(normalize_batch(jnp.asarray(r))),
                               rtol=1e-6)
    raw = ReferenceIndex(normalize=False).add("a", r)
    np.testing.assert_array_equal(np.asarray(raw.series), r)


def test_reference_index_registration_order(rng):
    idx = ReferenceIndex(normalize=False)
    series = {n: rng.normal(size=(40 + 10 * i,)).astype(np.float32)
              for i, n in enumerate(("b", "a", "c"))}
    assert idx.add_many(series.items()) is idx
    assert len(idx) == 3 and "a" in idx and "z" not in idx
    assert idx.names() == ["b", "a", "c"]          # registration order
    entries = idx.references()
    assert [e.name for e in entries] == ["b", "a", "c"]
    assert [e.order for e in entries] == [0, 1, 2]
    assert [e.length for e in entries] == [40, 50, 60]
    assert idx.spec == DEFAULT_SPEC


def test_reference_index_rejects_empty_reference():
    idx = ReferenceIndex()
    with pytest.raises(ValueError, match="empty"):
        idx.add("a", np.zeros((0,), np.float32))
    assert len(idx) == 0


def test_reference_index_layout_per_dtype_and_envelopes_per_chunk(rng):
    idx = ReferenceIndex()
    idx.add("a", rng.normal(size=(300,)).astype(np.float32))
    series = idx.get("a").series
    f32 = idx.layout("a", 4)
    np.testing.assert_array_equal(np.asarray(f32), np.asarray(
        swizzle_reference(series, 4)))
    bf16 = idx.layout("a", 4, jnp.bfloat16)
    assert bf16.dtype == jnp.bfloat16 and f32.dtype == jnp.float32
    assert idx.layout("a", 4, jnp.bfloat16) is bf16
    assert set(idx.get("a").layouts) == {(4, "float32"), (4, "bfloat16")}
    e4, e8 = idx.envelopes("a", 4), idx.envelopes("a", 8)
    assert (e4[0].shape[-1], e8[0].shape[-1]) == (75, 38)   # ceil(300/c)
    assert set(idx.get("a").envelopes) == {4, 8}


# -------------------------------------------------------------- batcher
@pytest.mark.parametrize("n, max_slots, want", [
    (1, 64, SUBLANES), (8, 64, 8), (9, 64, 16), (16, 64, 16),
    (17, 64, 32), (33, 64, 64), (33, 48, 48),
])
def test_grid_size(n, max_slots, want):
    """The smallest SUBLANES x 2^k that holds the batch, capped."""
    assert grid_size(n, max_slots) == want


def test_grid_size_rejects_more_than_max_slots():
    with pytest.raises(ValueError, match="exceeds max_slots"):
        grid_size(65, 64)


def test_batcher_records_fill_metrics(rng):
    metrics = obs.MetricsRegistry()
    b = QueryBatcher(max_slots=16, metrics=metrics)
    batches = b.pack([rng.normal(size=(20,)) for _ in range(19)],
                     ids=[f"q{i}" for i in range(19)])
    assert [x.n_real for x in batches] == [16, 3]
    assert batches[1].ids == ("q16", "q17", "q18")
    assert metrics.value("batcher.batches") == 2
    assert metrics.value("batcher.rows_real") == 19
    assert metrics.value("batcher.rows_padded") == 8 - 3
    fill = metrics.get("batcher.fill")
    assert fill.count == 2
    assert sorted(fill.summary()[k] for k in ("min", "max")) == [3 / 8, 1.0]


def test_batcher_buckets_and_grid(rng):
    b = QueryBatcher(max_slots=16)
    out = []
    for i in range(21):                      # two lengths interleaved
        out += b.add(i, rng.normal(size=(32 if i % 2 else 48,)))
    out += b.flush()
    assert b.pending() == 0
    by_len = {}
    for batch in out:
        by_len.setdefault(batch.length, []).append(batch)
        # fixed-shape discipline: batch dim on the SUBLANES x 2^k grid
        assert batch.queries.shape[0] == grid_size(batch.n_real, 16)
        assert batch.queries.shape[1] == batch.length
        # pad rows are zeros, real rows preserved
        np.testing.assert_array_equal(
            np.asarray(batch.queries[batch.n_real:]), 0.0)
    ids = sorted(i for batch in out for i in batch.ids)
    assert ids == list(range(21))            # every query exactly once
    assert sorted(by_len) == [32, 48]


def test_batcher_emits_full_buckets_eagerly(rng):
    b = QueryBatcher(max_slots=8)
    emitted = []
    for i in range(8):
        emitted += b.add(i, rng.normal(size=(16,)))
    assert len(emitted) == 1 and emitted[0].n_real == 8
    assert b.pending() == 0


def test_batcher_validation(rng):
    with pytest.raises(ValueError, match="multiple of SUBLANES"):
        QueryBatcher(max_slots=SUBLANES + 1)
    b = QueryBatcher()
    with pytest.raises(ValueError, match="1-D"):
        b.add(0, rng.normal(size=(2, 3)))
    with pytest.raises(ValueError, match="empty"):
        b.add(0, np.zeros((0,)))


# -------------------------------------------------------------- service
@pytest.mark.parametrize("backend", ["ref", "engine"])
@pytest.mark.parametrize("k", [1, 2])
def test_service_equals_brute_force(workload, backend, k):
    """The acceptance contract: same costs and end indices as a full
    repro.sdtw loop over all registered references — in particular the
    cascade never discards a pair the oracle would rank in the top-k."""
    index, queries, _ = workload
    for prune in (True, False):
        svc = SearchService(index, SearchConfig(backend=backend,
                                                prune=prune))
        got = svc.topk(queries, k=k)
        want = brute_force_topk(index, queries, k=k, backend=backend)
        assert got == want
        st = svc.stats
        assert st.pairs == len(queries) * len(index)
        assert st.dp_pairs + st.skipped == st.pairs
        if not prune:
            assert st.skipped == 0


def test_service_kernel_backend(workload):
    index, queries, _ = workload
    svc = SearchService(index, SearchConfig(backend="kernel"))
    got = svc.topk(queries[:4], k=1)
    want = brute_force_topk(index, queries[:4], k=1, backend="kernel")
    assert got == want


def test_service_variable_length_queries(workload):
    index, queries, _ = workload
    mixed = [queries[0], queries[1][:200], queries[2][:200], queries[3]]
    svc = SearchService(index, SearchConfig(backend="engine"))
    got = svc.topk(mixed, k=2)
    want = brute_force_topk(index, mixed, k=2, backend="engine")
    assert got == want


def test_service_prunes_search_workload(workload):
    """k=1 on the CBF search workload: the cascade must skip a sizable
    share of full sweeps (the benchmark's >= 30% acceptance bar)."""
    index, queries, labels = workload
    svc = SearchService(index, SearchConfig(backend="engine"))
    matches = svc.topk(queries, k=1)
    assert svc.stats.skip_fraction >= 0.3
    hits = sum(m[0].reference == labels[i] for i, m in enumerate(matches))
    assert hits == len(queries)


@pytest.mark.parametrize("backend,spec", [
    ("engine", DPSpec(distance="abs")),            # new distance, pruned
    ("kernel", DPSpec(distance="abs")),            # ... through the kernel
    ("engine", DPSpec(band=900)),                  # banded hard-min, pruned
    ("engine", DPSpec(distance="cosine")),         # angular-bound pruned
    ("engine", DPSpec(reduction="softmin", gamma=1.0, band=900)),
], ids=["abs-engine", "abs-kernel", "banded-engine", "cosine-engine",
        "soft-banded-engine"])
def test_service_spec_combinations_equal_brute_force(workload, backend,
                                                     spec):
    """The spec layer's end-to-end contract: top-k search stays exact
    for the spec'd recurrence under new distances, banding and soft-min
    — with the cascade auto-disabled where its bounds are inadmissible."""
    index, queries, _ = workload
    svc = SearchService(index, SearchConfig(backend=backend, spec=spec))
    assert svc.prune_active == prune_admissible(spec)
    got = svc.topk(queries[:4], k=2)
    want = brute_force_topk(index, queries[:4], k=2, backend=backend,
                            spec=spec)
    assert got == want
    st = svc.stats
    assert st.dp_pairs + st.skipped == st.pairs


def test_index_spec_is_service_default(workload):
    """An index built for a matching regime carries it: the service
    falls back to index.spec when the config doesn't override."""
    index, queries, _ = workload
    spec = DPSpec(distance="abs")
    idx2 = ReferenceIndex(spec=spec)
    for e in index.references():
        idx2.add(e.name, e.series)
    # idx2 already normalized the entries once; re-normalizing is a no-op
    svc = SearchService(idx2, SearchConfig(backend="engine"))
    assert svc.spec == spec
    got = svc.topk(queries[:3], k=1)
    want = brute_force_topk(idx2, queries[:3], k=1, backend="engine",
                            spec=spec)
    assert got == want


def test_service_rejects_incapable_backend(workload):
    index, _, _ = workload
    with pytest.raises(ValueError, match="does not support distance"):
        SearchService(index, SearchConfig(
            backend="kernel", spec=DPSpec(distance="cosine")))
    # soft-min runs on the kernel since the carry-channel executor,
    # but soft WINDOWS stay impossible (no argmin path)
    with pytest.raises(ValueError, match="soft-min"):
        SearchService(index, SearchConfig(
            backend="kernel", spec=DPSpec(reduction="softmin"),
            windows=True))
    with pytest.raises(ValueError, match="distributed"):
        SearchService(index, SearchConfig(backend="distributed"))


def test_service_quantized_backend_equals_brute_force(workload):
    """Backends without per-query reference batching (the quantized
    codebook is built per reference) must sweep one reference per
    dispatch — and their approximation makes the cascade's exact-DP
    bounds inadmissible, so pruning must stay off."""
    index, queries, _ = workload
    svc = SearchService(index, SearchConfig(backend="quantized"))
    assert not svc.prune_active          # approximate backend: no pruning
    got = svc.topk(queries[:3], k=2)
    want = brute_force_topk(index, queries[:3], k=2, backend="quantized")
    assert got == want


_DIST_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from repro.search import (ReferenceIndex, SearchConfig, SearchService,
                          brute_force_topk)

rng = np.random.default_rng(7)
mesh = jax.make_mesh((2, 4), ("data", "model"))
index = ReferenceIndex()
for i in range(5):                 # N=512 divides the 4 model shards
    index.add(f"t{i}", rng.normal(size=(512,)).astype(np.float32))
queries = rng.normal(size=(8, 64)).astype(np.float32)

with mesh:
    svc = SearchService(index, SearchConfig(
        backend="distributed", options={"mesh": mesh, "row_block": 8}))
    got = svc.topk(queries, k=2)
want = brute_force_topk(index, queries, k=2, backend="engine")
assert got == want, (got[0], want[0])
assert svc.stats.dp_pairs + svc.stats.skipped == svc.stats.pairs
print("DIST-SEARCH-OK")
"""


def test_service_distributed_backend_via_mesh_options():
    """The ROADMAP item: SearchConfig(options={'mesh': ...}) routes the
    service's full sweeps through the distributed shard_map pipeline —
    results identical to the single-device engine brute force.  Runs in
    a subprocess (device count must be fixed before jax init)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _DIST_SCRIPT], env=env,
                         capture_output=True, text=True,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DIST-SEARCH-OK" in out.stdout


def test_service_distributed_without_mesh_errors(workload):
    index, _, _ = workload
    with pytest.raises(ValueError, match="mesh"):
        SearchService(index, SearchConfig(backend="distributed"))


def test_service_validation(workload, rng):
    index, queries, _ = workload
    svc = SearchService(index, SearchConfig())
    with pytest.raises(ValueError, match="k must be"):
        svc.topk(queries, k=0)
    with pytest.raises(ValueError, match="empty query batch"):
        svc.topk([])
    with pytest.raises(ValueError, match="1-D"):
        svc.topk([rng.normal(size=(2, 3))])
    with pytest.raises(ValueError, match="no references"):
        SearchService(ReferenceIndex(), SearchConfig()).topk(queries)
    with pytest.raises(ValueError, match="normalize"):
        SearchService(index, SearchConfig(normalize=False))
