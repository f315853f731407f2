"""Multivariate sDTW: (B, M, D) queries against an (N, D) reference on
the normal path, the kernel interpreted on the CPU.

The cell cost adds the per-feature costs; normalization is per feature
over time; one feature is exactly the univariate path; what declines
feature inputs does so with a capability error; and a session's
reference is an argument of its program, so sessions over references
of one shape share one program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import obs
from repro.backends import registry
from repro.core import ref
from repro.core.normalize import normalize_batch, normalize_reference
from repro.core.spec import DPSpec
from repro.dp.oracle import dp_oracle
from repro.kernels import ops


def _walks(rng, shape):
    return np.cumsum(rng.normal(size=shape), axis=-2).astype(np.float32)


def _data(b, m, n, d, seed=0):
    rng = np.random.default_rng(seed)
    return _walks(rng, (b, m, d)), _walks(rng, (n, d))


def _cmvn(x):
    """Per-feature mean and variance normalization over time, float64."""
    x = np.asarray(x, np.float64)
    return (x - x.mean(axis=-2, keepdims=True)) / x.std(axis=-2,
                                                        keepdims=True)


# ------------------------------------------------------------- parity
# lanes a chain runs at each batch (ops.plan_rows): one chain of 128 up
# to 16 queries, then 2, 4 and 8 chains as the batch fills them
CHAIN_LANES_AT = {8: 128, 16: 128, 24: 64, 32: 64, 64: 32, 128: 16}


def _cost_source(m, d):
    """Where each case's cell costs come from: the MXU's cost tile from
    5 features on (ops.COST_TILE_MIN_FEATURES), unless the tile outgrows
    ops.COST_TILE_VMEM_BUDGET, as 4,000-frame queries make it; else the
    VPU inside each step."""
    return "tile" if d >= 5 and m < 4_000 else "vpu"


@pytest.mark.parametrize("b, m, n, d", [
    (8, 13, 700, 2),        # one group; 700 columns pad to 3 blocks
    (16, 9, 300, 13),       # two groups, one step of 16 rows
    (24, 7, 260, 39),       # three groups: a pad group of zeros
    # D = 2, 5, 39 x chains of 128, 64, 32 and 16 lanes
    (32, 7, 260, 2), (64, 7, 260, 2), (128, 7, 260, 2),
    (16, 7, 260, 5), (24, 7, 260, 5), (64, 7, 260, 5), (128, 7, 260, 5),
    (8, 7, 260, 39), (64, 7, 260, 39), (128, 7, 260, 39),
    (8, 4_000, 200, 39),    # the tile outgrows its budget: the VPU
])
def test_kernel_ref_and_oracle_agree(b, m, n, d):
    plan = ops.kernel_plan(DPSpec(), m=m, n=n, segment_width=2, batch=b,
                           features=d, with_window=True)
    assert plan.lanes_per_chain == CHAIN_LANES_AT[b]
    assert ("tile" if plan.cost_tile else "vpu") == _cost_source(m, d)
    q, r = _data(b, m, n, d, seed=d)
    qn, rn = normalize_batch(jnp.asarray(q)), normalize_reference(
        jnp.asarray(r))
    kern = repro.sdtw(q, r, backend="kernel", segment_width=2,
                      outputs=("cost", "start", "end"))
    want = repro.sdtw(q, r, backend="ref", outputs=("cost", "start", "end"))
    np.testing.assert_allclose(np.asarray(kern.cost), np.asarray(want.cost),
                               rtol=1e-5)
    for name in ("start", "end"):
        np.testing.assert_array_equal(np.asarray(getattr(kern, name)),
                                      np.asarray(getattr(want, name)))
    q64, r64 = np.asarray(qn, np.float64), np.asarray(rn, np.float64)
    for i in range(0, b, 7):
        cost, end = dp_oracle(q64[i], r64, DPSpec())
        assert np.isclose(float(kern.cost[i]), cost, rtol=1e-5)
        assert int(kern.end[i]) == end


def test_band_skip_and_abs_run_on_features():
    q, r = _data(16, 12, 1100, 5, seed=3)
    for spec in (DPSpec(band=40), DPSpec(distance="abs")):
        got = ops.sdtw_wavefront(q, r, segment_width=2, spec=spec)
        want = ref.sdtw_ref(jnp.asarray(q), jnp.asarray(r), spec=spec)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))
    plan = ops.kernel_plan(DPSpec(band=40), m=12, n=1100, segment_width=2,
                           features=5)
    assert plan.skipped_blocks > 0


def test_ref_soft_min_on_features_matches_the_oracle():
    q, r = _data(3, 6, 40, 4, seed=5)
    spec = DPSpec(reduction="softmin", gamma=0.5)
    got = repro.sdtw(q, r, backend="ref", spec=spec, normalize=False)
    for i in range(3):
        cost, _ = dp_oracle(q[i], r, spec)
        assert np.isclose(float(got.cost[i]), cost, rtol=1e-5)
    # a soft-min feature session is differentiable through the ref
    grad = jax.grad(lambda x: repro.sdtw(
        x, r, backend="ref", spec=spec).cost.sum())(jnp.asarray(q))
    assert grad.shape == q.shape and np.all(np.isfinite(grad))


def test_oracles_add_the_feature_costs():
    """D copies of one feature cost D times the univariate alignment,
    ending where it ends."""
    rng = np.random.default_rng(1)
    q, r = rng.normal(size=(2, 5)), rng.normal(size=(2, 9))
    qd, rd = np.repeat(q[..., None], 3, -1), np.repeat(r[0][:, None], 3, -1)
    cost, end = ref.sdtw_numpy(q[0], r[0])
    cost3, end3 = ref.sdtw_numpy(qd[0], rd)
    assert np.isclose(cost3, 3 * cost) and end3 == end
    np.testing.assert_allclose(ref.sdtw_bottom_row(qd, rd),
                               3 * ref.sdtw_bottom_row(q, r[0]))


# ------------------------------------------------------- one feature
@pytest.mark.parametrize("backend", ["ref", "kernel"])
def test_one_feature_is_the_univariate_path_bit_for_bit(backend):
    q, r = _data(9, 10, 300, 1, seed=7)
    outs = ("cost", "start", "end")
    three = repro.sdtw(q, r, backend=backend, segment_width=2, outputs=outs)
    two = repro.sdtw(q[..., 0], r[:, 0], backend=backend, segment_width=2,
                     outputs=outs)
    a3 = repro.Aligner(r, backend=backend, segment_width=2)(q)
    a2 = repro.Aligner(r[:, 0], backend=backend, segment_width=2)(q[..., 0])
    for name in outs:
        np.testing.assert_array_equal(np.asarray(getattr(three, name)),
                                      np.asarray(getattr(two, name)))
    for name in ("cost", "end"):
        np.testing.assert_array_equal(np.asarray(getattr(a3, name)),
                                      np.asarray(getattr(a2, name)))


# ------------------------------------------------------ normalization
def test_normalization_is_per_feature_over_time():
    q, r = _data(3, 50, 200, 4, seed=2)
    q[1, :, 2] = 100.0 * q[1, :, 2] + 30.0     # one feature on its own
    #                                            scale and offset
    np.testing.assert_allclose(np.asarray(normalize_batch(jnp.asarray(q))),
                               _cmvn(q), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(normalize_reference(jnp.asarray(r))), _cmvn(r),
        atol=2e-5)
    np.testing.assert_allclose(np.asarray(ops.normalize(q)), _cmvn(q),
                               atol=2e-5)
    # univariate batches keep their last-axis normalization
    np.testing.assert_array_equal(
        np.asarray(normalize_reference(jnp.asarray(r[:, 0]))),
        np.asarray(normalize_batch(jnp.asarray(r[:, 0]))))


# ------------------------------------------------- capability errors
@pytest.mark.parametrize("backend", ["engine", "quantized", "distributed"])
def test_backends_without_features_decline_them(backend):
    q, r = _data(2, 5, 30, 3)
    with pytest.raises(ValueError, match="does not support multivariate"):
        repro.sdtw(q, r, backend=backend)
    with pytest.raises(ValueError, match="does not support multivariate"):
        repro.Aligner(r, backend=backend)
    assert not registry.supports(backend, DPSpec(), features=3)


@pytest.mark.parametrize("kw, match", [
    ({"family": "twed"}, "family 'twed' on multivariate"),
    ({"family": "erp"}, "family 'erp' on multivariate"),
    ({"family": "local"}, "family 'local' on multivariate"),
    ({"gamma": 0.5}, "softmin on multivariate"),
    ({"distance": "cosine"}, "cosine' on multivariate"),
    ({"outputs": ("cost", "path")}, r"\['path'\] on multivariate"),
])
def test_kernel_declines_what_it_does_not_serve_on_features(kw, match):
    q, r = _data(2, 5, 30, 3)
    with pytest.raises(ValueError, match=match):
        repro.sdtw(q, r, backend="kernel", **kw)


def test_declines_outside_the_registry():
    q, r = _data(2, 5, 30, 3)
    spec = DPSpec(reduction="softmin", gamma=0.5)
    with pytest.raises(ValueError, match="soft_alignment"):
        repro.sdtw(q, r, backend="ref", spec=spec,
                   outputs=("soft_alignment",))
    with pytest.raises(ValueError, match="'auto' tunes univariate"):
        repro.sdtw(q, r, segment_width="auto")
    with pytest.raises(ValueError, match="'auto' tunes univariate"):
        repro.Aligner(r, segment_width="auto")
    with pytest.raises(ValueError, match="3 features, the reference 2"):
        repro.sdtw(q, r[:, :2])
    with pytest.raises(ValueError, match="univariate"):
        ref.sdtw_ref(jnp.asarray(q), jnp.asarray(r),
                     spec=DPSpec(family="twed"))
    with pytest.raises(ValueError, match="multivariate plans"):
        ops.kernel_plan(DPSpec(family="erp"), m=5, n=30, features=3)


def test_search_and_serving_decline_features():
    from repro.search import (QueryBatcher, ReferenceIndex, SearchConfig,
                              SearchService)
    from repro.serve.stream import StreamServer
    q, r = _data(2, 5, 30, 3)
    index = ReferenceIndex()
    with pytest.raises(ValueError, match="search and serving are "
                                         "univariate"):
        index.add("a", r)
    index.add("a", r[:, 0])
    with pytest.raises(ValueError, match="univariate"):
        SearchService(index, SearchConfig()).topk(q)
    with pytest.raises(ValueError, match="univariate"):
        QueryBatcher().add(0, q[0])
    with StreamServer(index) as srv:
        with pytest.raises(ValueError, match="univariate"):
            srv.submit(q[0])


# ---------------------------------------------- sessions and counters
def _compiles():
    from jax._src import dispatch
    seen = []

    def listen(event, duration, **kw):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            seen.append(event)
    jax.monitoring.register_event_duration_secs_listener(listen)
    return seen


def test_sessions_over_references_of_one_shape_share_one_program():
    q, r1 = _data(8, 11, 400, 6, seed=11)
    _, r2 = _data(8, 11, 400, 6, seed=12)
    a1 = repro.Aligner(r1, backend="kernel", segment_width=2)
    a2 = repro.Aligner(r2, backend="kernel", segment_width=2)
    res1 = a1(q)
    seen = _compiles()
    res2 = a2(q)
    assert seen == [], "the second session built a program of its own"
    assert a1.stats.traces == 1 and a2.stats.traces == 0
    assert a2.stats.compiles == 1 and a2(q).cost.shape == (8,)
    assert not np.array_equal(np.asarray(res1.cost), np.asarray(res2.cost))
    np.testing.assert_allclose(
        np.asarray(res2.cost),
        np.asarray(repro.sdtw(q, r2, backend="ref").cost), rtol=1e-5)
    # the layout is an argument: no constant of its shape in the program
    text = a2.hlo_texts()[0]
    layout = "f32[2,6,2,128]"
    assert layout in text
    assert not [ln for ln in text.splitlines()
                if layout in ln and "constant(" in ln]


def test_feature_cells_count_d_times_the_cells():
    q, r = _data(9, 8, 300, 13, seed=4)
    obs.reset()
    tracer = obs.Tracer()
    aligner = repro.Aligner(r, backend="kernel", segment_width=2,
                            tracer=tracer, metrics=obs.MetricsRegistry())
    aligner(q)
    reg = obs.default_registry()
    cells = reg.value("kernel.wavefront.cells_real")
    assert cells == 9 * 8 * 300
    assert reg.value("kernel.wavefront.feature_cells") == 13 * cells
    work = ops.wavefront_work(batch=9, m=8, n=300, segment_width=2,
                              features=13)
    assert work["feature_cells"] == 13 * work["cells_real"]
    layout = [e for e in tracer.events if e["name"] == "aligner.layout"]
    assert [e["args"]["step"] for e in layout] == ["normalize", "swizzle"]


def test_cost_tile_dispatches_count_the_tiled_plans():
    """``kernel.wavefront.cost_tile_dispatches`` counts the dispatches
    whose cell costs the MXU built: every one of a multivariate plan
    that takes the tile, none of a univariate one or of one that keeps
    the VPU's cost."""
    reg = obs.default_registry()

    def dispatched(q, r):
        obs.reset()
        repro.Aligner(r, backend="kernel", segment_width=2,
                      metrics=obs.MetricsRegistry())(q)
        return (reg.value("kernel.wavefront.dispatches"),
                reg.value("kernel.wavefront.cost_tile_dispatches"))

    q, r = _data(9, 8, 300, 13, seed=4)
    assert dispatched(q, r) == (1, 1)                    # the tile
    assert dispatched(q[..., 0], r[:, 0]) == (1, 0)      # univariate
    assert dispatched(q[..., :2], r[:, :2]) == (1, 0)    # 2 features
    q, r = _data(8, 4_000, 200, 39, seed=6)
    assert dispatched(q, r) == (1, 0)                    # over budget
    # sws2013_qbe's plan takes the tile on every dispatch
    assert ops.wavefront_work(batch=32, m=100, n=7_200_000, segment_width=8,
                              features=39)["cost_tile"] == 1
