"""The multivariate cell ``sws2013_qbe.terms`` at tiny sizes on the CPU
(the kernel interpreted): a sound run is correct, a traced run reads
exactly its per-layer metrics, its reader finds nothing where the
program records nothing, and the plain reference it is checked against
agrees with a row-by-row sweep of its own."""
import types

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import layout, reference_features, run
from chipbench import trace as tr

CELL = "sws2013_qbe.terms"
SEED = 2**31 + 29
TINY = {
    "config": {"backend": "kernel", "check_sample": 8,
               "references": {"count": 1, "length": 2500,
                              "process": "random_walk", "seed": 3}},
    "traffic": {"batch": 8, "query_len": 12, "pool": 2},
}
FIXTURE = layout.HERE / "tests" / "data" / "small_trace.xplane.pb"
STAND_IN_PEAKS = {"vpu_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                  "mxu_bf16_flops_per_s": 1e13}


def _overrides():
    return {part: dict(v) for part, v in TINY.items()}


def test_a_tiny_run_is_correct():
    res = run.run_cell(CELL, SEED, 0.2, False, require_tpu=False,
                       overrides=_overrides())
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"align_gcells_per_s", "setup_s"}
    assert res["window"]["programs_built_in_window"] == 0
    w = res["window"]
    assert w["kernel.wavefront.dispatches"] == w["calls"] > 0
    assert w["kernel.wavefront.cells_real"] == w["calls"] * 8 * 12 * 2500
    assert w["kernel.wavefront.feature_cells"] == \
        39 * w["kernel.wavefront.cells_real"]
    for c in res["checks"].values():
        assert 0 <= c["value"] <= c["limit"]


def test_a_traced_run_reads_exactly_its_metrics(monkeypatch):
    """The profiler writes no TPU plane on the CPU, so the reduction is
    handed the small trace recorded on the chip; the counters are the
    tiny run's, the peaks stand-ins."""
    recorded = tr.load(FIXTURE)
    monkeypatch.setattr(tr, "load", lambda path: recorded)
    res = run.run_cell(CELL, SEED, 0.2, True, require_tpu=False,
                       overrides=_overrides(), peaks=STAND_IN_PEAKS)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"wavefront_feature_roofline.align",
                                   "device_idle_share.align"}
    assert res["metrics"]["wavefront_feature_roofline.align"]["value"] > 0


def _ctx(kernel_ns=2e9, cells=10**9, features=39, nbytes=4e9,
         peaks=STAND_IN_PEAKS):
    summary = tr.Summary(window_ns=3e9, busy_ns=3e9,
                         kernel_ns={"wavefront": kernel_ns} if kernel_ns
                         else {}, top_ops=[], idle_by_host=[], devices=1)
    counters = {} if cells is None else {
        "kernel.wavefront.cells_real": cells,
        "kernel.wavefront.feature_cells": features * cells}
    return types.SimpleNamespace(trace=summary, counters=counters,
                                 work={"cells": 1, "bytes": nbytes},
                                 peaks=peaks)


def test_the_reader():
    read = layout.load_cell(CELL).reader(
        "wavefront_feature_roofline.align").read
    # ops: 3e9 / 1e12 = 3 ms; MXU: 2 * 39e9 / 1e13 = 7.8 ms; bytes:
    # 4e9 / 1e11 = 40 ms, the longest, over 2 s of kernel time
    assert read(_ctx()) == pytest.approx(100 * 0.040 / 2.0)
    assert read(_ctx(nbytes=4e8)) == pytest.approx(100 * 0.0078 / 2.0)
    assert read(_ctx(kernel_ns=0)) is None
    assert read(_ctx(cells=None)) is None
    no_mxu = {k: v for k, v in STAND_IN_PEAKS.items()
              if k != "mxu_bf16_flops_per_s"}
    assert read(_ctx(peaks=no_mxu)) is None      # a CPU has no MXU peak


@pytest.mark.parametrize("block", [64, 4096])
def test_the_reference_agrees_with_a_sequential_sweep(block):
    rng = np.random.default_rng(5)
    q = reference_features.znorm_time(
        np.cumsum(rng.normal(size=(3, 7, 4)), axis=1)).astype(np.float32)
    r = reference_features.znorm_archive(
        np.cumsum(rng.normal(size=(300, 4)), axis=0))
    c = ((q[:, :, None, :].astype(np.float64)
          - r[None, None].astype(np.float64)) ** 2).sum(-1)   # (P, M, N)
    d = c[:, 0]
    for i in range(1, 7):
        row = np.full_like(d, np.inf)
        for j in range(300):
            left = row[:, j - 1] if j else np.inf
            upleft = d[:, j - 1] if j else np.inf
            row[:, j] = c[:, i, j] + np.minimum(np.minimum(left, d[:, j]),
                                                upleft)
        d = row
    target = np.array([0, 150, -1], np.int32)
    best, arg, at = reference_features.sweep(q, r, target, block=block)
    np.testing.assert_allclose(best, d.min(1), rtol=1e-5)
    np.testing.assert_array_equal(arg, d.argmin(1))
    np.testing.assert_allclose(at[:2], d[[0, 1], target[:2]], rtol=1e-5)
    assert np.isinf(at[2])
    low, _, _ = reference_features.sweep(q, r, dtype=jnp.bfloat16,
                                         block=block)
    assert np.max(np.abs(low - best) / best) > 1e-3
