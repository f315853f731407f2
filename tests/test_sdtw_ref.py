"""The scan oracle (core.ref.sdtw_ref) against the brute-force numpy DP."""
import numpy as np
import pytest

from repro.core.ref import (dtw_global_numpy, sdtw_bottom_row, sdtw_numpy,
                            sdtw_ref)
from repro.core.spec import DPSpec


@pytest.mark.parametrize("m,n", [(1, 1), (1, 7), (5, 5), (8, 3), (17, 53),
                                 (32, 128), (3, 200)])
def test_scan_oracle_matches_bruteforce(rng, m, n):
    B = 3
    q = rng.normal(size=(B, m)).astype(np.float32)
    r = rng.normal(size=(n,)).astype(np.float32)
    costs, ends = sdtw_ref(q, r)
    for b in range(B):
        c, e = sdtw_numpy(q[b], r)
        np.testing.assert_allclose(costs[b], c, rtol=1e-5, atol=1e-5)
        assert int(ends[b]) == e


@pytest.mark.parametrize("distance", ["sqeuclidean", "abs"])
@pytest.mark.parametrize("m,n", [(1, 7), (5, 5), (17, 53), (40, 300)])
def test_bottom_row_matches_bruteforce(rng, m, n, distance):
    spec = DPSpec(distance=distance)
    q = rng.normal(size=(3, m))
    r = rng.normal(size=(n,))
    last = sdtw_bottom_row(q, r, spec)
    assert last.shape == (3, n)
    for b in range(3):
        c, e = sdtw_numpy(q[b], r, spec)
        np.testing.assert_allclose(last[b].min(), c, rtol=1e-12,
                                   atol=1e-12)
        assert int(np.argmin(last[b])) == e


def test_bottom_row_refuses_soft_and_band():
    q, r = np.zeros((1, 4)), np.zeros(9)
    for spec in (DPSpec(reduction="softmin"), DPSpec(band=2)):
        with pytest.raises(ValueError, match="hard-min unbanded"):
            sdtw_bottom_row(q, r, spec)


def test_per_query_reference(rng):
    B, m, n = 4, 9, 31
    q = rng.normal(size=(B, m)).astype(np.float32)
    r = rng.normal(size=(B, n)).astype(np.float32)
    costs, ends = sdtw_ref(q, r)
    for b in range(B):
        c, e = sdtw_numpy(q[b], r[b])
        np.testing.assert_allclose(costs[b], c, rtol=1e-5, atol=1e-5)
        assert int(ends[b]) == e


def test_exact_submatch_is_zero(rng):
    r = rng.normal(size=(64,)).astype(np.float32)
    q = r[20:30]
    c, e = sdtw_numpy(q, r)
    assert c == 0.0 and e == 29


def test_sdtw_leq_global_dtw(rng):
    for _ in range(5):
        q = rng.normal(size=(12,))
        r = rng.normal(size=(40,))
        assert sdtw_numpy(q, r)[0] <= dtw_global_numpy(q, r) + 1e-9
