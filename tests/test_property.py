"""Hypothesis property tests on sDTW / normalizer invariants."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import example, given, settings, strategies as st
import hypothesis.extra.numpy as hnp

from repro.core.engine import sdtw_engine
from repro.core.normalize import normalize_batch
from repro.core.ref import sdtw_numpy, dtw_global_numpy
from repro.core.softdtw import sdtw_soft

finite = st.floats(-50, 50, allow_nan=False, width=32)


def series(min_len=1, max_len=24):
    return hnp.arrays(np.float32, st.integers(min_len, max_len),
                      elements=finite)


@settings(max_examples=30, deadline=None)
@given(q=series(2, 16), r=series(4, 48))
def test_sdtw_leq_global(q, r):
    assert sdtw_numpy(q, r)[0] <= dtw_global_numpy(q, r) + 1e-6


@settings(max_examples=30, deadline=None)
@given(q=series(1, 12), r=series(2, 32))
def test_nonnegative_and_engine_matches(q, r):
    c, e = sdtw_numpy(q, r)
    assert c >= 0
    ce, ee = sdtw_engine(q[None], r)
    np.testing.assert_allclose(np.asarray(ce)[0], c, rtol=1e-4, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(r=series(8, 48), start=st.integers(0, 4), ln=st.integers(2, 6))
def test_self_subsequence_zero(r, start, ln):
    q = r[start:start + ln]
    c, _ = sdtw_numpy(q, r)
    assert abs(c) < 1e-6


@settings(max_examples=20, deadline=None)
@given(q=series(2, 10), r=series(4, 24), shift=finite,
       scale=st.floats(0.125, 10, width=32))
def test_znorm_shift_scale_invariance(q, r, shift, scale):
    """z-normalized sDTW is invariant to affine rescale of the inputs
    (the reason the paper normalizes at all). (Numerically) constant
    series hit the eps-clamped variance and are inherently not
    affine-invariant — excluded."""
    from hypothesis import assume
    assume(float(np.std(q)) > 1e-3 * (1.0 + float(np.max(np.abs(q)))))
    qn = np.asarray(normalize_batch(q[None]))[0]
    qn2 = np.asarray(normalize_batch((q * scale + shift)[None]))[0]
    np.testing.assert_allclose(qn, qn2, atol=1e-3)


@settings(max_examples=20, deadline=None)
@given(q=series(2, 10), r=series(4, 24))
def test_batch_permutation_equivariance(q, r):
    batch = np.stack([q, q[::-1].copy(), np.roll(q, 1)])
    c, e = sdtw_engine(batch, r)
    perm = np.array([2, 0, 1])
    cp, ep = sdtw_engine(batch[perm], r)
    np.testing.assert_allclose(np.asarray(cp), np.asarray(c)[perm],
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ep), np.asarray(e)[perm])


@settings(max_examples=15, deadline=None)
@given(q=series(2, 8), r=series(4, 16))
def test_softdtw_lower_bounds_hard(q, r):
    """softmin <= min  =>  soft-sDTW <= hard sDTW (elementwise)."""
    hard = sdtw_numpy(q, r)[0]
    soft = float(np.asarray(sdtw_soft(q[None], r, gamma=0.5))[0])
    assert soft <= hard + 1e-3


@settings(max_examples=15, deadline=None)
@given(q=series(2, 8), r=series(4, 16))
@example(q=np.zeros(6, np.float32), r=np.zeros(9, np.float32))
def test_softdtw_gamma_to_zero_recovers_hard(q, r):
    """Soft-sDTW sits below hard sDTW by at most gamma x log(number of
    optimal alignments): ties in every cell (all zeros) give about
    26,000 at these lengths, so gamma is 1e-4, where that gap is 1e-3."""
    hard = sdtw_numpy(q, r)[0]
    soft = float(np.asarray(sdtw_soft(q[None], r, gamma=1e-4))[0])
    np.testing.assert_allclose(soft, hard, rtol=1e-2, atol=1e-2)
